"""The prefill step's share of the chip's peak: the model flops the
admitted prompts need (weight GEMMs on real tokens, the LM head on the one
row that predicts the first token, causal attention) over the prefill
programs' device time in the traced span."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    adm = ctx.admitted_in(*span)
    t = ctx.trace.program_s.get("prefill", 0.0)
    if not adm or t <= 0:
        return None
    k = ctx.kernel("attention")
    z = ctx.sizes
    flops = sum(
        2 * ctx.layer_matmul_params() * len(r.prompt) + 2 * ctx.head_params()
        + z["layers"] * k.flops(z, len(r.prompt))
        for r in adm
    )
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
