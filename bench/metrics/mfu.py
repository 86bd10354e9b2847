"""The whole window's share of the chip's peak by the host clock: model
flops of the prompt tokens admitted and the output tokens produced in the
window (weight GEMMs per token, the LM head per output token, causal
attention per prompt and per decoded token) over the window's length."""


def read(ctx):
    w = ctx.window
    if ctx.peaks is None:
        return None
    z = ctx.sizes
    pre = ctx.kernel("attention")
    dec = ctx.kernel("decode_attention")
    flops = 0.0
    for r in w.recs:
        for i, t in enumerate(r.stamps):
            if not w.t0 <= t < w.t1:
                continue
            if i == 0:
                s = len(r.prompt)
                flops += (2 * ctx.layer_matmul_params() * s
                          + z["layers"] * pre.flops(z, s))
            else:
                kv = len(r.prompt) + i
                flops += (2 * ctx.layer_matmul_params()
                          + z["layers"] * dec.flops(z, [kv]))
            flops += 2 * ctx.head_params()
    return 100.0 * flops / (ctx.seconds * ctx.peaks["bf16_flops_per_s"])
