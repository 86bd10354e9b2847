"""The decode step's share of the chip's peak: the model flops each launch
in the traced span needs for its active rows (weight GEMMs and LM head per
row, attention at each row's cache length) over the decode programs'
device time."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    launches = ctx.launches_in(*span)
    t = ctx.trace.program_s.get("decode", 0.0)
    if not launches or t <= 0:
        return None
    k = ctx.kernel("decode_attention")
    z = ctx.sizes
    per_row = 2 * (ctx.layer_matmul_params() + ctx.head_params())
    flops = sum(
        per_row * len(launch["pos"])
        + z["layers"] * k.flops(z, [int(x) + 1 for x in launch["pos"]])
        for launch in launches
    )
    return 100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"])
