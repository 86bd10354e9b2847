"""Decode attention's share of its roofline: the least time the chip needs
for each decode launch in the traced span, at each active row's own cache
length (bench/kernels/decode_attention.py, per layer), over the device
time of the attention kernels inside the decode programs."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    launches = ctx.launches_in(*span)
    k = ctx.kernel("decode_attention")
    t = ctx.trace.kernel_time(k.PROGRAM, k.OP)
    if not launches or t <= 0:
        return None
    z, p = ctx.sizes, ctx.peaks
    need = 0.0
    for launch in launches:
        kv = [int(x) + 1 for x in launch["pos"]]
        need += max(k.flops(z, kv) / p["bf16_flops_per_s"],
                    k.bytes_moved(z, kv) / p["hbm_bytes_per_s"])
    return 100.0 * z["layers"] * need / t
