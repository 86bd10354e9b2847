"""Prefill attention's share of its roofline: the least time the chip
needs for the causal attention of the prompts admitted in the traced span
(bench/kernels/attention.py, on real prompt lengths, per layer) over the
device time of the attention kernels inside the prefill programs."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    adm = ctx.admitted_in(*span)
    k = ctx.kernel("attention")
    t = ctx.trace.kernel_time(k.PROGRAM, k.OP)
    if not adm or t <= 0:
        return None
    z, p = ctx.sizes, ctx.peaks
    need = z["layers"] * sum(
        max(k.flops(z, len(r.prompt)) / p["bf16_flops_per_s"],
            k.bytes_moved(z, len(r.prompt)) / p["hbm_bytes_per_s"])
        for r in adm
    )
    return 100.0 * need / t
