"""Share of prefilled rows that are bucket pad: 1 - real prompt tokens /
(batch bucket x sequence bucket), over the requests admitted in the
window, with the buckets the server chose for them
(``VortexServer.prefill_seq_bucket``; one row per request, batch bucket 1).
"""


def read(ctx):
    w = ctx.window
    adm = ctx.admitted_in(w.t0, w.t1)
    if not adm:
        return None
    real = sum(len(r.prompt) for r in adm)
    rows = sum(ctx.prefill_bucket[len(r.prompt)] for r in adm)
    return 100.0 * (1.0 - real / rows)
