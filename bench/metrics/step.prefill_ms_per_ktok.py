"""Device time of the prefill programs in the traced span per thousand
real prompt tokens they prefilled."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    adm = ctx.admitted_in(*span)
    t = ctx.trace.program_s.get("prefill", 0.0)
    if not adm or t <= 0:
        return None
    return t * 1e3 / (sum(len(r.prompt) for r in adm) / 1e3)
