"""Device time of the decode programs in the traced span per decode
launch."""


def read(ctx):
    span = ctx.traced()
    if span is None:
        return None
    launches = ctx.launches_in(*span)
    t = ctx.trace.program_s.get("decode", 0.0)
    if not launches or t <= 0:
        return None
    return t * 1e3 / len(launches)
