"""Share of the traced time in which the scheduler held work (the loop was
not waiting for an arrival) that no operation ran on the device."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.busy_active_s is None or tr.active_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_active_s / tr.active_s)
