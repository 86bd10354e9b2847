"""Mean active rows per decode launch over the window, from the
scheduler's own per-launch record (``sched.step_positions``)."""


def read(ctx):
    launches = []
    for start, end, i, j in ctx.window_steps():
        launches.extend(ctx.positions[i:j])
    if not launches:
        return None
    return sum(len(p["pos"]) for p in launches) / len(launches)
