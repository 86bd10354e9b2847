"""Mean host time of one admission in the window, ms: ``admit_s`` of the
``admits`` in the scheduler's per-launch record (``sched.step_positions``),
timed by the stamps of its ``sched.admit`` span.  Steps that overlap the
traced span are left out, so the profiler's host cost does not enter.  A
record without ``admits`` gives nothing to read."""


def read(ctx):
    traced = ctx.window.trace
    admits = []
    for start, end, i, j in ctx.window_steps():
        if traced is not None and start < traced[1] and end > traced[0]:
            continue
        for launch in ctx.positions[i:j]:
            admits.extend(launch.get("admits", ()))
    if not admits:
        return None
    return 1e3 * sum(a["admit_s"] for a in admits) / len(admits)
