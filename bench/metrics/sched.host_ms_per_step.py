"""Mean host time of one decode step in the window, ms, less the time the
host waited on the device: ``t1 - t0 - blocked_s`` of each entry of the
scheduler's per-launch record (``sched.step_positions``).  Steps that
overlap the traced span are left out, so the profiler's host cost does not
enter.  A record without the stamps gives nothing to read."""


def read(ctx):
    traced = ctx.window.trace
    host = []
    for start, end, i, j in ctx.window_steps():
        if traced is not None and start < traced[1] and end > traced[0]:
            continue
        host.extend(p["t1"] - p["t0"] - p["blocked_s"]
                    for p in ctx.positions[i:j] if "t1" in p)
    if not host:
        return None
    return 1e3 * sum(host) / len(host)
