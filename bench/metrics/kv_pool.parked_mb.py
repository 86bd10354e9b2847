"""The most device memory the KV pool held parked in the window, MB: the
largest ``kv_parked_bytes`` of the scheduler's per-launch record
(``sched.step_positions``) over the window's steps.  Parked buffers wait
for a lease of their shape; ones no request leases again only hold memory
that admissions need.  A record without the counter gives nothing to read."""


def read(ctx):
    parked = [p["kv_parked_bytes"] for start, end, i, j in ctx.window_steps()
              for p in ctx.positions[i:j] if "kv_parked_bytes" in p]
    if not parked:
        return None
    return max(parked) / 1e6
