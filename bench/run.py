#!/usr/bin/env python3
"""One benchmark run of one cell: load, warm up, measure, check, report.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
limits and per-layer metric readers are files under ``bench/`` found by
name (see ``bench/harness/spec.py``).  With ``--trace 0`` the result's
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of a span inside the window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
the numbers ``correct`` was judged by last, under ``checks``.  The last
lines of stderr repeat those numbers beside their limits.

Without a TPU holding the cell's chips the run exits 3 and prints no
result.  ``--cpu-rehearsal`` runs the program's smoke preset of the
configuration on the CPU with the mix's ``rehearsal`` sizes; it reports no
device metric.  ``--control`` judges the float8 control in the program's
place, so the run has to come out not correct (it shows the check can
fail; benchmark runs never pass it).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def devices_or_exit(chips: int, rehearsal: bool):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        sys.exit(3)
    if rehearsal:
        return devs[:1]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"this cell needs {chips} TPU chip(s); JAX sees "
            f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr,
        )
        sys.exit(3)
    return devs[:chips]


def enable_cache() -> None:
    """The persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; programs that compile in
    well under a second are cached too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class WindowEvents:
    """JAX's compile-path events (tracing, lowering, backend compiles,
    persistent-cache lookups) counted by name while ``active``.  A window
    that finds every program warm records none."""

    COMPILES = ("/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_hits")

    def __init__(self):
        from jax import monitoring

        self.active = False
        self.counts: dict[str, int] = {}
        monitoring.register_event_listener(self._on)
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *args, **kw):
        if self.active:
            self.counts[name] = self.counts.get(name, 0) + 1

    @property
    def compiles(self) -> int:
        return sum(self.counts.get(n, 0) for n in self.COMPILES)


class Tracer:
    def __init__(self, path: Path):
        self.path = path

    def start(self):
        import jax

        shutil.rmtree(self.path, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.path), profiler_options=opts)

    def stop(self):
        import jax

        jax.profiler.stop_trace()


def resolve(cell, rehearsal: bool):
    """(mix, program ModelConfig, sizes) of a cell; a rehearsal takes the
    program's smoke preset and the mix's ``rehearsal`` sizes."""
    from bench.harness import system

    mix = dict(cell.mix)
    arch = cell.config["program_arch"]
    if rehearsal:
        mix.update(mix["rehearsal"])
        cfg = system.program_config(arch, None, smoke=True)
        return mix, cfg, system.program_sizes(cfg)
    return mix, system.program_config(arch, cell.sizes), cell.sizes


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import spec

    cell = spec.load(args.workload)
    devs = devices_or_exit(cell.workload["chips"], args.cpu_rehearsal)
    marks = [("start_to_devices", time.perf_counter())]

    import jax
    import numpy as np

    from bench.harness import check, context, loop, system, traffic, weights

    enable_cache()
    mix, cfg, sizes = resolve(cell, args.cpu_rehearsal)
    w = weights.make(sizes, args.seed)
    jax.block_until_ready(w)
    marks.append(("weights", time.perf_counter()))
    server, sched = system.build(cfg, w, mix)
    marks.append(("build", time.perf_counter()))
    warm = system.warm(server, sched, mix, sizes["vocab"])
    marks.append(("warm", time.perf_counter()))
    gen = traffic.Generator(mix, sizes["vocab"], mix["server"]["max_cache"],
                            weights.rng(args.seed, 1))
    lo, hi = traffic.lengths(mix["prompt_tokens"])
    buckets = {s: server.prefill_seq_bucket(s) for s in range(lo, hi + 1)}
    events = WindowEvents()

    trace_at = None
    if args.trace:
        a = args.seconds * mix["trace_from"]
        trace_at = (a, a + min(mix["trace_seconds"], args.seconds - a))
    marks.append(("traffic_prep", time.perf_counter()))
    events.active = True
    win = loop.run(sched, gen, args.seconds, lead_s=mix["lead_s"],
                   cap_s=mix["cap_s"], trace_at=trace_at,
                   tracer=Tracer(TRACE_DIR))
    events.active = False
    # Set-up ends where the window opens: the lead-in traffic that brings
    # the server to steady state is set-up the cell's traffic needs.
    setup_s = win.t0 - T_START
    marks.append(("lead_in", win.t0))
    phases = {name: b - a for (name, b), (_, a)
              in zip(marks, [("", T_START)] + marks[:-1])}
    loop.finish(sched, win)

    dev = devs[0]
    stats = dev.memory_stats() or {}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    counted = [r for r in win.recs if r.counted]
    failed = [r for r in counted if r.failed is not None]
    late = np.asarray(win.lateness) * 1e3
    info = {
        "setup_s": setup_s,
        "setup_phases_s": phases,
        "warm": warm,
        "requests_counted": len(counted),
        "requests_total": len(win.recs),
        "generator_late_ms_p50": float(np.median(late)) if len(late) else None,
        "generator_late_ms_max": float(late.max()) if len(late) else None,
        "window_compiles": events.compiles,
        "window_jax_events": events.counts,
        "steps_in_window": len([s for s in win.steps if s[0] < win.t1]),
        "drain_s": win.end - win.t1,
        # The slowest pass of each loop phase (seconds, offset from the
        # window's open) and Python's garbage collections while it ran.
        "slowest_s": win.slowest,
        "gc": loop.gc_summary(win.gc_pauses),
        "server": dict(server.stats),
        "kv_pool": server.kv_pool.stats(),
        # Buffers the pool holds for reuse (private; a diagnosis of memory).
        "kv_pool_parked_bytes": sum(
            b.nbytes for bufs in server.kv_pool._free.values() for b in bufs
        ),
    }

    peaks = None if args.cpu_rehearsal else spec.peaks(dev.device_kind)
    ctx = context.Context(
        sizes=sizes, peaks=peaks, window=win,
        positions=list(sched.step_positions), prefill_bucket=buckets,
    )
    metrics, breakdown = {}, None
    if args.trace:
        from bench.harness import trace as trace_mod

        summary = trace_mod.reduce(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx.trace = summary
        breakdown = summary.breakdown()
        if summary.busy_s is not None and not args.cpu_rehearsal:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
        wanted = cell.per_layer()
    else:
        wanted = cell.end_to_end()
    for m in wanted:
        if args.cpu_rehearsal and m["source"] == "device_trace":
            continue
        if args.trace:
            value = spec.metric_reader(m["name"])(ctx)
        else:
            value = end_to_end(m["name"], win, setup_s)
        if value is not None:
            name = ("cpu." + m["name"]) if args.cpu_rehearsal else m["name"]
            metrics[name] = {"value": value, "unit": m["unit"]}

    # The program's state goes before the reference runs: a process's
    # memory peak never falls again.
    del sched, server, ctx
    gc.collect()
    ref_mod = cell.reference()
    chk = mix["check"]
    picked = check.sample(win.recs, weights.rng(args.seed, 2),
                          chk["served_tokens"], chk["max_requests"])
    t = time.perf_counter()
    ok, checks, diag = check.judge(ref_mod, sizes, w, picked, cell.limits,
                                   control=args.control)
    diag["reference_s"] = time.perf_counter() - t
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    correct = bool(ok and not failed)

    print(json.dumps({"info": info, "check": diag,
                      "failures": [r.failed for r in failed][:5]}))
    out = {
        "correct": correct,
        "attempted": len(counted),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def end_to_end(name: str, win, setup_s: float):
    """The end-to-end metrics, by the host clock, over the window."""
    import numpy as np

    from bench.harness import traffic

    counted = [r for r in win.recs if r.counted and r.failed is None]
    if name == "setup_s":
        return setup_s
    if name == "ttft_p90_ms":
        return traffic.percentile(
            [(r.stamps[0] - r.due) * 1e3 for r in counted if r.stamps], 90
        )
    if name == "itl_p95_ms":
        gaps = np.concatenate(
            [np.diff(r.stamps) for r in counted if len(r.stamps) > 1] or [[]]
        )
        return traffic.percentile(gaps * 1e3, 95)
    if name == "tokens_per_s":
        n = sum(1 for r in win.recs for t in r.stamps if win.t0 <= t < win.t1)
        return n / (win.t1 - win.t0)
    raise KeyError(f"no end-to-end metric named {name!r}")


if __name__ == "__main__":
    sys.exit(main())
