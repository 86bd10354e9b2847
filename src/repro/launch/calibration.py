"""Serving-side drivers for the background calibrator (core/calibrate.py).

Three entry points, in increasing autonomy:

  * :func:`warm_from_disk` — one-shot: load persisted calibrated tables
    (by hardware fingerprint) into an engine at startup; zero
    measurements, zero effect when nothing matching is on disk;
  * :class:`CalibrationDaemon` — a thread that donates budgeted slices
    whenever the engine has pending calibration work, for serving stacks
    WITHOUT a scheduler loop of their own (the continuous scheduler
    donates idle ``step()`` slices instead — see
    ``ContinuousScheduler._donate_idle_slice`` — and needs no daemon);
  * :func:`main` — the nightly-CI CLI: build an engine over the standard
    bench workloads, run a full (non-budgeted) calibration pass, and
    write the measured-vs-analytical report as JSON.  Exits nonzero if
    any calibrated table picks worse than the analytical selection on a
    measured bucket — the same invariant the bench-smoke gate enforces.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading

__all__ = ["warm_from_disk", "CalibrationDaemon", "run_calibration", "main"]


def warm_from_disk(engine) -> int:
    """Load persisted calibrated tables into ``engine``'s kernels; returns
    how many kernels were calibrated from disk (0 when calibration is off,
    nothing is persisted, or the fingerprint/lattice doesn't match)."""
    cal = engine.calibrator
    return cal.load() if cal is not None else 0


class CalibrationDaemon:
    """Background thread feeding budgeted slices to ``engine.calibrator``.

    ``interval_s`` is the sleep between slices — the coarse "is the
    process idle enough" knob for hosts without a scheduler loop.  The
    thread exits by itself once nothing is pending (new kernels re-arm it
    via :meth:`poke`).  ``stop()`` is prompt: at most one in-flight slice
    (bounded by the engine's ``calibration_budget_s``) completes after it.
    """

    def __init__(self, engine, interval_s: float = 1.0):
        self.engine = engine
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "CalibrationDaemon":
        if self.engine.calibrator is None:
            return self  # calibration off: never spawn the thread
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="vortex-calibration", daemon=True
            )
            self._thread.start()
        return self

    def poke(self) -> None:
        """Wake the daemon early (e.g. after compiling a new kernel)."""
        self._wake.set()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _loop(self) -> None:
        cal = self.engine.calibrator
        cal.load()  # restart path: persisted tables beat re-measuring
        while not self._stop.is_set():
            try:
                if cal.pending():
                    cal.run_slice()
                elif not self._wake.wait(timeout=self.interval_s * 10):
                    continue
            except Exception:
                return  # never let calibration kill a serving process
            self._wake.clear()
            self._stop.wait(timeout=self.interval_s)


def run_calibration(engine, *, load: bool = True) -> dict:
    """One full (non-budgeted) calibration pass over ``engine``'s current
    kernels: optionally load persisted tables first, measure the rest to
    completion, and return the measured-vs-analytical report plus the
    calibrator counters."""
    cal = engine.calibrator
    if cal is None:
        raise ValueError(
            'engine has calibration="off"; construct it with '
            'calibration="on-idle" or "eager-warmup"'
        )
    if load:
        cal.load()
    cal.run()
    return {"report": cal.report(), "stats": cal.stats()}


def main(argv: list[str] | None = None) -> int:
    """Nightly-CI calibration pass (see .github/workflows/ci.yml)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    from repro.vortex import Engine

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the calibration report as JSON")
    ap.add_argument("--cache-dir", default=None,
                    help="persistence dir (default: $VORTEX_CACHE_DIR "
                         "or ~/.cache/vortex)")
    ap.add_argument("--top-k", type=int, default=3)
    ap.add_argument("--budget-s", type=float, default=0.25)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced bucket set / round counts")
    args = ap.parse_args(argv)

    enable_compile_cache()
    eng = Engine(
        "host_cpu", empirical_levels=(),
        calibration="on-idle",
        calibration_top_k=args.top_k,
        calibration_budget_s=args.budget_s,
        calibration_cache_dir=args.cache_dir,
    )
    rng = np.random.default_rng(23)
    # The standard bench workload mix: gemm and conv2d calibrate (default
    # exec_key); attention is enrolled to prove the calibrator skips
    # exec-specialized kernels instead of mis-measuring them.
    eng.dispatch(
        "gemm",
        jnp.asarray(rng.normal(size=(33, 256)), jnp.float32),
        jnp.asarray(rng.normal(size=(256, 128)), jnp.float32),
    )
    eng.dispatch(
        "conv2d",
        jnp.asarray(rng.normal(size=(2, 14, 14, 8)), jnp.float32),
        jnp.asarray(rng.normal(size=(3, 3, 8, 16)), jnp.float32),
    )
    q = jnp.asarray(rng.normal(size=(1, 4, 67, 64)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(1, 2, 67, 64)), jnp.float32)
    eng.dispatch("attention", q, kv, kv)

    if args.smoke:
        import dataclasses

        cal = eng.calibrator
        cal.policy = dataclasses.replace(
            cal.policy, m_max=192, max_buckets=3, min_rounds=3,
            max_rounds=8, patience=2,
        )
    out = run_calibration(eng)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
    ok = True
    for kind, rep in out["report"].items():
        line = (
            f"{kind}: mode={rep['mode']} "
            f"agreement={rep['agreement_rate']:.2f} "
            f"pinned={rep['pinned_buckets']}/{rep['measured_buckets']} "
            f"never_worse={rep['never_worse_on_measured']}"
        )
        print(line)
        ok = ok and rep["never_worse_on_measured"]
    s = out["stats"]
    print(
        f"calibrated {s['applied']}/{s['kernels']} kernels "
        f"({s['skipped']} skipped) in {s['seconds']:.2f}s; "
        f"saved={s['saves']} loaded={s['loaded_from_disk']}"
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
