"""Benchmark runner — one module per paper table/figure.

Each prints ``name,us_per_call,derived`` CSV lines (benchmarks/util.emit).

  bench_gemm             Fig. 12 / Table 5  operator-level speedups
  bench_offsample        Fig. 3  / Table 6  off-sample degradation
  bench_models           Fig. 13            model-level dynamic shapes
  bench_compile_time     §7.4               offline overhead
  bench_hierarchy        Fig. 15            static/dynamic ablation
  bench_analyzer         Table 7            hybrid analyzer configs
  bench_adaptive         Fig. 16            MXU/VPU adaptation
  bench_runtime_overhead Fig. 14            selection overhead
  bench_workloads        §4 generality      gemm/attention/conv one engine

``--json PATH`` writes the serving-trajectory snapshot (BENCH_serving.json
at the repo root, committed once per PR): unseen-shape dispatch overhead
(table vs argmin), the aligned-vs-unaligned hot-path wall-clock ratio and
copies/launches per call.  With ``--json`` the module loop is SKIPPED
unless a module filter is also given — CI's bench-smoke job runs
``run.py --smoke --json BENCH_serving.json`` and gates on the ratio.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback

MODULES = [
    "bench_compile_time",
    "bench_runtime_overhead",
    "bench_adaptive",
    "bench_analyzer",
    "bench_gemm",
    "bench_workloads",
    "bench_offsample",
    "bench_hierarchy",
    "bench_models",
]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("filter", nargs="?", default=None,
                    help="substring filter over benchmark module names")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced streams / analytical-only offline stage")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the BENCH_serving.json payload")
    args, passthrough = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.json and args.filter:
        # --json here means the SERVING payload; a module's own JSON flag
        # would be silently shadowed — force the unambiguous invocation.
        ap.error(
            "--json writes the serving payload and cannot be combined with "
            "a module filter; invoke the module directly for its own JSON "
            "(e.g. benchmarks/bench_workloads.py --json ...)"
        )

    failures = 0
    if args.filter is not None or args.json is None:
        # Module mains parse sys.argv themselves; strip the runner's own
        # arguments so they only see explicit passthrough flags (--smoke
        # is forwarded when a filter names the modules to run, since the
        # user is explicitly targeting modules that understand it).
        fwd = ["--smoke"] if args.smoke and args.filter else []
        sys.argv = [sys.argv[0]] + fwd + passthrough
        print("name,us_per_call,derived")
        for name in MODULES:
            if args.filter and args.filter not in name:
                continue
            t0 = time.perf_counter()
            print(f"# --- {name} ---", flush=True)
            try:
                importlib.import_module(f"benchmarks.{name}").main()
            except Exception:
                failures += 1
                traceback.print_exc()
            print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
                  flush=True)

    if args.json:
        from benchmarks.bench_workloads import serving_payload

        payload = serving_payload(args.smoke)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
