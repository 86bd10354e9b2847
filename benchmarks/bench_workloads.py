"""Workload-generic engine benchmark: dispatch overhead + cache behaviour.

The paper's runtime claim (Fig. 14) is that sample-free selection stays in
the microseconds regime and the executable cache stays bounded by the
lattice, not by the number of distinct runtime shapes.  This benchmark
drives GEMM, flash attention and Conv2D through ONE vortex Engine
session (repro.vortex) and
reports, per workload kind:

  * mean per-call dispatch overhead for UNSEEN shapes on the
    offline-materialized selection table vs the fused argmin path (the
    constant-time-dispatch speedup this repo tracks),
  * table/LRU/argmin serve counts over a repeated dynamic stream,
  * executable-cache entries vs calls served (bucket amortization),
  * steady-state wall-clock per call,
  * the padding-free hot path: steady-state wall-clock of UNALIGNED
    dispatch (staged masked-tail launch) vs ALIGNED dispatch (zero-copy
    launch) on the SAME bucket executable, plus copies/launches per call
    from the engine's DispatchStats — the Fig. 8 "padding confined to the
    outermost level" claim as a tracked ratio (CI gates it at 1.10x).

    PYTHONPATH=src:. python benchmarks/bench_workloads.py
    PYTHONPATH=src:. python benchmarks/bench_workloads.py \
        --smoke --json BENCH_dispatch.json   # CI smoke job

``--json`` writes BENCH_dispatch.json so the perf trajectory of the
serving hot path is tracked from run to run; ``benchmarks/run.py --json``
reuses :func:`serving_payload` to write the committed BENCH_serving.json
snapshot.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_hardware
from repro.core.timing import interleaved_minima, retry_best
from repro.vortex import Engine
from repro.core.selector import RuntimeSelector
from benchmarks.util import emit

# Dynamic streams: every shape appears twice (second pass measures cache
# behaviour), sizes deliberately prime/non-tile-aligned.
GEMM_MS = [5, 33, 63, 128, 200, 381]
ATTN_SEQS = [31, 67, 127, 199, 257]
CONV_BATCHES = [1, 2, 3, 5]

# Unseen-shape dispatch stream: distinct extents a serving process has
# never selected before (the case an LRU keyed by raw M cannot help with).
DISPATCH_STREAM = 400
DISPATCH_M_MAX = 2048


def _bench(name: str, calls) -> float:
    t0 = time.perf_counter()
    for fn in calls:
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / len(calls)


def _bench_dispatch(eng, hw, smoke: bool) -> dict[str, dict]:
    """Per kind: mean select overhead for unseen extents, table vs argmin.

    Fresh selectors over the SAME scored lattices the engine serves from,
    so both paths price the identical strategy space; every extent in the
    stream is unseen by construction (new selector, distinct extents).
    """
    stream_len = 60 if smoke else DISPATCH_STREAM
    rng = np.random.default_rng(42)
    ms = rng.permutation(np.arange(1, DISPATCH_M_MAX + 1))[:stream_len]
    ms = [int(m) for m in ms]

    results: dict[str, dict] = {}
    seen_kinds: set[str] = set()
    for kernel in eng._kernels.values():
        wl = kernel.workload
        if wl.kind in seen_kinds:
            continue
        seen_kinds.add(wl.kind)
        scored = kernel.selector.scored
        tabled = RuntimeSelector(hw, wl, scored, table_m_max=DISPATCH_M_MAX)
        argmin = RuntimeSelector(hw, wl, scored, table_m_max=0, cache_size=1)
        assert tabled.table is not None  # materialize offline, not in-loop

        # Best-of-N passes: the table loop's whole window is tens of us, so
        # a single scheduler preemption inside one pass would otherwise
        # dominate the (CI-gated) speedup ratio.
        repeats = 5

        def _best_of(select) -> float:
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for m in ms:
                    select(m)
                best = min(best, time.perf_counter() - t0)
            return best / len(ms) * 1e6

        table_us = _best_of(tabled.select)
        argmin_us = _best_of(argmin.select)

        assert tabled.stats.table_hits == len(ms) * repeats
        results[wl.kind] = {
            "table_us": table_us,
            "argmin_us": argmin_us,
            "speedup": argmin_us / max(table_us, 1e-9),
            "table_entries": len(tabled.table),
            "table_build_s": tabled.stats.table_build_seconds,
            "stream_len": len(ms),
        }
    return results


def _attn_aligned_seq(kern, s0: int) -> int:
    """The first extent >= s0 whose attention bucket pads NEITHER seq dim
    (pq == s == pkv): the zero-copy aligned case.  Walk bucket starts, not
    every integer."""
    s = s0
    for _ in range(64):
        sel = kern.select(s)
        if sel.bucket[0] == s and sel.bucket[2] == s:
            return s
        s = max(sel.bucket[0], sel.bucket[2])
    raise RuntimeError("no both-dims-aligned attention extent found")


def _same_entry_unaligned(kern, aligned_m: int) -> int:
    """The largest extent below ``aligned_m`` that the selector serves with
    the SAME strategy and bucket (hence the same compiled executable).

    The aligned/unaligned comparison must time one program two ways; an
    extent one short of the bucket can fall in a different breakpoint
    interval with a different tile, which would time two different kernels.
    """
    ref = kern.select(aligned_m)
    for m in range(aligned_m - 1, max(aligned_m - 64, 0), -1):
        sel = kern.select(m)
        if (
            sel.bucket == ref.bucket
            and sel.strategy.l1 == ref.strategy.l1
            and sel.backend == ref.backend
        ):
            return m
    raise RuntimeError(
        f"no same-executable unaligned extent below {aligned_m}"
    )


def _bench_hot_path(smoke: bool) -> dict[str, dict]:
    """Aligned vs unaligned steady-state dispatch on the SAME bucket.

    Per kind: the unaligned extent is bucket-1 (staging + masked launch +
    output slice), the aligned extent the bucket itself (zero-copy launch)
    — same compiled program, so the ratio isolates exactly the cost the
    padding-free path adds at the boundary.  Conv uses a 1x1-kernel im2col
    view so the probe extents are exactly reachable; its im2col transform
    runs in BOTH variants.
    """
    eng = Engine("host_cpu", empirical_levels=())
    rng = np.random.default_rng(3)
    # Short interleaved windows + adaptive min-vs-min stop (the
    # throttling defense lives in repro.core.timing, shared with the
    # background calibrator): sample until BOTH variants' minima have
    # stopped improving, then gate min-vs-min.
    min_rounds = 20 if smoke else 30
    max_rounds = 80 if smoke else 120

    def paired_us(aligned_call, unaligned_call):
        """(aligned_us, unaligned_us, min-vs-min ratio, raw samples) —
        phase-robust minima for the gate, with the per-round samples kept
        so a flaky gate can be diagnosed from the committed JSON (was the
        distribution bimodal throttling or a real shift?)."""
        t = interleaved_minima(
            [aligned_call, unaligned_call],
            inner=2, min_rounds=min_rounds, max_rounds=max_rounds,
            patience=10,
        )
        return (
            t.best_s[0] * 1e6,
            t.best_s[1] * 1e6,
            t.ratio(1, 0),
            {
                "aligned_us": list(t.samples_us[0]),
                "unaligned_us": list(t.samples_us[1]),
            },
        )

    def f32(shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    # Kernel compute must dominate the boundary copies for the ratio to
    # measure the contract rather than XLA's fixed per-launch overhead:
    # ratio-1 ~ c*(1/N + 1/K), so the static dims are sized in the
    # thousands (multi-ms kernels against sub-ms copies).
    cases: dict[str, tuple] = {}
    # gemm: any extent is reachable.
    gk = eng.op_kernel("gemm", (f32((8, 2304)), f32((2304, 2304))), {})
    gb = gk.select(381).padded_m
    gu = _same_entry_unaligned(gk, gb)
    wg = f32((2304, 2304))
    cases["gemm"] = (
        lambda a=f32((gb, 2304)): eng.dispatch("gemm", a, wg),
        lambda a=f32((gu, 2304)): eng.dispatch("gemm", a, wg),
    )
    # attention: aligned needs BOTH seq dims on their tile.
    q0 = (f32((2, 8, 8, 64)), f32((2, 4, 8, 64)), f32((2, 4, 8, 64)))
    ak = eng.op_kernel("attention", q0, {})
    sa = _attn_aligned_seq(ak, 199)
    su = _same_entry_unaligned(ak, sa)

    def attn_args(s):
        return (f32((2, 8, s, 64)), f32((2, 4, s, 64)), f32((2, 4, s, 64)))

    aa, au = attn_args(sa), attn_args(su)
    cases["attention"] = (
        lambda: eng.dispatch("attention", *aa),
        lambda: eng.dispatch("attention", *au),
    )
    # conv2d: 1x1 kernel -> im2col extent == the seq-like dim exactly.
    ck = eng.op_kernel(
        "conv2d", (f32((1, 1, 8, 1536)), f32((1, 1, 1536, 1536))), {}
    )
    cb = ck.select(500).padded_m
    cu = _same_entry_unaligned(ck, cb)
    wc = f32((1, 1, 1536, 1536))
    xa, xu = f32((1, 1, cb, 1536)), f32((1, 1, cu, 1536))
    cases["conv2d"] = (
        lambda: eng.dispatch("conv2d", xa, wc),
        lambda: eng.dispatch("conv2d", xu, wc),
    )

    results: dict[str, dict] = {}
    for kind, (aligned_call, unaligned_call) in cases.items():
        before = dict(eng.stats()[kind])
        # Up to 4 measurement attempts, keeping the best ratio: throttling
        # noise is strictly one-sided (it can only inflate a window), so
        # the min across attempts estimates the true boundary cost, while
        # a real regression fails every attempt.
        gate: dict = {}
        aligned_us, unaligned_us, ratio, samples = retry_best(
            lambda: paired_us(aligned_call, unaligned_call),
            attempts=4,
            accept=lambda r: r[2] <= 1.08,
            key=lambda r: r[2],
            stats=gate,
        )
        after = eng.stats()[kind]
        calls = after["calls"] - before["calls"]
        unaligned = after["unaligned_calls"] - before["unaligned_calls"]
        results[kind] = {
            "aligned_us": aligned_us,
            "unaligned_us": unaligned_us,
            "unaligned_over_aligned": ratio,
            # The gated attempt's raw per-round samples (same order the
            # minima were taken over) — the flake audit trail.
            "samples": samples,
            # Gate retry telemetry (DESIGN.md §11 robustness surface):
            # how many measurement attempts the gate burned, whether the
            # kept attempt passed, and which interleaved round each side's
            # min-vs-min winner came from.
            "gate_attempts": gate.get("attempts", 1),
            "gate_accepted": gate.get("accepted", True),
            "min_round": {
                side: int(np.argmin(vals)) for side, vals in samples.items()
            },
            # Zero-overhead guard: a no-fault bench must never touch the
            # degradation ladder.  CI asserts both stay 0.
            "fallbacks": after["fallbacks"] - before["fallbacks"],
            "quarantined": after["quarantined"] - before["quarantined"],
            "launches_per_call": (
                (after["launches"] - before["launches"]) / max(calls, 1)
            ),
            "copies_per_unaligned_call": (
                (
                    after["stage_copies"] + after["unstage_copies"]
                    - before["stage_copies"] - before["unstage_copies"]
                ) / max(unaligned, 1)
            ),
            "padded_calls": after["padded_calls"] - before["padded_calls"],
        }
    return results


def _bench_decode(smoke: bool) -> dict:
    """The serving decode section: drive VortexServer through a prompt
    whose generation crosses a kv-bucket boundary and report the per-token
    decode contract (one AOT launch per token, zero pad fallbacks, growth
    copies only at bucket transitions) plus steady-state wall-clock per
    token.  CI gates launches_per_token == 1 and padded_calls == 0."""
    from jax.sharding import Mesh
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    server = VortexServer(cfg, mesh, max_cache=256)
    rng = np.random.default_rng(17)
    s = 120
    kvb0 = server.kv_bucket(server.seq_bucket(s))
    max_new = min(max(kvb0 - s + 4, 8), 24)
    reqs = [
        Request(
            tokens=rng.integers(0, cfg.vocab, (b, s)).astype(np.int32),
            max_new=max_new,
        )
        for b in (1, 2)
    ]
    # Warm EVERY (batch, seq) shape once: the timed window below must hold
    # decode steps only — a first-time jit trace + AOT compile (seconds)
    # inside it would make us_per_token track compile noise, not decode.
    for req in reqs:
        server.generate(req)
    tokens_before = server.decode_stats.calls
    t0 = time.perf_counter()
    for req in reqs:
        server.generate(req)
    wall = time.perf_counter() - t0
    d = server.decode_stats
    tokens = d.calls
    timed = max(tokens - tokens_before, 1)
    # Engine-side REAL observables from the decode lowerings: padded == 0
    # means no zero-pad was baked into any compiled decode step (every
    # traced dispatch hit the bucket-aligned path).
    eng_decode = server.engine_dispatch_stats()["decode_attention"]
    return {
        "tokens": tokens,
        "launches_per_token": d.launches / max(tokens, 1),
        "padded_calls": d.padded_calls,
        "growth_copies": d.stage_copies,
        "bucket_transitions": d.unaligned_calls,
        "decode_exec_buckets": len(server._decode_exec),
        "decode_compiles": server.stats["decode_compiles"],
        "engine_traced_calls": eng_decode["traced_calls"],
        "engine_padded_calls": eng_decode["padded_calls"],
        "decode_us_per_token": wall / timed * 1e6,
    }


def _bench_continuous_batching(smoke: bool) -> dict:
    """The continuous-batching serving section: the SAME 16 requests
    served (a) serially through ``generate()`` and (b) through the
    admission-queue scheduler at concurrency 1/4/16, reporting tokens/sec
    per mode plus the batched-step contract — exactly one AOT launch per
    batched decode step, zero padded calls.  CI gates
    launches_per_batched_step == 1, padded_calls == 0 and
    speedup_at_16 >= 1.5 (the batch-bucket dimension amortizes the
    per-launch cost serial decode pays per request)."""
    from jax.sharding import Mesh
    from repro.launch.scheduler import ContinuousScheduler
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    server = VortexServer(cfg, mesh, max_cache=256)
    rng = np.random.default_rng(23)
    max_new = 8
    reqs = [
        Request(
            tokens=rng.integers(
                0, cfg.vocab, (1, int(s))
            ).astype(np.int32),
            max_new=max_new,
        )
        for s in rng.integers(30, 60, 16)
    ]
    total_tokens = len(reqs) * max_new

    def timed_serial() -> float:
        t0 = time.perf_counter()
        for req in reqs:
            server.generate(req)
        return time.perf_counter() - t0

    def counts() -> tuple[int, int]:
        """(decode programs looked up, engine calls run padded) so far."""
        st = server.stats
        padded = sum(
            s.get("padded_calls", 0)
            for s in server.engine_dispatch_stats().values()
        )
        return st["decode_compiles"] + st["decode_bucket_hits"], padded

    def timed_sched(batch_rows: int) -> tuple[float, int, int, int]:
        """(wall, batched steps, decode launches, padded calls)."""
        sched = ContinuousScheduler(server, batch_rows=batch_rows)
        launches0, padded0 = counts()
        t0 = time.perf_counter()
        for req in reqs:
            sched.submit(req)
        res = sched.drain()
        wall = time.perf_counter() - t0
        assert len(res) == len(reqs)
        sched.close()
        launches, padded = counts()
        return (wall, sched.stats["steps"], launches - launches0,
                padded - padded0)

    timed_serial()  # warm every prefill/decode executable
    serial_wall = timed_serial()
    out: dict = {
        "requests": len(reqs),
        "max_new": max_new,
        "serial_tokens_per_s": total_tokens / serial_wall,
        "concurrency": {},
    }
    worst_lps, padded = 0.0, 0
    for c in (1, 4, 16):
        timed_sched(c)  # warm the (c, kvb) mixed-progress programs
        wall, steps, launches, padded_c = timed_sched(c)
        lps = launches / max(steps, 1)
        worst_lps = max(worst_lps, lps)
        padded += padded_c
        out["concurrency"][str(c)] = {
            "tokens_per_s": total_tokens / wall,
            "batched_steps": steps,
            "launches_per_batched_step": lps,
            "padded_calls": padded_c,
        }
    out["launches_per_batched_step"] = worst_lps
    out["padded_calls"] = padded
    out["speedup_at_16"] = (
        out["concurrency"]["16"]["tokens_per_s"]
        / out["serial_tokens_per_s"]
    )
    pool = server.engine_dispatch_stats()["kv_pool"]
    out["kv_pool"] = pool
    assert pool["leases_active"] == 0, pool
    return out


def _bench_moe(smoke: bool) -> dict:
    """The MoE serving section: a granite_moe-shaped expert-FFN layer
    served engine-vs-dense.  With a session installed, ``_expert_ffn``
    collapses its three dense ``(g,E,C,·)`` einsums into three grouped-GEMM
    dispatches — each is ONE bucketed masked-tail launch covering all E
    experts, with the per-expert token counts (a routing outcome, not an
    input length) riding in as the runtime extent vector.

    ``launches_per_moe_layer`` is normalized per projection (three
    projections — w_in, w_gate, w_out — per layer call): 1.0 means every
    projection ran as exactly ONE grouped launch for all experts, never E
    per-expert launches and never a pad fallback.  CI gates
    ``launches_per_moe_layer == 1 && padded_calls == 0`` plus bit-identity
    vs the dense-einsum fallback.
    """
    import dataclasses

    import repro.vortex as vortex
    from repro.configs.granite_moe_1b import CONFIG, SMOKE
    from repro.models import layers as Lyr
    from repro.models.partitioning import AxisRules

    rules = AxisRules(rules={}, mesh_axes=())
    if smoke:
        cfg = SMOKE
        b, s = 2, 33
    else:
        # granite_moe_1b's expert geometry (32 experts, top-8) at a width
        # a CPU runner can turn around; the launch accounting is what the
        # gate consumes, not the absolute wall-clock.
        cfg = dataclasses.replace(
            CONFIG, d_model=256,
            moe=dataclasses.replace(CONFIG.moe, d_ff_expert=128),
        )
        b, s = 2, 96
    m = cfg.moe
    rng = np.random.default_rng(41)
    mk = lambda *sh: jnp.asarray(rng.normal(size=sh) * 0.05, jnp.float32)
    p = {
        "router": mk(cfg.d_model, m.num_experts),
        "w_in": mk(m.num_experts, cfg.d_model, m.d_ff_expert),
        "w_gate": mk(m.num_experts, cfg.d_model, m.d_ff_expert),
        "w_out": mk(m.num_experts, m.d_ff_expert, cfg.d_model),
    }
    x = jnp.asarray(rng.normal(size=(b, s, cfg.d_model)), jnp.float32)

    layer_call = lambda: Lyr.moe_forward(p, x, cfg, rules)[0]
    y_dense = jax.block_until_ready(layer_call())
    rounds = dict(
        inner=1, min_rounds=3 if smoke else 10,
        max_rounds=10 if smoke else 40, patience=3,
    )
    # Dense timing OUTSIDE the session — with one installed, the same
    # layer call routes through the engine, so the two sides are the same
    # moe_forward with/without the grouped-GEMM dispatch path.
    dense_us = interleaved_minima([layer_call], **rounds).best_s[0] * 1e6

    eng = Engine("host_cpu", empirical_levels=(() if smoke else None))
    with vortex.use(eng):
        y_eng = jax.block_until_ready(layer_call())  # warm: compile + AOT
        before = {
            k: eng.stats()["grouped_gemm"][k]
            for k in ("launches", "padded_calls", "stage_copies")
        }
        layer_calls = 4 if smoke else 8
        for _ in range(layer_calls):
            jax.block_until_ready(layer_call())
        after = {
            k: eng.stats()["grouped_gemm"][k]
            for k in ("launches", "padded_calls", "stage_copies")
        }
        engine_us = interleaved_minima([layer_call], **rounds).best_s[0] * 1e6

    launches = after["launches"] - before["launches"]
    max_abs = float(np.max(np.abs(np.asarray(y_eng) - np.asarray(y_dense))))
    dropped = float(Lyr.moe_forward(p, x, cfg, rules)[2])
    return {
        "experts": m.num_experts,
        "top_k": m.top_k,
        "d_ff_expert": m.d_ff_expert,
        "tokens": b * s,
        "layer_calls": layer_calls,
        # per projection: 3 grouped-GEMM dispatches per layer call, each
        # must be exactly one launch for all experts.
        "launches_per_moe_layer": launches / (3 * layer_calls),
        "padded_calls": after["padded_calls"],
        "stage_copies": after["stage_copies"] - before["stage_copies"],
        "dropped_frac": dropped,
        "engine_us_per_layer": engine_us,
        "dense_us_per_layer": dense_us,
        "max_abs_diff_vs_dense": max_abs,
        "bit_identical_to_dense": max_abs == 0.0,
    }


def _bench_calibration(smoke: bool) -> dict:
    """Background-calibration quality section (BENCH_dispatch.json).

    A small gemm engine runs one full calibration pass (measure top-K
    candidates per bucket, fit/re-rank, atomic table swap), then reports
    measured-vs-analytical agreement and the calibrated pick's regret vs
    the measured-best candidate per bucket.  CI gates two invariants:

      * ``never_worse_on_measured`` — on every measured bucket the
        calibrated table's pick is at least as fast (by the measurements)
        as the analytical pick;
      * the persistence roundtrip — a FRESH engine loads the persisted
        tables by hardware fingerprint with ZERO re-measurements.
    """
    import dataclasses
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="vortex-bench-calib-")

    def fresh_engine() -> Engine:
        eng = Engine(
            "host_cpu", empirical_levels=(),
            calibration="on-idle",
            calibration_top_k=2 if smoke else 3,
            calibration_cache_dir=cache_dir,
        )
        rng = np.random.default_rng(11)
        eng.dispatch(
            "gemm",
            jnp.asarray(rng.normal(size=(33, 256)), jnp.float32),
            jnp.asarray(rng.normal(size=(256, 128)), jnp.float32),
        )
        return eng

    def tune(cal) -> None:
        # Bench-sized measurement plan; the policy only steers NEW
        # kernel-state planning, so set it before the first slice.
        cal.policy = dataclasses.replace(
            cal.policy,
            m_max=192 if smoke else 512,
            max_buckets=3 if smoke else 6,
            min_rounds=3 if smoke else 8,
            max_rounds=8 if smoke else 24,
            patience=2 if smoke else 4,
        )

    eng = fresh_engine()
    cal = eng.calibrator
    tune(cal)
    t0 = time.perf_counter()
    cal.run()
    calibrate_s = time.perf_counter() - t0
    report = cal.report()

    # Persistence roundtrip: fresh engine, same fingerprint -> the tables
    # load from disk and nothing is re-measured.
    eng2 = fresh_engine()
    cal2 = eng2.calibrator
    tune(cal2)
    loaded = cal2.load()
    roundtrip = {
        "loaded": loaded,
        "re_measurements": cal2.counters["measurements"],
        "pending_after_load": cal2.pending(),
        "table_swaps": cal2.counters["table_swaps"],
    }
    return {
        "kinds": report,
        "roundtrip": roundtrip,
        "calibrate_s": calibrate_s,
        "stats": cal.stats(),
    }


def serving_payload(smoke: bool) -> dict:
    """The BENCH_serving.json payload (benchmarks/run.py --json): dispatch
    overhead on unseen shapes, the aligned-vs-unaligned hot-path ratio and
    copies/launches per call (with raw per-round samples), the serving
    decode contract, and the MoE grouped-GEMM contract (one launch per
    projection for all experts)."""
    hardware = "host_cpu"
    eng = Engine(hardware, empirical_levels=(() if smoke else None))
    hw = get_hardware(hardware)
    rng = np.random.default_rng(0)
    # Touch one signature per kind so _bench_dispatch sees all three.
    eng.dispatch(
        "gemm",
        jnp.asarray(rng.normal(size=(33, 768)), jnp.float32),
        jnp.asarray(rng.normal(size=(768, 768)), jnp.float32),
    )
    q = jnp.asarray(rng.normal(size=(1, 4, 67, 64)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(1, 2, 67, 64)), jnp.float32)
    eng.dispatch("attention", q, kv, kv)
    eng.dispatch(
        "conv2d",
        jnp.asarray(rng.normal(size=(2, 28, 28, 16)), jnp.float32),
        jnp.asarray(rng.normal(size=(3, 3, 16, 32)), jnp.float32),
    )
    return {
        "mode": "smoke" if smoke else "full",
        "dispatch": _bench_dispatch(eng, hw, smoke),
        "hot_path": _bench_hot_path(smoke),
        "decode": _bench_decode(smoke),
        "continuous_batching": _bench_continuous_batching(smoke),
        "moe": _bench_moe(smoke),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced stream + analytical-only offline stage (CI)",
    )
    ap.add_argument(
        "--json", metavar="PATH", default=None,
        help="write per-kind dispatch-overhead results as JSON",
    )
    ap.add_argument(
        "--no-hot-path", action="store_true",
        help="skip the (minutes-long) aligned-vs-unaligned hot-path "
        "measurement — CI runs it separately via run.py --json and must "
        "not pay for it twice",
    )
    args = ap.parse_args()

    hardware = "host_cpu"
    eng = Engine(
        hardware, empirical_levels=(() if args.smoke else None)
    )
    hw = get_hardware(hardware)
    rng = np.random.default_rng(0)
    gemm_ms = GEMM_MS[:3] if args.smoke else GEMM_MS
    attn_seqs = ATTN_SEQS[:2] if args.smoke else ATTN_SEQS
    conv_batches = CONV_BATCHES[:2] if args.smoke else CONV_BATCHES

    # --- gemm ----------------------------------------------------------
    N, K = 768, 768
    b = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    mats = {
        m: jnp.asarray(rng.normal(size=(m, K)), jnp.float32) for m in gemm_ms
    }
    gemm_calls = [
        (lambda a=mats[m]: eng.dispatch("gemm", a, b)) for m in gemm_ms * 2
    ]
    gemm_us = _bench("gemm", gemm_calls) * 1e6

    # --- attention -----------------------------------------------------
    qkv = {}
    for s in attn_seqs:
        qkv[s] = (
            jnp.asarray(rng.normal(size=(1, 8, s, 64)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 4, s, 64)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 4, s, 64)), jnp.float32),
        )
    attn_calls = [
        (lambda t=qkv[s]: eng.dispatch("attention", *t)) for s in attn_seqs * 2
    ]
    attn_us = _bench("attention", attn_calls) * 1e6

    # --- conv2d --------------------------------------------------------
    wconv = jnp.asarray(rng.normal(size=(3, 3, 16, 32)), jnp.float32)
    xs = {
        bs: jnp.asarray(rng.normal(size=(bs, 28, 28, 16)), jnp.float32)
        for bs in conv_batches
    }
    conv_calls = [
        (lambda x=xs[bs]: eng.dispatch("conv2d", x, wconv)) for bs in conv_batches * 2
    ]
    conv_us = _bench("conv2d", conv_calls) * 1e6

    # --- serving-path report -------------------------------------------
    wall = {"gemm": gemm_us, "attention": attn_us, "conv2d": conv_us}
    stats = eng.stats()
    stats.pop("calibration", None)  # engine-level section, not a kind
    for kind, s in stats.items():
        selects = max(s["selects"], 1)
        misses = s["select_argmin_misses"]
        # mean argmin-miss latency is only a measurement when misses exist
        # (with the table on, a typical stream never misses).
        miss_us = f"{s['select_us_sum'] / misses:.1f}" if misses else "n/a"
        emit(
            f"workloads/{kind}", wall[kind],
            f"argmin_miss_us={miss_us};"
            f"table_hit_rate={s['select_table_hits'] / selects:.2f};"
            f"lru_hits={s['select_lru_hits']};"
            f"argmin_misses={s['select_argmin_misses']};"
            f"table_entries={s['table_entries']};"
            f"exec_entries={s['exec_entries']};"
            f"exec_hits={s['exec_hits']};"
            f"compile_s={s['compile_seconds']:.2f}",
        )
    total_exec = sum(s["exec_entries"] for s in stats.values())
    total_calls = sum(s["exec_hits"] for s in stats.values())
    emit(
        "workloads/summary", 0.0,
        f"executables={total_exec};calls_served={total_calls};"
        f"amortization={total_calls / max(total_exec, 1):.1f}x",
    )

    # --- dispatch overhead: table vs argmin on unseen shapes ------------
    dispatch = _bench_dispatch(eng, hw, args.smoke)
    for kind, d in dispatch.items():
        emit(
            f"dispatch/{kind}", d["table_us"],
            f"argmin_us={d['argmin_us']:.1f};speedup={d['speedup']:.1f}x;"
            f"table_entries={d['table_entries']};"
            f"table_build_ms={d['table_build_s'] * 1e3:.1f}",
        )

    # --- padding-free hot path: aligned vs unaligned same-bucket --------
    hot = {} if args.no_hot_path else _bench_hot_path(args.smoke)
    for kind, h in hot.items():
        emit(
            f"hot_path/{kind}", h["unaligned_us"],
            f"aligned_us={h['aligned_us']:.1f};"
            f"ratio={h['unaligned_over_aligned']:.3f};"
            f"launches_per_call={h['launches_per_call']:.2f};"
            f"copies_per_unaligned_call={h['copies_per_unaligned_call']:.1f};"
            f"padded_calls={h['padded_calls']}",
        )

    # --- background calibration: measured vs analytical -----------------
    calibration = _bench_calibration(args.smoke)
    for kind, c in calibration["kinds"].items():
        emit(
            f"calibration/{kind}", c["mean_regret_vs_best"] * 1e2,
            f"mode={c['mode']};agreement={c['agreement_rate']:.2f};"
            f"pinned={c['pinned_buckets']}/{c['measured_buckets']};"
            f"never_worse={c['never_worse_on_measured']};"
            f"residual={c['residual']:.3f}",
        )
    rt = calibration["roundtrip"]
    emit(
        "calibration/roundtrip", calibration["calibrate_s"] * 1e6,
        f"loaded={rt['loaded']};re_measurements={rt['re_measurements']};"
        f"pending_after_load={rt['pending_after_load']}",
    )

    if args.json:
        payload = {
            "dispatch": dispatch,
            "hot_path": hot,
            "calibration": calibration,
            "serving": {
                kind: {
                    "selects": s["selects"],
                    "table_hit_rate": (
                        s["select_table_hits"] / max(s["selects"], 1)
                    ),
                    "argmin_misses": s["select_argmin_misses"],
                    "exec_entries": s["exec_entries"],
                    "launches": s["launches"],
                    "stage_copies": s["stage_copies"],
                    "unstage_copies": s["unstage_copies"],
                    "padded_calls": s["padded_calls"],
                    "wall_us_per_call": wall[kind],
                }
                for kind, s in stats.items()
            },
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
