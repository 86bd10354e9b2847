"""The padding-free hot path: masked-tail staging vs the zero-pad reference.

Acceptance surface of the staging contract (DESIGN.md §4):

  * every registered workload kind, at extents {1, bucket-1, bucket,
    bucket+1, prime}, is BIT-IDENTICAL between the staged hot path and the
    zero-pad reference path, on both executable impls;
  * poisoned staging — the engine-owned buffers' pad regions are filled
    with NaNs and the outputs must not move (correctness comes from the
    kernel masks, never from zero fill);
  * the copy/launch counters: an unaligned call is exactly ONE fused
    program launch plus its boundary copies, an aligned call is one launch
    with zero copies, and ``jnp.pad`` (the padded fallback) never fires;
  * a Selection that cannot be honored raises instead of being clamped;
  * the staging pool retains at most ``staging_pool_cap`` idle buffer sets
    (LRU eviction, MRU reuse) and eviction can never race an in-flight
    dispatch (checked-out sets are not in the free list).
"""
import dataclasses
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import _StagingPool
from repro.core.workloads import (
    AttentionWorkload,
    Conv2dWorkload,
    GemmWorkload,
    SelectionDeviationError,
)
from repro.vortex import Engine, EngineConfig

RNG = np.random.default_rng(11)


def _arr(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def engine(request):
    return Engine("host_cpu", empirical_levels=(), impl=request.param)


# One entry per registered workload kind: (workload params for
# engine.dispatch kwargs, args builder at a given dynamic extent m).
# Conv uses a 1x1 kernel on a (1, 1, m, cin) image so that the im2col
# extent is EXACTLY m — every probe extent is reachable.
def _gemm_args(m):
    return (_arr((m, 96)), _arr((96, 80)))


def _attn_args(m):
    return (_arr((2, 4, m, 32)), _arr((2, 2, m, 32)), _arr((2, 2, m, 32)))


def _decode_args(m):
    # One query row against a cache of length m; all m rows valid.
    return (_arr((2, 4, 1, 32)), _arr((2, 2, m, 32)), _arr((2, 2, m, 32)), m)


def _conv_args(m):
    return (_arr((1, 1, m, 5)), _arr((1, 1, 5, 7)))


def _grouped_args(m):
    # 6 groups over 3 experts (r = 2 groups per stack entry); ragged
    # per-group extents — several strictly below the capacity m — so the
    # per-group masked-tail contract is exercised at every probe extent.
    counts = np.clip(np.array([m, 1, 0, m - 1, 2, m]), 0, m).astype(np.int32)
    return (_arr((6, m, 96)), _arr((3, 96, 80)), jnp.asarray(counts))


KIND_CASES = [
    ("gemm", {}, _gemm_args),
    ("grouped_gemm", {}, _grouped_args),
    ("attention", {}, _attn_args),
    ("decode_attention", {}, _decode_args),
    ("conv2d", {}, _conv_args),
]


def _probe_extents(kern) -> list[int]:
    sel = kern.select(257)
    bucket = kern.workload.dynamic_bucket(sel)
    prime = 263
    return sorted({1, bucket - 1, bucket, bucket + 1, prime})


@pytest.mark.parametrize("kind,params,make", KIND_CASES,
                         ids=[c[0] for c in KIND_CASES])
def test_staged_bit_identical_to_padded_reference(engine, kind, params, make):
    """Staged hot path == zero-pad reference path, bitwise, at every
    boundary extent (1, bucket-1, bucket, bucket+1, prime)."""
    kern = engine.op_kernel(kind, make(8), params)
    for m in _probe_extents(kern):
        args = make(m)
        staged = np.asarray(kern(*args))
        padded = np.asarray(kern.call_padded(*args))
        np.testing.assert_array_equal(
            staged, padded,
            err_msg=f"{kind}: staged != padded at extent {m}",
        )
        ref = np.asarray(kern.workload.reference(*args))
        np.testing.assert_allclose(
            staged, ref, rtol=2e-3, atol=2e-3,
            err_msg=f"{kind}: staged != flat reference at extent {m}",
        )


@pytest.mark.parametrize("kind,params,make", KIND_CASES,
                         ids=[c[0] for c in KIND_CASES])
def test_poisoned_staging_buffers_do_not_leak(engine, kind, params, make):
    """Fill every staging buffer's pad region with NaNs (by poisoning the
    WHOLE buffer — staging then overwrites only the true extent) and assert
    the outputs are unaffected: correctness is the kernel's masking."""
    kern = engine.op_kernel(kind, make(8), params)
    bucket = kern.workload.dynamic_bucket(kern.select(257))
    m = bucket - 1  # unaligned: staging buffers are in play
    args = make(m)
    padded = np.asarray(kern.call_padded(*args))
    np.testing.assert_array_equal(np.asarray(kern(*args)), padded)
    poisoned = 0
    for entry in kern._exec_cache.values():
        for bufs in entry.pool.retained:
            for i, buf in bufs.items():
                bufs[i] = jnp.full_like(buf, jnp.nan)
                poisoned += 1
    assert poisoned >= 1, "unaligned dispatch must have created buffers"
    again = np.asarray(kern(*args))
    assert np.isfinite(again).all(), f"{kind}: NaN poison leaked"
    np.testing.assert_array_equal(
        again, padded, err_msg=f"{kind}: poisoned staging changed output"
    )


# One unaligned call per kind: (extent, stage copies, unstage copies).
UNALIGNED_COPIES = {
    "gemm": (61, 1, 1),  # only A is dynamic; B passes through
    "grouped_gemm": (61, 1, 1),  # only x stages; w and counts pass through
    "attention": (37, 3, 1),  # q, k and v all stage
    # Only the k/v cache buffers stage; out is (b, h, 1, d): nothing to slice.
    "decode_attention": (37, 2, 0),
    "conv2d": (61, 1, 1),  # the im2col columns stage; the weights pass through
}


@pytest.mark.parametrize("kind,params,make", KIND_CASES,
                         ids=[c[0] for c in KIND_CASES])
def test_unaligned_dispatch_is_one_launch_plus_boundary_copies(
    kind, params, make
):
    """The acceptance counter: an unaligned extent issues exactly one
    compiled-program launch (for grouped GEMM, one for all its ragged
    groups), one staging copy per dynamic operand, one output slice where
    the output is bucket-shaped — and never a jnp.pad fallback."""
    m, stage, unstage = UNALIGNED_COPIES[kind]
    eng = Engine("host_cpu", empirical_levels=())
    eng.dispatch(kind, *make(m), **params)
    d = eng.stats()[kind]
    assert d["calls"] == 1 and d["launches"] == 1
    assert d["unaligned_calls"] == 1 and d["aligned_calls"] == 0
    assert d["stage_copies"] == stage
    assert d["unstage_copies"] == unstage
    assert d["padded_calls"] == 0 and d["traced_calls"] == 0


def test_aligned_dispatch_is_one_launch_zero_copies():
    eng = Engine("host_cpu", empirical_levels=())
    kern = eng.op_kernel("gemm", _gemm_args(8), {})
    aligned_m = kern.select(257).padded_m
    eng.dispatch("gemm", *_gemm_args(aligned_m))
    d = eng.stats()["gemm"]
    assert d["aligned_calls"] == 1 and d["launches"] == 1
    assert d["stage_copies"] == 0 and d["unstage_copies"] == 0
    assert d["padded_calls"] == 0


def test_staging_buffers_are_reused_not_reallocated():
    """Two sequential unaligned calls in the same bucket reuse ONE pooled
    engine-owned buffer set (donated in place), and the executable cache
    does not grow."""
    eng = Engine("host_cpu", empirical_levels=())
    kern = eng.op_kernel("gemm", _gemm_args(8), {})
    bucket = kern.select(257).padded_m

    def pool_sets():
        return sum(len(e.pool.retained) for e in kern._exec_cache.values())

    kern(*_gemm_args(bucket - 1))
    entries = len(kern._exec_cache)
    assert pool_sets() == 1
    kern(*_gemm_args(bucket - 2))
    assert len(kern._exec_cache) == entries
    assert pool_sets() == 1  # the set was checked out, reused, returned
    assert kern.dispatch_stats.stage_copies == 2


def test_concurrent_same_bucket_dispatch_no_cross_talk():
    """N threads hammering ONE bucket concurrently: every output must be
    bit-identical to its own sequential reference — a shared/serialized
    staging buffer would interleave tenants' rows — and the pool retains
    at most its cap of buffer sets afterwards."""
    eng = Engine("host_cpu", empirical_levels=())
    kern = eng.op_kernel("gemm", _gemm_args(8), {})
    bucket = kern.select(257).padded_m
    m = bucket - 3
    b = _arr((96, 80))
    inputs = [
        jnp.asarray(
            np.random.default_rng(100 + i).normal(size=(m, 96)), jnp.float32
        )
        for i in range(8)
    ]
    kern(inputs[0], b)  # warm: compile once, outside the threads
    expected = [np.asarray(kern.call_padded(a, b)) for a in inputs]

    failures: list = []

    def worker(idx: int):
        for _ in range(16):
            out = np.asarray(kern(inputs[idx], b))
            if not np.array_equal(out, expected[idx]):
                failures.append(idx)
                return

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(len(inputs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, f"cross-talk detected for tenants {failures}"
    for entry in kern._exec_cache.values():
        assert len(entry.pool.retained) <= entry.pool.cap


def test_tracer_context_falls_back_to_functional_path():
    """Inside an enclosing jit the engine must not capture its own buffers:
    tracer calls take the zero-pad functional path (which XLA fuses into
    the surrounding program) and are counted as traced, not launched."""
    eng = Engine("host_cpu", empirical_levels=())
    a, b = _gemm_args(61)

    @jax.jit
    def outer(a, b):
        return eng.dispatch("gemm", a, b) * 2.0

    out = np.asarray(outer(a, b))
    ref = 2.0 * np.asarray(eng.dispatch("gemm", a, b))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    d = eng.stats()["gemm"]
    assert d["traced_calls"] == 1
    assert d["launches"] == 1  # only the eager reference dispatch launched


def test_staging_disabled_knob_matches_staged_outputs():
    """EngineConfig.staging=False forces the zero-pad reference path; the
    numbers must not move (it is a parity/debug knob, not a semantics
    switch)."""
    staged = Engine("host_cpu", empirical_levels=())
    padded = Engine("host_cpu", empirical_levels=(), staging=False)
    for m in (1, 61, 128):
        args = _gemm_args(m)
        np.testing.assert_array_equal(
            np.asarray(staged.dispatch("gemm", *args)),
            np.asarray(padded.dispatch("gemm", *args)),
        )
    d = padded.stats()["gemm"]
    assert d["launches"] == 0 and d["stage_copies"] == 0


def test_selection_deviation_raises_instead_of_clamping():
    """A Selection whose bucket is not a multiple of its own tile cannot be
    honored; the builder must refuse loudly, never clamp the tile."""
    eng = Engine("host_cpu", empirical_levels=())
    kern = eng.op_kernel("gemm", _gemm_args(8), {})
    sel = kern.select(64)
    bad = dataclasses.replace(sel, padded_m=sel.padded_m + 1)
    with pytest.raises(SelectionDeviationError, match="not a multiple"):
        kern.workload.build_executable(bad, impl="pallas")

    wl = AttentionWorkload(seq=None, head_dim=32)
    akern = eng.kernel_for(wl)
    asel = akern.select(64)
    abad = dataclasses.replace(
        asel, bucket=(asel.bucket[0] + 1,) + asel.bucket[1:]
    )
    with pytest.raises(SelectionDeviationError, match="not a multiple"):
        wl.build_executable(abad, impl="pallas")


def test_conv_stage_view_feeds_the_gemm_bucket():
    """Conv's im2col runs in stage_view; the staged buffer is the GEMM-view
    bucket, and the unaligned call still serves in one fused launch."""
    eng = Engine("host_cpu", empirical_levels=())
    x, w = _conv_args(61)
    out = eng.dispatch("conv2d", x, w)
    wl = Conv2dWorkload(m=None, cin=5, cout=7, kh=1, kw=1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(wl.reference(x, w)),
        rtol=1e-3, atol=1e-3,
    )
    d = eng.stats()["conv2d"]
    assert d["launches"] == 1 and d["stage_copies"] == 1
    assert d["padded_calls"] == 0


def test_gemm_workload_staged_shapes_contract():
    """The staged-shape tuple marks exactly the dynamic operands."""
    wl = GemmWorkload(M=None, N=80, K=96)
    eng = Engine("host_cpu", empirical_levels=())
    kern = eng.kernel_for(wl)
    a, b = _gemm_args(61)
    sel = kern.select(61)
    shapes = wl.staged_shapes(sel, a, b)
    assert shapes == ((sel.padded_m, 96), None)
    assert wl.runtime_scalars(sel, a, b) == (61,)


# ---------------------------------------------------------------------------
# Staging pool: LRU retention bound
# ---------------------------------------------------------------------------


def test_staging_pool_lru_cap_and_mru_reuse():
    pool = _StagingPool(cap=2)
    need = {0: ((4, 4), jnp.float32)}
    sets = [pool.acquire(need) for _ in range(3)]  # all checked out
    assert len({id(s) for s in sets}) == 3
    assert pool.retained == []  # in-flight sets are NOT in the free list
    for s in sets:
        pool.release(s)
    assert len(pool.retained) == 2  # LRU (first released) evicted
    assert pool.retained[-1] is sets[-1]
    assert all(s is not sets[0] for s in pool.retained)
    assert pool.acquire(need) is sets[-1]  # MRU-first reuse


def test_staging_pool_cap_zero_retains_nothing():
    pool = _StagingPool(cap=0)
    need = {0: ((4, 4), jnp.float32)}
    pool.release(pool.acquire(need))
    assert pool.retained == []


def test_engine_config_threads_pool_cap():
    e = Engine(EngineConfig(
        hardware="host_cpu", empirical_levels=(), staging_pool_cap=0,
    ))
    kern = e.op_kernel("gemm", (_arr((5, 16)), _arr((16, 8))), {})
    m = next(m for m in range(3, 257) if kern.select(m).padded_m > m)
    out = kern(_arr((m, 16)), _arr((16, 8)))
    assert out.shape == (m, 8)
    assert kern.dispatch_stats.stage_copies >= 1
    pools = [entry.pool for entry in kern._exec_cache.values()]
    assert pools and all(p.cap == 0 and p.retained == [] for p in pools)


def test_pool_eviction_never_races_in_flight():
    """cap=1 under concurrent unaligned dispatch: every result stays
    bit-identical to its serial reference (a set in use is checked out, so
    eviction can only ever drop idle sets) and at most one set is retained
    after the burst."""
    e = Engine(EngineConfig(
        hardware="host_cpu", empirical_levels=(), staging_pool_cap=1,
    ))
    kern = e.op_kernel("gemm", (_arr((5, 16)), _arr((16, 8))), {})
    m = next(m for m in range(3, 257) if kern.select(m).padded_m > m)
    w = _arr((16, 8))
    xs = [_arr((m, 16)) for _ in range(8)]
    refs = [np.asarray(kern(x, w)) for x in xs]

    errors: list = []

    def worker(i):
        try:
            for _ in range(4):
                got = np.asarray(kern(xs[i], w))
                np.testing.assert_array_equal(got, refs[i])
        except Exception as exc:  # pragma: no cover - failure path
            errors.append((i, exc))

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(len(xs))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:2]
    for entry in kern._exec_cache.values():
        assert len(entry.pool.retained) <= 1
