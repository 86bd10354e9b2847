"""Continuous batching must be invisible in the outputs.

The step scheduler packs concurrent requests into the batch-bucket
dimension and advances them with ONE mixed-progress decode launch per
step — rows at different kv positions, free slots riding at pos 0, the
shared cache leased from the kv-bucket pool.  Every test here compares
against the serial ``generate()`` path on the SAME server (identical
params, identical prefill executables): per-request token sequences must
match exactly.

Structural contract, asserted alongside identity: one
``step_positions`` record per batched decode launch (``stats["steps"]``),
and the pool's lease ledger settles to 0 — on retirement, on
``generate()`` exceptions, and after ``close()``.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.launch.scheduler import (
    ContinuousScheduler,
    batched_decode_supported,
)
from repro.launch.serve import Request, VortexServer
from repro.models.registry import get_smoke_config

MAX_CACHE = 256


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke_config("paper-gpt2-124m")
    return VortexServer(cfg, make_host_mesh(), max_cache=MAX_CACHE)


def _requests(rng, n, *, lo=4, hi=60, max_new=12, rows=1):
    return [
        Request(
            tokens=rng.integers(0, 512, (rows, int(s))).astype(np.int32),
            max_new=max_new,
        )
        for s in rng.integers(lo, hi, n)
    ]


def _serial(server, reqs):
    return [server.generate(r) for r in reqs]


def _assert_clean(server, sched):
    assert len(sched.step_positions) == sched.stats["steps"]
    sched.close()
    pool = server.engine_dispatch_stats()["kv_pool"]
    assert pool["leases_active"] == 0, pool


def test_batched_matches_serial_token_identical(server):
    """Five concurrent single-row requests at mixed prompt lengths, four
    slots: batched greedy decode must reproduce the serial tokens for
    every request, with at least one genuinely mixed-progress step."""
    rng = np.random.default_rng(0)
    reqs = _requests(rng, 5, max_new=12)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    rids = [sched.submit(r) for r in reqs]
    res = sched.drain()
    for rid, ser in zip(rids, serial):
        assert np.array_equal(res[rid], ser), rid
    mixed = [
        s for s in sched.step_positions
        if len(set(s["pos"].tolist())) >= 2
    ]
    assert mixed, "no step ever served two rows at different positions"
    _assert_clean(server, sched)


def test_bucket_boundary_staggering(server):
    """Rows at kvb-1 / kvb / kvb+1 in ONE step: three prompts at adjacent
    lengths march across the initial kv bucket boundary in lockstep, so
    one launch serves a row still inside the old bucket, one exactly at
    it, and one past it — and the outputs still match serial exactly."""
    rng = np.random.default_rng(1)
    base = 119
    reqs = [
        Request(
            tokens=rng.integers(0, 512, (1, base + d)).astype(np.int32),
            max_new=16,
        )
        for d in range(3)
    ]
    boundary = server.kv_bucket(server.seq_bucket(base + 2))
    assert base + 2 < boundary <= base + 16, (
        "prompt lengths no longer straddle the first kv bucket; "
        f"retune base for boundary {boundary}"
    )
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    rids = [sched.submit(r) for r in reqs]
    res = sched.drain()
    for rid, ser in zip(rids, serial):
        assert np.array_equal(res[rid], ser), rid
    straddled = [
        s for s in sched.step_positions
        if {boundary - 1, boundary, boundary + 1} <= set(s["pos"].tolist())
    ]
    assert straddled, (
        f"no step served rows at {boundary - 1}/{boundary}/{boundary + 1}; "
        f"steps: {[sorted(s['pos'].tolist()) for s in sched.step_positions]}"
    )
    # The straddling step ran at the GROWN bucket (one program, one shape).
    assert all(s["kvb"] > boundary for s in straddled)
    _assert_clean(server, sched)


def test_nan_poisoned_pool_buffers_never_read(server):
    """Park NaN-poisoned buffers of exactly the shapes the scheduler will
    lease (shared cache + growth): if ANY stale tail byte were read, the
    greedy argmax would diverge from serial.  It must not."""
    from repro.models.model import abstract_cache

    rng = np.random.default_rng(2)
    reqs = _requests(rng, 4, lo=100, hi=130, max_new=16)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    # Poison: one parked buffer per leaf shape at the initial bucket AND
    # at every growable bucket up to max_cache.
    pool = server.kv_pool
    kvb = server.kv_bucket(server.seq_bucket(129))
    buckets = {kvb}
    while kvb < MAX_CACHE:
        kvb = server._grown_kv_bucket(kvb, kvb + 1)
        buckets.add(kvb)
    for b in buckets:
        spec = abstract_cache(server.cfg, sched.batch_rows, b)
        for entry in spec.values():
            for leaf in entry.values():
                key = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
                pool._free.setdefault(key, []).append(
                    jnp.full(leaf.shape, jnp.nan, leaf.dtype)
                )
    rids = [sched.submit(r) for r in reqs]
    res = sched.drain()
    hits_after = pool.stats()["lease_hits"]
    assert hits_after > 0, "poisoned buffers were never leased — test inert"
    for rid, ser in zip(rids, serial):
        assert np.array_equal(res[rid], ser), rid
        assert not np.isnan(res[rid].astype(np.float64)).any()
    sched.close()
    assert pool.stats()["leases_active"] == 0


def test_multirow_request_and_stop_token(server):
    """A 2-row request occupies two slots and reassembles in submission
    order; a stop token retires its row early, padding the tail."""
    rng = np.random.default_rng(3)
    req = Request(
        tokens=rng.integers(0, 512, (2, 24)).astype(np.int32), max_new=10
    )
    serial = server.generate(req)

    sched = ContinuousScheduler(server, batch_rows=4)
    rid = sched.submit(req)
    res = sched.drain()
    assert np.array_equal(res[rid], serial)

    # Early stop: pick the token serial emits at step 3 of row 0 as the
    # stop token; the batched row must retire there and pad with it.
    stop = int(serial[0, 3])
    req2 = Request(tokens=req.tokens[:1], max_new=10, stop=stop)
    rid2 = sched.submit(req2)
    res2 = sched.drain()
    out = res2[rid2][0]
    cut = int(np.argmax(out == stop))
    assert out[cut] == stop and (out[cut:] == stop).all()
    assert np.array_equal(out[:cut], serial[0, :cut])
    _assert_clean(server, sched)


def test_admission_rejects_at_submit(server):
    """Oversized requests fail AT SUBMIT with a queue-level error — not
    mid-decode — and an over-wide request names the slot limit."""
    sched = ContinuousScheduler(server, batch_rows=4)
    big = Request(
        tokens=np.zeros((1, 200), np.int32), max_new=MAX_CACHE,
    )
    with pytest.raises(ValueError, match="admission refused"):
        sched.submit(big)
    wide = Request(tokens=np.zeros((8, 8), np.int32), max_new=2)
    with pytest.raises(ValueError, match="batch_rows"):
        sched.submit(wide)
    assert sched.drain() == {}
    _assert_clean(server, sched)


def test_generate_exception_releases_leases(server):
    """A decode failure mid-``generate`` must still settle every pool
    lease (the try/finally arm), or concurrent serving leaks buffers."""
    rng = np.random.default_rng(4)
    req = Request(
        tokens=rng.integers(0, 512, (1, 20)).astype(np.int32), max_new=8
    )
    before = server.kv_pool.stats()["leases_active"]
    orig = server._decode_exec_for
    calls = {"n": 0}

    def boom(bp, kvb):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("injected decode failure")
        return orig(bp, kvb)

    server._decode_exec_for = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            server.generate(req)
    finally:
        server._decode_exec_for = orig
    assert server.kv_pool.stats()["leases_active"] == before


def test_unsupported_arch_refused():
    """Non-attention decoders keep the serial path; the scheduler says so
    up front instead of corrupting a shared cache."""
    cfg = get_smoke_config("falcon-mamba-7b")
    assert not batched_decode_supported(cfg)
    srv = VortexServer(cfg, make_host_mesh(), max_cache=64)
    with pytest.raises(ValueError, match="serial generate"):
        ContinuousScheduler(srv, batch_rows=2)


def test_admit_fault_isolated_to_one_request(server):
    """A pool-lease fault while admitting resolves THAT request to a
    typed error; every other request completes token-identical to
    serial and the lease ledger settles."""
    from repro.launch.serve import RequestError
    from repro.runtime import faults

    rng = np.random.default_rng(6)
    reqs = _requests(rng, 3, max_new=6)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    plan = faults.FaultPlan({"pool_lease": [1]})
    with faults.installed(plan):
        rids = [sched.submit(r) for r in reqs]
        res = sched.drain()
    assert plan.fired == [("pool_lease", 1)]
    assert set(res) == set(rids)
    err = res[rids[0]]
    assert isinstance(err, RequestError)
    assert err.stage == "admit" and err.request_id == rids[0]
    for rid, ser in zip(rids[1:], serial[1:]):
        assert np.array_equal(res[rid], ser), rid
    assert sched.stats["request_errors"] == 1
    _assert_clean(server, sched)


def test_decode_fault_fails_sharers_loop_stays_serviceable(server):
    """A fault in the mixed-progress decode launch fails exactly the
    rows that shared it — and the NEXT submission on the same scheduler
    decodes normally (the step loop and shared cache survive)."""
    from repro.launch.serve import RequestError
    from repro.runtime import faults

    rng = np.random.default_rng(7)
    reqs = _requests(rng, 2, max_new=6)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    # scheduler_step occurrences: admit, admit, then the decode launch.
    plan = faults.FaultPlan({"scheduler_step": [3]})
    with faults.installed(plan):
        rids = [sched.submit(r) for r in reqs]
        res = sched.drain()
        assert plan.fired == [("scheduler_step", 3)]
        for rid in rids:
            assert isinstance(res[rid], RequestError)
            assert res[rid].stage == "decode"
        # Same scheduler, same (exhausted) plan: full recovery.
        rid2 = sched.submit(reqs[0])
        res2 = sched.drain()
    assert np.array_equal(res2[rid2], serial[0])
    _assert_clean(server, sched)


def test_bounded_queue_backpressure(server):
    """``max_queue`` bounds the admission queue: the overflow submit
    raises QueueFullError, the queued request still completes."""
    from repro.launch.serve import QueueFullError

    rng = np.random.default_rng(8)
    reqs = _requests(rng, 2, max_new=4)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4, max_queue=1)
    rid = sched.submit(reqs[0])
    with pytest.raises(QueueFullError, match="admission queue is full"):
        sched.submit(reqs[1])
    res = sched.drain()
    assert np.array_equal(res[rid], serial[0])
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousScheduler(server, batch_rows=4, max_queue=0)
    _assert_clean(server, sched)


def test_deadline_expires_and_slot_reuse(server):
    """An already-expired deadline resolves to DeadlineExceeded before
    any decode work; the freed capacity serves the next request."""
    from repro.launch.serve import DeadlineExceeded
    from repro.runtime import faults  # noqa: F401 (site parity import)

    rng = np.random.default_rng(9)
    reqs = _requests(rng, 2, max_new=4)
    serial = _serial(server, reqs)

    sched = ContinuousScheduler(server, batch_rows=4)
    doomed = Request(
        tokens=reqs[0].tokens, max_new=4, deadline_s=0.0
    )
    rid0 = sched.submit(doomed)
    rid1 = sched.submit(reqs[1])
    res = sched.drain()
    err = res[rid0]
    assert isinstance(err, DeadlineExceeded)
    assert err.stage == "deadline" and err.request_id == rid0
    assert np.array_equal(res[rid1], serial[1])
    assert sched.stats["deadline_expired"] == 1
    # The expired request's slot capacity is reusable immediately.
    rid2 = sched.submit(reqs[0])
    res2 = sched.drain()
    assert np.array_equal(res2[rid2], serial[0])
    _assert_clean(server, sched)


def test_cache_overflow_one_typed_error_both_paths(server):
    """``generate()`` and ``submit()`` refuse an impossible request with
    the SAME typed error (CacheOverflowError, a ValueError subclass) —
    one overflow contract across the serial and batched paths."""
    from repro.launch.serve import CacheOverflowError

    big = Request(tokens=np.zeros((1, 200), np.int32), max_new=MAX_CACHE)
    sched = ContinuousScheduler(server, batch_rows=4)
    with pytest.raises(CacheOverflowError, match="admission refused"):
        sched.submit(big)
    with pytest.raises(CacheOverflowError):
        server.generate(big)
    assert issubclass(CacheOverflowError, ValueError)
    assert sched.drain() == {}
    _assert_clean(server, sched)


@pytest.mark.contention
def test_threaded_submitters_stress(server):
    """Submitters race the scheduler thread: every request completes and
    matches its serial tokens, the ledger settles.  Timing-sensitive by
    design — nightly ``pytest -m contention``, not tier-1."""
    rng = np.random.default_rng(5)
    reqs = _requests(rng, 12, max_new=8)
    serial = _serial(server, reqs)
    sched = ContinuousScheduler(server, batch_rows=4)
    rids: dict[int, int] = {}
    lock = threading.Lock()

    def submitter(idxs):
        for i in idxs:
            rid = sched.submit(reqs[i])
            with lock:
                rids[i] = rid

    threads = [
        threading.Thread(target=submitter, args=(range(k, 12, 3),))
        for k in range(3)
    ]
    for t in threads:
        t.start()
    results: dict[int, np.ndarray] = {}
    while len(results) < len(reqs):
        results.update(sched.drain())
    for t in threads:
        t.join()
    for i, ser in enumerate(serial):
        assert np.array_equal(results[rids[i]], ser), i
    _assert_clean(server, sched)


def test_scheduler_parks_no_dead_cache():
    """Admissions across every prefill bucket: the scheduler retires each
    b=1 prefill cache after its slot copy, and the shared cache's outgrown
    leaves as it grows, so nothing is parked — every release it makes
    retires, each step records 0 parked bytes, and the leases settle at
    the shared cache's own.  ``generate()``'s growth still parks."""
    server = VortexServer(get_smoke_config("paper-gpt2-124m"),
                          make_host_mesh(), max_cache=MAX_CACHE)
    pool = server.kv_pool
    sched = ContinuousScheduler(server, batch_rows=4)
    rng = np.random.default_rng(9)
    max_new = 4
    buckets = server.prefill_seq_buckets(MAX_CACHE)
    assert len(buckets) >= 2
    lengths = [min(b, MAX_CACHE - max_new + 1) for b in buckets]
    assert [server.prefill_seq_bucket(s) for s in lengths] == buckets
    reqs = [Request(tokens=rng.integers(0, 512, (1, s)).astype(np.int32),
                    max_new=max_new) for s in lengths]
    before = pool.stats()
    copies = server.decode_stats.stage_copies
    for r in reqs:  # one at a time: the shared cache grows between them
        sched.submit(r)
        sched.drain()
    after = pool.stats()
    shared = sum(1 for _ in server._cache_kv_leaves(sched.cache))
    grown = server.decode_stats.stage_copies - copies
    assert grown > 0, "the shared cache never grew — test inert"
    assert after["parked_bytes"] == 0
    assert after["leases_active"] == shared
    released = after["released"] - before["released"]
    assert after["retired"] - before["retired"] == released
    assert released == shared * len(reqs) + grown
    assert all(rec["kv_parked_bytes"] == 0 for rec in sched.step_positions)
    _assert_clean(server, sched)

    parked = pool.stats()["parked_bytes"]  # close() parks the shared cache
    server.generate(Request(tokens=reqs[0].tokens[:, :100], max_new=60))
    assert pool.stats()["parked_bytes"] > parked
    assert pool.stats()["parked_bytes"] == sum(
        b.nbytes for bufs in pool._free.values() for b in bufs)


def test_pool_parked_bytes_follow_park_lease_and_drop():
    """``parked_bytes`` is kept as buffers are parked, leased and dropped
    past ``_MAX_PARKED``; a release with ``reuse=False`` retires."""
    from repro.launch.serve import KVBucketPool

    pool = KVBucketPool()
    shape, n = (4, 8), 4 * 8 * 4
    extra = 3
    for _ in range(KVBucketPool._MAX_PARKED + extra):
        pool.release(pool.lease(shape, jnp.float32, zero=True))
    st = pool.stats()
    assert st["parked_bytes"] == KVBucketPool._MAX_PARKED * n
    assert st["retired"] == 0
    pool.lease(shape, jnp.float32)
    assert pool.stats()["parked_bytes"] == (KVBucketPool._MAX_PARKED - 1) * n
    pool.release(pool.lease((2, 2), jnp.float32), reuse=False)
    st = pool.stats()
    assert st["retired"] == 1
    assert st["parked_bytes"] == (KVBucketPool._MAX_PARKED - 1) * n
