"""Workload-generic pipeline end-to-end: registry-dispatched gemm /
attention / conv2d (vortex.ops through an Engine session) must match the
flat JAX references for prime (non-tile-aligned) dynamic sizes across
execution backends, selection must be deterministic, and the
bucketing/caching contracts must hold."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    HOST_CPU,
    TPU_V5E,
    AttentionWorkload,
    Conv2dWorkload,
    GemmWorkload,
    WORKLOADS,
)
from repro import vortex
from repro.vortex import Engine
from repro.core.analyzer import AnalyticalProfiler, HybridAnalyzer
from repro.core.candidates import generate_lattice
from repro.core.selector import RuntimeSelector
from repro.kernels.ref import ref_attention, ref_conv2d, ref_gemm

RNG = np.random.default_rng(7)


def _arr(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def engine(request):
    # pallas runs in interpret mode on the CPU; empirical_levels=() keeps
    # the offline stage fast and deterministic.
    return Engine("host_cpu", empirical_levels=(), impl=request.param)


# ---------------------------------------------------------------------------
# End-to-end numerics at prime dynamic sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 7, 61, 127])
def test_gemm_matches_reference(engine, m):
    a, b = _arr((m, 96)), _arr((96, 80))
    np.testing.assert_allclose(
        np.asarray(engine.dispatch("gemm", a, b)), np.asarray(ref_gemm(a, b)),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("seq", [3, 37, 101])
def test_attention_matches_reference(engine, seq):
    q = _arr((2, 4, seq, 32))
    k = _arr((2, 2, seq, 32))  # GQA: 2 query heads per kv head
    v = _arr((2, 2, seq, 32))
    out = engine.dispatch("attention", q, k, v)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_attention_window_matches_reference(engine):
    q = k = v = _arr((1, 2, 53, 32))
    out = engine.dispatch("attention", q, k, v, window=16)
    ref = ref_attention(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("batch,hw_px", [(1, 9), (3, 11)])
def test_conv2d_matches_reference(engine, batch, hw_px):
    x = _arr((batch, hw_px, hw_px, 5))
    w = _arr((3, 3, 5, 7))
    out = engine.dispatch("conv2d", x, w)
    ref = ref_conv2d(x, w, stride=1, padding="VALID")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_non_causal_attention_served(engine):
    """Bucket padding no longer leans on the causal structure: the explicit
    kv-validity mask makes bidirectional (encoder) attention bucket exactly
    as safely, at a prime (pad-exercising) sequence length."""
    q = _arr((1, 2, 53, 32))
    k = _arr((1, 2, 53, 32))
    v = _arr((1, 2, 53, 32))
    out = engine.dispatch("attention", q, k, v, causal=False)
    ref = ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


# ---------------------------------------------------------------------------
# Registry / shared caches
# ---------------------------------------------------------------------------


def test_registry_serves_all_kinds():
    assert {"gemm", "attention", "conv2d"} <= set(WORKLOADS)


def test_one_kernel_per_signature_and_shared_lattice():
    eng = Engine("host_cpu", empirical_levels=())
    q = _arr((1, 2, 13, 32))
    k = v = _arr((1, 2, 13, 32))
    eng.dispatch("attention", q, k, v)
    eng.dispatch("attention", q, k, v, window=8)  # same lattice_key, new signature
    stats = eng.stats()["attention"]
    assert stats["signatures"] == 2
    # Masking flags share one scored lattice (engine-wide scored cache).
    assert len(eng._scored_cache) == 1


def test_attention_precompile_warms_serving_keys():
    """Precompiled attention entries must sit under the SAME executable-cache
    keys that real calls with the given batch/head layout hit — a later call
    at any seq <= m_max must not add cache entries."""
    eng = Engine("host_cpu", empirical_levels=())
    wl = AttentionWorkload(seq=None, head_dim=32)
    q = _arr((2, 4, 5, 32))
    k = v = _arr((2, 2, 5, 32))
    n = eng.precompile(wl, 64, q, k, v)
    assert n >= 1
    kernel = eng.kernel_for(wl)
    entries_before = kernel.cache_info["entries"]
    for seq in (5, 23, 61):
        qq = _arr((2, 4, seq, 32))
        kk = vv = _arr((2, 2, seq, 32))
        eng.dispatch("attention", qq, kk, vv)
    assert kernel.cache_info["entries"] == entries_before


def test_executable_cache_bounded_by_buckets():
    eng = Engine("host_cpu", empirical_levels=())
    b = _arr((64, 48))
    for m in range(1, 40):  # 39 distinct runtime shapes
        eng.dispatch("gemm", _arr((m, 64)), b)
    s = eng.stats()["gemm"]
    assert s["exec_hits"] == 39
    # Bounded by the lattice's bucket set, not by #distinct shapes.
    assert s["exec_entries"] <= 8


# ---------------------------------------------------------------------------
# Selector: determinism, bucket key, fast precompilation set, LRU bound
# ---------------------------------------------------------------------------


def _scored(hw, wl, backend):
    lat = generate_lattice(hw, wl, backend)
    analyzer = HybridAnalyzer(
        hw, wl, profiler=AnalyticalProfiler(hw), empirical_levels=()
    )
    return analyzer.score(lat)


GOLDEN_MS = [1, 7, 16, 61, 127, 128, 500, 1021]


@pytest.mark.parametrize(
    "wl",
    [
        GemmWorkload(M=None, N=768, K=2304),
        AttentionWorkload(seq=None, head_dim=64),
        Conv2dWorkload(m=None, cin=16, cout=32, kh=3, kw=3),
    ],
    ids=lambda wl: wl.kind,
)
def test_selector_determinism_golden(wl):
    """Two independently-built selectors must agree exactly on every
    selection — the sample-free pipeline has no stochastic stage."""
    picks = []
    for _ in range(2):
        sel = RuntimeSelector(TPU_V5E, wl, {"mxu": _scored(TPU_V5E, wl, "mxu")})
        picks.append(
            [(s.strategy.tiles, s.backend, s.grid, s.bucket)
             for s in map(sel.select, GOLDEN_MS)]
        )
    assert picks[0] == picks[1]


def test_bucket_uses_true_static_dims():
    """Selection.bucket must report the TRUE N/K extents: static dims are
    never padded at the bucket level (the executable pads internally when
    its blocks require it)."""
    wl = GemmWorkload(M=None, N=96, K=200)  # not multiples of any l1 tile
    sel = RuntimeSelector(HOST_CPU, wl, {"simd": _scored(HOST_CPU, wl, "simd")})
    s = sel.select(13)
    assert s.bucket == (s.padded_m, 96, 200)
    assert s.padded_m >= 13


def test_attention_bucket_pads_both_seq_dims():
    wl = AttentionWorkload(seq=None, head_dim=64)
    sel = RuntimeSelector(TPU_V5E, wl, {"mxu": _scored(TPU_V5E, wl, "mxu")})
    s = sel.select(37)
    pq, d, pkv = s.bucket
    assert d == 64
    assert pq >= 37 and pq % s.strategy.l1[0] == 0
    assert pkv >= 37 and pkv % s.strategy.l1[2] == 0


@pytest.mark.parametrize(
    "wl",
    [
        GemmWorkload(M=None, N=768, K=2304),
        AttentionWorkload(seq=None, head_dim=64),
    ],
    ids=lambda wl: wl.kind,
)
def test_buckets_upto_matches_bruteforce(wl):
    """The breakpoint-derived precompilation set must equal the exhaustive
    per-M enumeration (it is a speedup, not an approximation).  The brute
    side runs with the selection table disabled, so this cross-checks the
    table-derived set against the pure argmin path."""
    scored = {"mxu": _scored(TPU_V5E, wl, "mxu")}
    fast = RuntimeSelector(TPU_V5E, wl, scored)
    brute = RuntimeSelector(
        TPU_V5E, wl, scored, cache_size=1 << 16, table_m_max=0
    )
    m_max = 700
    expect = sorted({brute.select(m).padded_m for m in range(1, m_max + 1)})
    assert fast.buckets_upto(m_max) == expect


def test_selection_cache_is_lru_bounded():
    """With the table disabled, the argmin fallback's LRU stays bounded."""
    wl = GemmWorkload(M=None, N=256, K=256)
    sel = RuntimeSelector(
        HOST_CPU, wl, {"simd": _scored(HOST_CPU, wl, "simd")},
        cache_size=8, table_m_max=0,
    )
    for m in range(1, 100):
        sel.select(m)
    assert len(sel._cache) == 8
    assert sel.stats.selects == 99
    assert sel.stats.argmin_misses == 99
    assert sel.stats.table_hits == 0


def test_table_serves_without_lru_growth():
    """With the table on (the default), a high-cardinality shape stream is
    served entirely by table hits: no LRU entries, no argmin misses."""
    wl = GemmWorkload(M=None, N=256, K=256)
    sel = RuntimeSelector(
        HOST_CPU, wl, {"simd": _scored(HOST_CPU, wl, "simd")}, cache_size=8
    )
    for m in range(1, 100):
        sel.select(m)
    assert sel.stats.table_hits == 99
    assert sel.stats.argmin_misses == 0
    assert len(sel._cache) == 0


# ---------------------------------------------------------------------------
# Model-layer routing
# ---------------------------------------------------------------------------


def test_attn_forward_routes_through_engine():
    import jax
    from jax.sharding import Mesh

    from repro.models import layers
    from repro.models.config import LayerSpec
    from repro.models.partitioning import make_rules
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("paper-gpt2-124m")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rules = make_rules(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    p = {
        "wq": _arr((d, cfg.n_heads * hd)) * 0.02,
        "wk": _arr((d, cfg.n_kv_heads * hd)) * 0.02,
        "wv": _arr((d, cfg.n_kv_heads * hd)) * 0.02,
        "wo": _arr((cfg.n_heads * hd, d)) * 0.02,
    }
    x = _arr((1, 23, d))  # prime seq: exercises bucketing
    spec = LayerSpec(mixer="attn")
    positions = jnp.arange(23)
    kw = dict(mode="prefill", positions=positions, cache_len=32)

    y_ref, _ = layers.attn_forward(p, x, cfg, spec, rules, **kw)
    eng = Engine("host_cpu", empirical_levels=())
    with vortex.use(eng):
        y_eng, _ = layers.attn_forward(p, x, cfg, spec, rules, **kw)
    assert vortex.installed_engine() is None  # scoped install restored
    np.testing.assert_allclose(
        np.asarray(y_eng), np.asarray(y_ref), rtol=1e-4, atol=1e-4
    )
    # The engine actually served the attention (one signature, one call).
    assert eng.stats()["attention"]["exec_hits"] == 1
