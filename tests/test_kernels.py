"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import flash_attention
from repro.kernels.gemm import vortex_gemm
from repro.kernels.ref import (
    chunked_attention,
    ref_attention,
    ref_gemm,
)

RNG = np.random.default_rng(42)


def _arr(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


GEMM_CASES = [
    # (M, N, K, bm, bn, bk)
    (128, 128, 128, 64, 64, 64),
    (256, 128, 384, 128, 128, 128),
    (64, 256, 128, 64, 128, 128),
    (512, 64, 64, 128, 64, 64),
    (128, 128, 128, 128, 128, 128),  # single block
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", GEMM_CASES)
def test_gemm_matches_ref(case, dtype):
    m, n, k, bm, bn, bk = case
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    out = vortex_gemm(a, b, block_m=bm, block_n=bn, block_k=bk,
                      interpret=True)
    ref = ref_gemm(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_gemm_masked_tails_handle_misaligned_shapes():
    """Shapes that are not block multiples run with masked boundary tiles —
    the selected blocks are honored verbatim, never clamped or rejected."""
    a, b = _arr((100, 150), jnp.float32), _arr((150, 130), jnp.float32)
    out = vortex_gemm(a, b, block_m=64, block_n=64, block_k=64,
                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_gemm(a, b)), rtol=1e-4, atol=1e-4
    )


def test_gemm_blocks_larger_than_shape_honored():
    """A selected tile larger than the whole problem still runs (grid 1,
    fully masked boundary) instead of being silently clamped to the shape."""
    a, b = _arr((5, 7), jnp.float32), _arr((7, 3), jnp.float32)
    out = vortex_gemm(a, b, block_m=64, block_n=64, block_k=64,
                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_gemm(a, b)), rtol=1e-4, atol=1e-4
    )


def test_gemm_rejects_degenerate_blocks():
    a, b = _arr((64, 64), jnp.float32), _arr((64, 64), jnp.float32)
    with pytest.raises(ValueError, match="cannot be honored"):
        vortex_gemm(a, b, block_m=0, block_n=64, block_k=64, interpret=True)


def test_gemm_m_true_masks_garbage_tail():
    """Rows past the runtime extent are masked on load: NaN garbage in the
    pad tail (a stale staging buffer) cannot reach the real rows, and the
    real rows are bit-identical to a zero-padded run."""
    m_true = 77
    a = _arr((128, 96), jnp.float32)
    b = _arr((96, 64), jnp.float32)
    a_zero = a.at[m_true:].set(0.0)
    a_nan = a.at[m_true:].set(jnp.nan)
    out_zero = vortex_gemm(a_zero, b, m_true, block_m=64, block_n=64,
                           block_k=64, interpret=True)
    out_nan = vortex_gemm(a_nan, b, m_true, block_m=64, block_n=64,
                          block_k=64, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out_zero)[:m_true], np.asarray(out_nan)[:m_true]
    )
    np.testing.assert_allclose(
        np.asarray(out_nan)[:m_true], np.asarray(ref_gemm(a, b))[:m_true],
        rtol=1e-4, atol=1e-4,
    )


def test_flash_attention_kv_len_masks_garbage_tail():
    """kv rows past the runtime kv_len are score-masked and value-zeroed:
    NaN garbage there cannot poison any real query row, causal or not."""
    kv_true = 53
    q = _arr((1, 2, 64, 32), jnp.float32)
    k = _arr((1, 2, 64, 32), jnp.float32)
    v = _arr((1, 2, 64, 32), jnp.float32)
    k_nan = k.at[:, :, kv_true:].set(jnp.nan)
    v_nan = v.at[:, :, kv_true:].set(jnp.nan)
    for causal in (True, False):
        out = flash_attention(
            q, k_nan, v_nan, kv_true, block_q=32, block_k=32,
            causal=causal, interpret=True,
        )
        ref = ref_attention(
            q[:, :, :kv_true] if causal else q,
            k[:, :, :kv_true], v[:, :, :kv_true], causal=causal,
        )
        got = np.asarray(out)[:, :, :kv_true] if causal else np.asarray(out)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-3,
                                   atol=2e-3)
    assert np.isfinite(np.asarray(out)).all()


def test_flash_attention_misaligned_seq_masked():
    """Sequence lengths that are not block multiples run with masked
    boundary tiles (no clamping, no pre-padding required)."""
    q = _arr((1, 2, 100, 32), jnp.float32)
    out = flash_attention(q, q, q, block_q=64, block_k=64, causal=True,
                          interpret=True)
    ref = ref_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


ATTN_CASES = [
    # (b, hq, hkv, s, d, causal, window, softcap)
    (1, 4, 4, 128, 64, True, None, None),
    (2, 4, 2, 128, 64, True, None, None),     # GQA
    (1, 2, 2, 256, 32, True, 64, None),       # sliding window
    (1, 2, 1, 128, 64, True, None, 50.0),     # softcap (gemma2)
    (1, 4, 4, 128, 64, False, None, None),    # bidirectional (encoder)
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_ref(case):
    b, hq, hkv, s, d, causal, window, softcap = case
    q = _arr((b, hq, s, d), jnp.float32)
    k = _arr((b, hkv, s, d), jnp.float32)
    v = _arr((b, hkv, s, d), jnp.float32)
    out = flash_attention(
        q, k, v, block_q=64, block_k=64, causal=causal, window=window,
        softcap=softcap, interpret=True,
    )
    ref = ref_attention(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3,
    )


@pytest.mark.parametrize("case", ATTN_CASES)
def test_chunked_attention_matches_ref(case):
    """The scan-based flash attention (used inside the models) == oracle."""
    b, hq, hkv, s, d, causal, window, softcap = case
    q = _arr((b, hq, s, d), jnp.float32)
    k = _arr((b, hkv, s, d), jnp.float32)
    v = _arr((b, hkv, s, d), jnp.float32)
    out = chunked_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, chunk=64,
    )
    ref = ref_attention(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3,
    )


def test_chunked_attention_mixed_v_dim():
    """MLA uses d_v != d_qk; the chunked path must support it."""
    q = _arr((1, 2, 128, 48), jnp.float32)
    k = _arr((1, 2, 128, 48), jnp.float32)
    v = _arr((1, 2, 128, 32), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, chunk=32)
    ref = ref_attention(q, k, v, causal=True)
    assert out.shape == (1, 2, 128, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_attention_kernel_blocks_from_vortex_lattice():
    """Block sizes drawn from the Vortex lattice are valid kernel configs."""
    from repro.core import GemmWorkload, TPU_V5E
    from repro.core.candidates import generate_lattice

    wl = GemmWorkload(M=None, N=128, K=64)
    lat = generate_lattice(TPU_V5E, wl, "mxu")
    bq = int(lat.l1[0][0])
    q = _arr((1, 2, max(bq, 128), 64), jnp.float32)
    out = flash_attention(
        q, q, q, block_q=min(bq, 128), block_k=128, interpret=True
    )
    ref = ref_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


DECODE_CASES = [
    # (b, hkv, group, d, S, block_k, kv_len, window, softcap, dtype)
    (3, 2, 1, 64, 128, 32, [0, 128, 45], None, None, jnp.float32),
    (3, 2, 3, 128, 128, 128, [0, 128, 77], None, None, jnp.float32),
    (2, 3, 1, 64, 96, 32, 53, None, None, jnp.float32),
    (2, 2, 3, 128, 128, 128, 128, None, None, jnp.float32),
    (3, 1, 3, 128, 128, 32, [0, 128, 61], 24, None, jnp.float32),
    (3, 2, 1, 64, 64, 64, [0, 64, 9], None, 30.0, jnp.float32),
    (2, 4, 3, 64, 128, 32, 100, 16, 20.0, jnp.float32),
    (3, 8, 3, 128, 256, 64, [0, 256, 130], None, None, jnp.bfloat16),
]


@pytest.mark.parametrize("causal", [False, True], ids=["masked", "causal"])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_attention_decode_grid_matches_ref(case, causal):
    """A decode-shaped call (sq == 1) takes the decode grid and matches
    the oracle at each row's own kv_len, with the cache past it
    NaN-poisoned; a row at kv_len 0 gives an exact zero."""
    b, hkv, group, d, s, block_k, kv_len, window, softcap, dtype = case
    hq = hkv * group
    lens = np.broadcast_to(np.asarray(kv_len, np.int32), (b,))
    q = _arr((b, hq, 1, d), dtype)
    k = _arr((b, hkv, s, d), dtype)
    v = _arr((b, hkv, s, d), dtype)
    tail = (np.arange(s)[None, :] >= lens[:, None])[:, None, :, None]
    kv_arg = jnp.asarray(kv_len, jnp.int32)
    out = flash_attention(
        q, jnp.where(tail, jnp.nan, k), jnp.where(tail, jnp.nan, v),
        kv_arg, q_offset=kv_arg - 1, block_q=1, block_k=block_k,
        causal=causal, window=window, softcap=softcap, interpret=True,
    )
    assert out.shape == (b, hq, 1, d)
    got = np.asarray(out, np.float32)
    live = lens > 0
    assert not got[~live].any(), "a row at kv_len 0 must give exact zeros"
    ref = ref_attention(
        q, k, v, causal=False, window=window, softcap=softcap,
        offset=lens - 1, kv_len=lens,
    )
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got[live], np.asarray(ref, np.float32)[live], rtol=tol, atol=tol,
    )
