"""Convolution via im2col + the Vortex GEMM kernel.

The paper benchmarks convolution (Table 4) by lowering it to the same
hierarchized GEMM strategy space: im2col turns Conv2D into a GEMM with
M = b*h'*w' (dynamic: batch/fmap), N = cout, K = kh*kw*cin — after which the
entire Vortex lattice/selector machinery applies unchanged.

The GEMM-view kernel masks its own tails (kernels/gemm.py), so this path is
padding-free end to end: no dim is rounded up, no block is clamped to the
shape, and the blocks the caller selected are the blocks that run.
"""
from __future__ import annotations

import jax

from repro.kernels.gemm import vortex_gemm

__all__ = ["im2col", "vortex_conv2d"]


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1) -> jax.Array:
    """(b, h, w, cin) -> (b*h'*w', kh*kw*cin) patches, VALID padding."""
    b, h, w, cin = x.shape
    ho = (h - kh) // stride + 1
    wo = (w - kw) // stride + 1
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=(kh, kw),
        window_strides=(stride, stride),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (b, ho, wo, cin*kh*kw), feature dim ordered (cin, kh, kw)
    return patches.reshape(b * ho * wo, cin * kh * kw), (b, ho, wo)


def vortex_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Conv2D (VALID) through im2col + masked-tail Vortex GEMM.

    Args: x (b, h, w, cin); w (kh, kw, cin, cout).
    """
    kh, kw, cin, cout = w.shape
    cols, (b, ho, wo) = im2col(x, kh, kw, stride)
    # conv_general_dilated_patches orders features as (cin, kh, kw); match it.
    wmat = w.transpose(2, 0, 1, 3).reshape(kh * kw * cin, cout)
    out = vortex_gemm(
        cols, wmat, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
    return out.reshape(b, ho, wo, cout)
