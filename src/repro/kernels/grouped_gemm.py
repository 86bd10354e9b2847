"""Ragged grouped GEMM as a masked-tail Pallas TPU kernel.

MoE expert FFNs are G independent GEMMs that share one stacked weight
tensor: group g multiplies its ``(C, K)`` activation slab against expert
``g // groups_per_expert``'s ``(K, N)`` weights.  The slabs are capacity-
shaped (C rows each) but only ``counts[g]`` leading rows are real — the
rest is routing pad whose content is arbitrary (and, for an engine staging
buffer, stale bytes from a previous dispatch).

This is the masked-tail contract of ``vortex_gemm`` lifted from one scalar
``m_true`` to a per-group ``(G,)`` i32 extent vector: the grid flattens
(group, m-tile) into its first dimension, and every program masks A-rows at
ITS OWN group's count before they can reach the MXU.  Rows at or past
``counts[g]`` are exactly zero in the output (zero A-rows -> zero C-rows),
which is what makes staged dispatch bit-identical to the zero-padded
reference path.

One ``pallas_call`` covers all G groups — a single launch per projection
regardless of how routing distributed the tokens.

TARGET: TPU (MXU).  Validated on CPU via ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import interpret_pallas, validate_blocks

__all__ = ["vortex_grouped_gemm"]


def _grouped_gemm_kernel(
    counts_ref, x_ref, w_ref, o_ref, acc_ref,
    *, gm: int, gk: int, block_m: int, block_n: int, block_k: int,
    N: int, K: int, out_dtype,
):
    """One (group, m-tile, n-tile) block; k is the sequential reduction dim.

    Grid dim 0 enumerates (group, m-tile) pairs: ``g = i // gm`` selects the
    group, ``mi = i % gm`` the row tile within it.  ``counts_ref`` (SMEM,
    full ``(G,)`` vector) holds every group's true row count; this program
    masks its A-rows at ``counts_ref[g]``, so each group gets its own
    runtime extent from ONE launch.  K/N tail masks as in ``_gemm_kernel``.
    """
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = i // gm
    mi = i % gm

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Row mask is unconditional: counts[g] is a runtime value, and the rows
    # past it may be NaN (staging-pool garbage) — they must never reach the
    # accumulator, even through a 0-weight.
    rows = mi * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_k), 0
    )
    valid = rows < counts_ref[g]
    if K % block_k:
        cols = k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_k), 1
        )
        valid &= cols < K
    x = jnp.where(valid, x_ref[0], 0)

    if K % block_k or N % block_n:
        wrows = k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 0
        )
        wcols = j * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 1
        )
        w = jnp.where((wrows < K) & (wcols < N), w_ref[0], 0)
    else:
        w = w_ref[0]

    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == gk - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "interpret", "out_dtype",
        "vmem_limit_bytes",
    ),
)
def vortex_grouped_gemm(
    x: jax.Array,
    w: jax.Array,
    counts: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """out[g] = x[g] @ w[g // r] with per-group masked-tail row extents.

    Args:
      x: ``(G, C, K)`` capacity-shaped activation slabs, one per group.
      w: ``(E, K, N)`` stacked expert weights; ``r = G // E`` consecutive-
         in-expert-major-order groups share each stack entry (callers lay
         groups out expert-major: group ``e * r + b`` uses expert ``e``).
      counts: ``(G,)`` i32 — group g's TRUE row count.  Rows of ``x[g]`` at
         or past ``counts[g]`` may hold arbitrary garbage; the matching
         output rows are exactly zero.

    One launch covers all groups: grid dim 0 is the flattened
    (group, m-tile) space, so Selection's (block_m, block_n, block_k) tile
    is honored verbatim per group and the per-group extent is a runtime
    SMEM value, not a shape.
    """
    G, C, K = x.shape
    E, K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert G % E == 0, (G, E)
    validate_blocks(
        "vortex_grouped_gemm",
        block_m=block_m, block_n=block_n, block_k=block_k,
    )
    r = G // E
    gm, gn, gk = pl.cdiv(C, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k)
    out_dtype = out_dtype or x.dtype
    counts_arr = jnp.asarray(counts, jnp.int32).reshape(G)

    kernel = functools.partial(
        _grouped_gemm_kernel,
        gm=gm, gk=gk, block_m=block_m, block_n=block_n, block_k=block_k,
        N=N, K=K, out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(G * gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_m, block_k), lambda i, j, k: (i // gm, i % gm, k)),
            pl.BlockSpec((1, block_k, block_n), lambda i, j, k: ((i // gm) // r, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n), lambda i, j, k: (i // gm, i % gm, j)),
        out_shape=jax.ShapeDtypeStruct((G, C, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret_pallas(interpret),
    )(counts_arr, x, w)
