"""Vortex-tiled GEMM as a Pallas TPU kernel, with masked tails.

The BlockSpec tiling is *not* hand-picked: the (block_m, block_n, block_k)
triple is the layer-1 tile selected by Vortex's runtime selector from the
hardware-pruned candidate lattice (core/), and the grid is the layer-2
parallel/temporal loop structure of the rKernel program:

    grid = (gm, gn, gk)   — (m, n) are the PARALLEL loops (distributed over
                            TensorCores on real hardware), k is the
                            TEMPORAL-REDUCTION loop (sequential, accumulator
                            resident in VMEM across the k steps).

The selected tile is honored VERBATIM: dims that are not multiples of their
block are handled by in-kernel tail masks (iota row/column masks on load,
out-of-bounds stores dropped by the grid), never by silently clamping the
block to the shape — a clamped tile would diverge from the Selection the
cost model priced.  Correctness therefore does not depend on zero-filled
padding anywhere: the ``m_true`` scalar marks how many leading rows of ``a``
are real, and everything past it (stale bytes in an engine staging buffer,
uninitialized pad, NaNs) is masked to zero before it can reach the MXU.

TARGET: TPU (MXU).  Validated on CPU via ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hardware import resolve_platform

__all__ = ["vortex_gemm", "validate_blocks", "interpret_pallas"]


def interpret_pallas(requested: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode, from the platform.

    ``None`` derives it: interpreted on the CPU, compiled natively on a
    TPU.  ``False`` compiles natively anywhere (a described, unattached TPU
    compiles from a CPU process).  ``True`` on a TPU raises: the chip
    would run the interpreter, and nothing would say so.
    """
    native = resolve_platform().native_pallas
    if requested is None:
        return not native
    if requested and native:
        raise ValueError(
            "Pallas interpret mode was requested on a TPU backend; "
            "kernels compile natively there (pass interpret=None)"
        )
    return requested


def validate_blocks(kind: str, **blocks: int) -> None:
    """Reject block sizes the kernel could not honor.

    The masked-tail kernels never clamp a requested tile (that would
    silently deviate from the Selection that was priced); a tile they
    cannot realize at all is therefore an error, not an adjustment.
    """
    for name, blk in blocks.items():
        if not isinstance(blk, (int,)) or isinstance(blk, bool) or blk < 1:
            raise ValueError(
                f"{kind}: {name}={blk!r} cannot be honored — selected tiles "
                "must be positive integers (the kernel masks tails instead "
                "of clamping, so a degenerate block has no meaning)"
            )


def _gemm_kernel(
    m_ref, a_ref, b_ref, o_ref, acc_ref,
    *, gk: int, block_m: int, block_n: int, block_k: int,
    M: int, N: int, K: int, mask_rows: bool, out_dtype,
):
    """One (m, n) block: accumulate A[m,k] @ B[k,n] over the k grid dim.

    ``acc_ref`` is an f32 VMEM scratch accumulator — it survives across the
    sequential k steps because the k grid dimension is innermost and TPU
    grids execute sequentially per core (rKernel level-2 temporal loop).

    ``m_ref`` (SMEM) holds the TRUE row count: rows past it are masked to
    zero on load, so the pad region of a staged input may hold arbitrary
    garbage.  The static K/N tail masks neutralize boundary blocks when a
    block does not divide the dim (out-of-bounds reads are undefined).
    """
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    if mask_rows or K % block_k:
        rows = i * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_k), 0
        )
        cols = k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_k), 1
        )
        valid = cols < K
        if mask_rows:
            valid &= rows < m_ref[0]
        a = jnp.where(valid, a, 0)
    if K % block_k or N % block_n:
        brows = k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 0
        )
        bcols = j * block_n + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_n), 1
        )
        b = jnp.where((brows < K) & (bcols < N), b_ref[...], 0)
    else:
        b = b_ref[...]

    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == gk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "interpret", "out_dtype",
        "vmem_limit_bytes",
    ),
)
def vortex_gemm(
    a: jax.Array,
    b: jax.Array,
    m_true=None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] with Vortex layer-1 tiles as BlockSpecs.

    Shapes need NOT be multiples of the blocks: the grid rounds up and the
    boundary tiles are masked in-kernel, so the selected tile is executed
    exactly as priced (no silent clamping) and padding never has to be
    zero-filled.

    ``m_true`` (optional int or i32 scalar) is the number of REAL leading
    rows of ``a``; rows past it are masked to zero on load.  The serving
    engine passes the runtime extent here and hands the kernel a
    bucket-shaped staging buffer whose pad tail holds stale bytes.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    validate_blocks(
        "vortex_gemm", block_m=block_m, block_n=block_n, block_k=block_k
    )
    gm, gn, gk = pl.cdiv(M, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k)
    out_dtype = out_dtype or a.dtype
    # The row mask costs a VPU compare per tile; skip it when every row is
    # statically real (no runtime extent, M divides evenly).
    mask_rows = m_true is not None or M % block_m != 0
    if m_true is None:
        m_true = M
    m_arr = jnp.asarray(m_true, jnp.int32).reshape(1)

    kernel = functools.partial(
        _gemm_kernel,
        gk=gk, block_m=block_m, block_n=block_n, block_k=block_k,
        M=M, N=N, K=K, mask_rows=mask_rows, out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret_pallas(interpret),
    )(m_arr, a, b)
