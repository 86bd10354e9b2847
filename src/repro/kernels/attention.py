"""Flash-attention Pallas TPU kernel with Vortex-selected block sizes.

Attention's two contractions (QK^T and PV) are GEMMs whose dynamic dim is the
sequence length — exactly the paper's dynamic-M case.  The (block_q, block_k)
pair is drawn from the Vortex layer-1 lattice (m-tile for queries, k-tile for
keys), so the same sample-free bucketing governs attention and plain GEMMs.

Key-side padding is handled by an EXPLICIT validity mask, not by the causal
structure: ``kv_len`` (a runtime i32 in SMEM — one scalar shared by the
batch, or a per-batch-row vector for mixed-progress decode) marks how many
leading key/value rows are real, scores past it are masked to -inf and the
value rows are zeroed on load.  The pad tail of k/v may therefore hold arbitrary
garbage (stale bytes in an engine staging buffer, NaNs), and non-causal
attention buckets exactly as safely as causal attention.  Requested blocks
are honored verbatim — sequence lengths that are not block multiples get
masked boundary tiles, never a silently clamped block.

Supports causal masking, sliding-window attention (h2o-danube, gemma2 local
layers) and GQA (kv heads shared across query-head groups via the BlockSpec
index map).  TARGET: TPU; validated on CPU with ``interpret=True``.

Two grids, chosen from the static query length.  Prefill (``sq > 1``)
takes one grid step per (batch row x query head, q block, kv block).
Decode (``sq == 1``) has one query per head, so it takes one step per
(batch row, kv block) with every KV head of the row in the block and
each KV head's query group as the rows of its q tile: a step moves the
row's whole K/V block once, not once per query head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import interpret_pallas, validate_blocks

__all__ = ["flash_attention", "decode_grid_steps", "decode_vmem_bytes"]

_NEG_INF = -1e30
_LANES = 128


def _attn_kernel(
    kv_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, gkv: int, block_q: int, block_k: int, scale: float,
    causal: bool, window: int | None, softcap: float | None,
    heads: int, rows: int,
):
    """One (head, q-block): stream kv blocks, online softmax in VMEM scratch.

    ``kv_ref`` (SMEM, shape ``(2, rows)``) holds two runtime i32 values per
    batch row: the TRUE key/value length and the absolute position of query
    row 0.  With ``rows == 1`` both are shared by every batch row (the
    scalar contract); with ``rows == b`` each batch row masks at ITS OWN
    extent — one launch serves rows at different kv positions
    (mixed-progress batched decode), a ``kv_len`` of 0 masking a row to
    zero work (all scores -inf, value rows zeroed, output exactly 0).
    Everything past the per-row kv length — bucket pad, stale staging
    bytes, out-of-bounds block tails — is masked out of the scores and
    zeroed out of the PV product, so no zero-filled padding (and no causal
    structure) is needed for correctness.  The query offset re-bases the
    causal/window masks so a single-row decode query (``sq == 1`` at
    absolute position ``kv_len - 1``) masks exactly like the matching row
    of a full-sequence call.
    """
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (block_k, d)
    v = v_ref[0]
    # Grid axis 0 is flattened (batch, head): the batch row owning this
    # program recovers as pid // heads (0 when the extents are shared).
    row = pl.program_id(0) // heads if rows > 1 else 0
    kv_limit = kv_ref[0, row]
    q_off = kv_ref[1, row]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = q_off + pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_limit  # key validity: replaces zero-pad reliance
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, _NEG_INF)

    # Invalid value rows must be ZEROED, not merely down-weighted: their
    # softmax weight is an exact 0.0, but 0 * garbage(NaN/Inf) would still
    # poison the accumulator of every REAL query row.
    v_rows = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, v.shape, 0
    )
    v = jnp.where(v_rows < kv_limit, v, 0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_i == gkv - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def decode_vmem_bytes(
    hkv: int, group: int, block_k: int, d: int, itemsize: int
) -> int:
    """VMEM one decode grid step holds, counted in (sublane, lane) tiles.

    Two buffers each of the q, out, K and V blocks; the f32 scratch
    (running max, sum and accumulator); the f32 scores and weights; and
    the value block with its rows past ``kv_len`` zeroed.  ``d`` pads to
    128 lanes and the sublane dims to the dtype's packed tile (16 rows
    for bf16, 8 for f32).
    """
    lanes = _round_up(d, _LANES)
    sub = 32 // itemsize
    qo = 2 * _round_up(group, sub) * lanes * itemsize
    kv = 2 * _round_up(block_k, sub) * lanes * itemsize
    scratch = (
        2 * _round_up(hkv, 8) * _round_up(group, _LANES)
        + hkv * _round_up(group, 8) * lanes
    ) * 4
    scores = 2 * hkv * _round_up(group, 8) * _round_up(block_k, _LANES) * 4
    v_zeroed = hkv * _round_up(block_k, sub) * lanes * itemsize
    return 2 * hkv * (qo + kv) + scratch + scores + v_zeroed


def _decode_kernel(
    kv_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, gkv: int, block_k: int, scale: float,
    causal: bool, window: int | None, softcap: float | None, rows: int,
):
    """One (batch row, kv block) step of single-token decode.

    The q tile is ``(hkv, group, d)``: each KV head's query group rides
    as the M rows of its QK^T, so every K/V block is fetched once for the
    whole group.  Masking is the prefill kernel's, at one query position
    per row: keys at or past ``kv_ref[0, row]`` are score-masked and
    their value rows zeroed, ``causal``/``window`` re-base at the query's
    absolute position ``kv_ref[1, row]``, and a row with ``kv_len`` 0
    gives an exact zero output.
    """
    kv_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (hkv, group, d)
    k = k_ref[0]  # (hkv, block_k, d)
    v = v_ref[0]
    row = pl.program_id(0) if rows > 1 else 0
    kv_limit = kv_ref[0, row]
    q_pos = kv_ref[1, row]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # (hkv, group, block_k)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 2
    )
    mask = k_pos < kv_limit
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, _NEG_INF)

    # Zero invalid value rows (0 * NaN would poison the accumulator).
    v_rows = kv_i * block_k + jax.lax.broadcasted_iota(
        jnp.int32, v.shape, 1
    )
    v = jnp.where(v_rows < kv_limit, v, 0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(kv_i == gkv - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _decode_attention(
    q, k, v, kv_arr, *, block_k, causal, window, softcap, interpret,
    vmem_limit_bytes,
):
    """The ``sq == 1`` grid: ``(b, gkv)``, q reshaped to ``(b, hkv, group,
    d)`` so each KV head's query group is its q tile."""
    b, hq, _, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    need = decode_vmem_bytes(hkv, group, block_k, d, k.dtype.itemsize)
    if vmem_limit_bytes is not None and need > vmem_limit_bytes:
        raise ValueError(
            f"decode attention: a row's block ({hkv} KV heads, group "
            f"{group}, block_k {block_k}, d {d}) needs {need} bytes of "
            f"VMEM, over the limit of {vmem_limit_bytes}"
        )
    gkv = pl.cdiv(skv, block_k)
    interpret = interpret_pallas(interpret)
    if not interpret:
        # K and V stream from HBM block by block.  Left free, the compiler
        # may place a whole operand in VMEM inside the op that produces it
        # (the layer slice of a stacked cache), where that read escapes
        # this kernel's time and the kernel's speed hangs on whether the
        # operand happens to fit the VMEM left over.
        k, v = (
            pltpu.with_memory_space_constraint(x, pltpu.HBM) for x in (k, v)
        )
    kernel = functools.partial(
        _decode_kernel,
        gkv=gkv, block_k=block_k, scale=d ** -0.5, causal=causal,
        window=window, softcap=softcap, rows=kv_arr.shape[1],
    )
    q_spec = pl.BlockSpec((1, hkv, group, d), lambda i, j: (i, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, hkv, block_k, d), lambda i, j: (i, 0, j, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, gkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, kv_spec, kv_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hkv, group), jnp.float32),
            pltpu.VMEM((hkv, group), jnp.float32),
            pltpu.VMEM((hkv, group, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(kv_arr, q.reshape(b, hkv, group, d), k, v)
    return out.reshape(b, hq, 1, d)


def decode_grid_steps(jaxpr) -> int:
    """Grid steps of every decode-attention launch in ``jaxpr``: each
    decode ``pallas_call``'s grid size, times the length of every scan it
    sits in (a layer scan runs its body once per layer)."""
    steps = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            body = eqn.params["jaxpr"].debug_info.func_name
            if body == _decode_kernel.__name__:
                steps += math.prod(eqn.params["grid_mapping"].grid)
            continue
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                inner = getattr(inner, "jaxpr", inner)
                steps += times * decode_grid_steps(inner)
    return steps


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_q", "block_k", "causal", "window", "softcap", "interpret",
        "vmem_limit_bytes",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len=None,
    q_offset=None,
    *,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Multi-head attention.

    Args:
      q: (batch, q_heads, seq, head_dim)
      k, v: (batch, kv_heads, seq, head_dim); q_heads % kv_heads == 0 (GQA).
      kv_len: optional runtime i32 — the number of REAL key/value rows;
        rows past it (staging-buffer pad, garbage) are masked out.
        Either a scalar shared by the whole batch or a ``(batch,)`` vector
        giving each batch row its OWN extent (mixed-progress batched
        decode; a 0 masks that row to zero work and an all-zero output).
        Defaults to the full (static) key length.
      q_offset: optional runtime i32 scalar or ``(batch,)`` vector — the
        absolute position of query row 0 (decode: ``kv_len - 1`` for the
        single new token).  Re-bases the causal/window masks; defaults to
        0 (self-attention with queries and keys sharing position 0).
      block_q/block_k: Vortex layer-1 tiles for the sequence dims — honored
        verbatim; non-multiple sequence lengths get masked boundary tiles.
        A decode-shaped call (sq == 1) ignores block_q: it takes the decode
        grid, one step per (batch row, kv block) with all of the row's KV
        heads, and raises if that block outgrows ``vmem_limit_bytes``.
      window: sliding-window size (keys within [q-window+1, q]).
      softcap: gemma2-style logit soft-capping applied to QK^T scores.
    Returns (batch, q_heads, seq, head_dim).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    validate_blocks("flash_attention", block_q=block_q, block_k=block_k)
    gq, gkv = pl.cdiv(sq, block_q), pl.cdiv(skv, block_k)
    scale = d ** -0.5
    if kv_len is None:
        kv_len = skv
    if q_offset is None:
        q_offset = 0
    kv_vec = jnp.asarray(kv_len, jnp.int32)
    off_vec = jnp.asarray(q_offset, jnp.int32)
    for name, vec in (("kv_len", kv_vec), ("q_offset", off_vec)):
        assert vec.ndim <= 1 and (vec.ndim == 0 or vec.shape == (b,)), (
            f"{name} must be a scalar or a (batch,)=({b},) vector, "
            f"got shape {vec.shape}"
        )
    # Per-row extents ride as a (2, rows) SMEM array: one column per batch
    # row when either extent is a vector, one shared column otherwise.
    rows = b if (kv_vec.ndim or off_vec.ndim) else 1
    kv_arr = jnp.stack([
        jnp.broadcast_to(kv_vec.reshape(-1), (rows,)),
        jnp.broadcast_to(off_vec.reshape(-1), (rows,)),
    ])

    if sq == 1:
        return _decode_attention(
            q, k, v, kv_arr, block_k=block_k, causal=causal, window=window,
            softcap=softcap, interpret=interpret,
            vmem_limit_bytes=vmem_limit_bytes,
        )

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    kernel = functools.partial(
        _attn_kernel,
        gkv=gkv, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, window=window, softcap=softcap,
        heads=hq, rows=rows,
    )

    def kv_map(h, i, j):
        del i
        return (h // group, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, gq, gkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret_pallas(interpret),
    )(kv_arr, qf, kf, vf)
    return out.reshape(b, hq, sq, d)
