"""Functional layer library covering all 10 assigned architectures.

Every mixer/MLP is a pure function ``(params, x, ...) -> (y, new_cache)``
with three modes:

  * ``train``   — full sequence, no cache,
  * ``prefill`` — full sequence, emits a decode cache of length ``cache_len``,
  * ``decode``  — one new token against an existing cache at ``pos``.

Attention uses the flash-style chunked online-softmax (kernels/ref.py) so the
compiled memory stays linear in sequence length; on real TPU the Pallas
flash kernel (kernels/attention.py) is the drop-in replacement, with block
sizes drawn from the Vortex lattice (core/).

Sharding is expressed through logical-axis constraints (partitioning.py);
layers never mention physical mesh axes.
"""
from __future__ import annotations

import contextlib
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.kernels.ref import chunked_attention, ref_attention
from repro.models.config import LayerSpec, ModelConfig
from repro.models.partitioning import AxisRules, constrain
from repro.vortex import _deprecation, session

__all__ = [
    "rmsnorm",
    "apply_rope",
    "attn_forward",
    "attn_forward_lazy",
    "block_forward_lazy",
    "lazy_matmul",
    "mla_forward",
    "mamba_forward",
    "mlp_forward",
    "mlp_forward_lazy",
    "moe_forward",
    "set_attention_engine",
    "get_attention_engine",
    "attention_engine",
    "ATTN_CHUNK",
]

# KV-chunk length of the flash-style attention scan; overridable by the
# Vortex autoconfig (core/autoconfig.py picks it from the cost model).
ATTN_CHUNK = 1024

# Optional vortex-engine routing for the serving attention paths: when a
# serving harness installs an Engine session (`with vortex.use(engine):`),
# prefill self-attention (causal or not), non-causal encoder attention and
# single-token decode attention all dispatch through the sample-free
# bucketed pipeline instead of the inline chunked scan / cache mask.  The
# steady-state dispatch is constant time: the engine resolves the call site
# from a raw shape tuple (Workload.dispatch_key) and the selector serves
# unseen sequence lengths from the offline-materialized breakpoint table
# (core/selection_table.py), so a high-cardinality stream of prefill
# lengths costs a bisect per call — no per-call workload construction, no
# argmin.  The installation is contextvar-scoped (repro/vortex/session.py):
# nestable, exception-safe, thread-isolated; no session installed keeps the
# inline path (training, sharded runs, and every existing caller are
# unaffected — the lazily-created *default* engine never reroutes layers).
#
# set_attention_engine / get_attention_engine / attention_engine are the
# deprecated pre-session surface; they delegate to the contextvar.


def set_attention_engine(engine):
    """Deprecated: install (or clear, with None) the engine used by
    :func:`attn_forward` for causal prefill attention; returns the previous
    one.  Use ``vortex.use(engine)`` — scoped, exception-safe, and local to
    the calling thread (this shim shares its semantics: it no longer
    mutates other threads' routing)."""
    _deprecation.warn_deprecated(
        "models.layers.set_attention_engine",
        "repro.vortex.use(engine) — NOTE the shim now writes the "
        "context/thread-LOCAL session (no longer a process-wide global): "
        "multi-threaded harnesses must install per serving thread",
    )
    return session.install(engine)


def get_attention_engine():
    """Deprecated: the engine :func:`attn_forward` currently routes
    through, or None.  Use ``repro.vortex.installed_engine()``."""
    _deprecation.warn_deprecated(
        "models.layers.get_attention_engine",
        "repro.vortex.installed_engine()",
    )
    return session.installed_engine()


@contextlib.contextmanager
def attention_engine(engine):
    """Deprecated: scoped engine install.  Use ``vortex.use(engine)`` —
    identical semantics (this shim delegates to it)."""
    _deprecation.warn_deprecated(
        "models.layers.attention_engine", "repro.vortex.use(engine)"
    )
    with session.use(engine):
        yield engine


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def norm(x: jax.Array, w: jax.Array, cfg: ModelConfig) -> jax.Array:
    return rmsnorm(x, w) if cfg.norm == "rmsnorm" else layernorm(x, w)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_tables(
    positions: jax.Array, dim: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """(..., dim/2) cos/sin tables for integer positions, rotating ``dim``
    dims (``ModelConfig.rotary_dim``): frequencies ``theta^(-2i/dim)``."""
    half = dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array
) -> jax.Array:
    """Rotate pairs (split-half convention). x: (..., seq, dim);
    cos/sin: (seq, rot/2) broadcastable.  The first ``rot`` dims rotate
    and the rest pass through unchanged (partial rotary)."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1
        )
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (dense archs, gemma2 local/global, whisper, jamba attn layers)
# ---------------------------------------------------------------------------


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)  # (b, h, s, hd)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _decode_attend(
    q: jax.Array,       # (b, H, 1, hd)
    k_cache: jax.Array,  # (b, KV, S, hd)
    v_cache: jax.Array,  # (b, KV, S, dv)
    pos: jax.Array,      # i32 index of the new token: scalar or (b,) per-row
    window: int | None,
    softcap: float | None,
    scale: float,
    rules: AxisRules | None = None,
) -> jax.Array:
    b, hq, _, hd = q.shape
    _, hkv, S, _ = k_cache.shape
    group = hq // hkv
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1  # mixed-progress batched decode

    # §Perf C: sliding-window layers only ever read the last ``window``
    # positions — slice them out (static size) instead of scoring the whole
    # cache with a mask.  At 500k context this is a 128x compute/traffic
    # reduction; correctness is preserved by re-basing the position mask.
    base = 0
    if window is not None and S > 2 * window:
        start = jnp.clip(pos - window + 1, 0, S - window)
        dv = v_cache.shape[-1]
        if per_row:
            # Each row slices ITS OWN window: the slice start is per-row.
            k_cache = jax.vmap(
                lambda c, st: jax.lax.dynamic_slice(
                    c, (0, st, 0), (hkv, window, hd)
                )
            )(k_cache, start)
            v_cache = jax.vmap(
                lambda c, st: jax.lax.dynamic_slice(
                    c, (0, st, 0), (hkv, window, dv)
                )
            )(v_cache, start)
            k_pos = start[:, None] + jnp.arange(window)[None]  # (b, window)
        else:
            k_cache = jax.lax.dynamic_slice(
                k_cache, (0, 0, start, 0), (b, hkv, window, hd)
            )
            v_cache = jax.lax.dynamic_slice(
                v_cache, (0, 0, start, 0), (b, hkv, window, dv)
            )
            k_pos = start + jnp.arange(window)
        base = start
        S = window
    else:
        k_pos = (
            jnp.broadcast_to(jnp.arange(S)[None], (b, S)) if per_row
            else jnp.arange(S)
        )

    # Engine-served decode: with a session installed, the single-token
    # query dispatches through the kv_len-masked decode workload — the
    # cache is consumed at its (bucketed) length S and the number of valid
    # rows rides as a runtime scalar (or a (b,) per-row vector under
    # mixed-progress batched decode: ``pos`` per row, one launch for the
    # whole batch), so cache tails past the last written token may hold
    # ANYTHING (bucket pad, stale bytes) and the selection is static (S),
    # trace-safe.  The inline math below remains the bit-identical
    # fallback for sessionless callers (training harnesses, sharded
    # decode) and for the rare shapes the workload does not cover
    # (MLA-style dv != hd, a non-default scale).
    engine = session.installed_engine()
    if (
        engine is not None
        and v_cache.shape[-1] == hd
        and abs(scale - hd ** -0.5) < 1e-12
    ):
        kv_len = pos - base + 1  # valid rows in (the slice of) the cache
        return engine.dispatch(
            "decode_attention", q, k_cache, v_cache, kv_len,
            window=window, softcap=softcap,
        ).astype(q.dtype)

    # GQA without materializing repeated K/V: fold the group into q's head
    # layout (b, KV, group, 1, hd) and contract against (b, KV, S, hd).
    # NOTE: this inline fallback masks SCORES only — softmax weight 0 at
    # masked rows — so cache tails must be finite here (0 * NaN poisons);
    # the engine path above tolerates garbage tails by zeroing v rows.
    qf = q.astype(jnp.float32).reshape(b, hkv, group, hd)
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qf, kf) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    if per_row:
        mask = k_pos <= pos[:, None]  # (b, S)
        if window is not None:
            mask &= k_pos > pos[:, None] - window
        s = jnp.where(mask[:, None, None, :], s, -1e30)
    else:
        mask = k_pos <= pos
        if window is not None:
            mask &= k_pos > pos - window
        s = jnp.where(mask[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    vf = v_cache.astype(jnp.float32)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vf)
    return out.reshape(b, hq, 1, -1).astype(q.dtype)


def flash_decode_sharded(
    q: jax.Array,        # (b, H, 1, hd)
    k_cache: jax.Array,  # (b, KV, S, hd) — seq-sharded over the TP axis
    v_cache: jax.Array,  # (b, KV, S, dv)
    k_new: jax.Array,    # (b, KV, 1, hd)
    v_new: jax.Array,    # (b, KV, 1, dv)
    pos: jax.Array,
    window: int | None,
    softcap: float | None,
    scale: float,
    rules: AxisRules,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed flash-decode (§Perf B).

    When kv_heads do not divide the TP axis the KV cache must shard on
    sequence; naive attention (and the cache write at a dynamic position)
    then all-gathers the whole cache every layer every token.  Here each
    seq-shard (a) writes the new K/V only if it owns position ``pos``,
    (b) computes a partial online-softmax over its own positions, and
    (c) combines with pmax/psum of (b, KV, group, dv) — bytes per step drop
    from O(cache) to O(heads x head_dim).

    Returns (out, k_cache', v_cache').
    """
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    seq_ax = rules.rules.get("seq")
    b, hq, _, hd = q.shape
    _, hkv, S, dv = v_cache.shape
    group = hq // hkv
    nshard = rules.axis_sizes[seq_ax]
    s_loc = S // nshard
    batch_ax = rules.rules.get("batch")
    bspec = rules.sanitize(P(batch_ax), (b,))
    b_part = bspec[0] if len(bspec) else None

    cache_spec = P(b_part, None, seq_ax, None)
    flat_spec = P(b_part, None, None, None)

    def body(q_, kc, vc, kn, vn, pos_):
        idx = jax.lax.axis_index(seq_ax)
        base = idx * s_loc
        off = pos_ - base
        owned = (off >= 0) & (off < s_loc)
        safe = jnp.clip(off, 0, s_loc - 1)

        def write(c, new):
            upd = jax.lax.dynamic_update_slice(
                c, new.astype(c.dtype), (0, 0, safe, 0)
            )
            return jnp.where(owned, upd, c)

        kc = write(kc, kn)
        vc = write(vc, vn)

        k_pos = base + jnp.arange(s_loc)
        qf = q_.astype(jnp.float32).reshape(-1, hkv, group, hd)
        sc = jnp.einsum("bkgd,bksd->bkgs", qf, kc.astype(jnp.float32))
        sc = sc * scale
        if softcap is not None:
            sc = jnp.tanh(sc / softcap) * softcap
        mask = k_pos <= pos_
        if window is not None:
            mask &= k_pos > pos_ - window
        sc = jnp.where(mask[None, None, None, :], sc, -1e30)

        m_loc = jnp.max(sc, axis=-1)
        m_glob = jax.lax.pmax(m_loc, seq_ax)
        p = jnp.exp(sc - m_glob[..., None])
        l_glob = jax.lax.psum(jnp.sum(p, axis=-1), seq_ax)
        o_loc = jnp.einsum("bkgs,bksd->bkgd", p, vc.astype(jnp.float32))
        o_glob = jax.lax.psum(o_loc, seq_ax)
        out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(-1, hq, 1, dv).astype(q_.dtype), kc, vc

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(flat_spec, cache_spec, cache_spec, flat_spec, flat_spec,
                  P()),
        out_specs=(flat_spec, cache_spec, cache_spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, k_new, v_new, pos)


def decode_seq_sharded(rules: AxisRules, cache_len: int) -> bool:
    """True when a decode KV cache of ``cache_len`` positions shards on the
    sequence axis (the kv heads do not divide the TP axis), so decode runs
    :func:`flash_decode_sharded`."""
    model_size = rules.axis_sizes.get("model", 1)
    return (
        rules.mesh is not None
        and rules.rules.get("seq") is not None
        and rules.rules.get("kv_heads_act") is None
        and cache_len % max(model_size, 1) == 0
        and model_size > 1
    )


def _write_token_rows(
    k_leaf: jax.Array,  # (G, b, KV, S, hd): every layer's K cache, stacked
    v_leaf: jax.Array,
    k: jax.Array,       # (b, KV, 1, hd): this token's K
    v: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    rules: AxisRules,
) -> tuple[jax.Array, jax.Array]:
    """Write one token's K and V into layer ``layer`` of the stacked cache
    leaves in place: every row at scalar ``pos``, or row r at ``pos[r]``
    (one ``dynamic_update_slice`` per row — a scatter or a vmap over the
    row axis compiles to whole-leaf copies).  Each result is pinned to the
    layout the device stores the leaf in, the layout of the program's
    donated input and its output: left free, the compiler lays the carried
    leaves out for the row writes and copies both whole leaves in and out
    of that layout every launch."""
    dev = (
        rules.mesh.devices.flat[0] if rules.mesh is not None
        else jax.devices()[0]
    )
    layout = Layout.from_pjrt_layout(dev.client.get_default_layout(
        k_leaf.dtype, k_leaf.shape, dev
    ))

    def write(leaf, new, start):
        return with_layout_constraint(
            jax.lax.dynamic_update_slice(leaf, new, start), layout
        )

    k = k.astype(k_leaf.dtype)
    v = v.astype(v_leaf.dtype)
    if not getattr(pos, "ndim", 0):
        start = (layer, 0, 0, pos, 0)
        return write(k_leaf, k[None], start), write(v_leaf, v[None], start)

    def row(r, kv):
        start = (layer, r, 0, pos[r], 0)
        return (
            write(kv[0], k[r][None, None], start),
            write(kv[1], v[r][None, None], start),
        )

    return jax.lax.fori_loop(0, k.shape[0], row, (k_leaf, v_leaf))


def attn_forward(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    spec: LayerSpec,
    rules: AxisRules,
    *,
    mode: str,
    positions: jax.Array | None = None,
    cache: dict | None = None,
    pos: jax.Array | None = None,
    cache_len: int = 0,
    causal: bool = True,
    use_rope: bool = True,
    encoder_out: jax.Array | None = None,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """GQA attention with RoPE, sliding window, logit softcap, cross-attn.

    In decode, ``layer`` set means ``cache`` holds the STACKED leaves
    (G, b, KV, S, hd) carried through the layer scan: the new token's K/V
    are written into layer ``layer`` in place and the updated leaves are
    returned.  Otherwise ``cache`` is this layer's own slice."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    if mode == "train":
        # Megatron-SP gather point: leave the residual stream seq-sharded,
        # gather the full sequence only for the mixer body.  Train-only:
        # prefill has no bwd remat interactions and XLA's own placement
        # measured cheaper there (§Perf iteration log).
        x = constrain(x, rules, "batch", None, None)
    q = _split_heads(x @ p["wq"], H)
    k = _split_heads(x @ p["wk"], KV)
    v = _split_heads(x @ p["wv"], KV)
    if mode == "train":
        # Train-only: in prefill these pins fight the seq-sharded cache
        # layout (and replicate k over 'model' when kv_heads_act is None).
        q = constrain(q, rules, "batch", "heads_act", None, None)
        k = constrain(k, rules, "batch", "kv_heads_act", None, None)

    if use_rope:
        if mode == "decode":
            assert pos is not None
            if getattr(pos, "ndim", 0):
                # Per-row positions: (b,) -> tables (b, 1, rot/2), lifted to
                # (b, 1, 1, rot/2) so every row rotates at ITS OWN position.
                cos, sin = rope_tables(
                    pos[:, None], cfg.rotary_dim, cfg.rope_theta
                )
                cos, sin = cos[:, None], sin[:, None]
            else:
                cos, sin = rope_tables(
                    pos[None], cfg.rotary_dim, cfg.rope_theta
                )  # (1, rot/2)
                cos, sin = cos[None, None], sin[None, None]
        else:
            assert positions is not None
            cos, sin = rope_tables(positions, cfg.rotary_dim, cfg.rope_theta)
            cos, sin = cos[None, None], sin[None, None]  # (1,1,s,rot/2)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    scale = hd ** -0.5
    new_cache: dict | None = None
    if mode == "decode" and layer is not None:
        assert cache is not None and pos is not None
        k_leaf, v_leaf = _write_token_rows(
            cache["k"], cache["v"], k, v, layer, pos, rules
        )
        out = _decode_attend(
            q,
            jax.lax.dynamic_index_in_dim(k_leaf, layer, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_leaf, layer, 0, keepdims=False),
            pos, spec.window, cfg.attn_softcap, scale,
        )
        new_cache = {"k": k_leaf, "v": v_leaf}
    elif mode == "decode":
        assert cache is not None and pos is not None
        if decode_seq_sharded(rules, cache["k"].shape[2]):
            out, k_cache, v_cache = flash_decode_sharded(
                q, cache["k"], cache["v"], k, v, pos,
                spec.window, cfg.attn_softcap, scale, rules,
            )
        else:
            if getattr(pos, "ndim", 0):
                # Mixed-progress rows: each row's new K/V lands at ITS OWN
                # position (vmap over batch — per-row dynamic_update_slice).
                def row_write(c, new, p_):
                    return jax.lax.dynamic_update_slice(
                        c, new, (0, p_, 0)
                    )

                k_cache = jax.vmap(row_write)(
                    cache["k"], k.astype(cache["k"].dtype), pos
                )
                v_cache = jax.vmap(row_write)(
                    cache["v"], v.astype(cache["v"].dtype), pos
                )
            else:
                k_cache = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, pos, 0)
                )
                v_cache = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, pos, 0)
                )
            out = _decode_attend(
                q, k_cache, v_cache, pos, spec.window, cfg.attn_softcap,
                scale,
            )
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        engine = session.installed_engine()
        if engine is not None and (mode == "prefill" or not causal):
            # Dynamic-seq serving path: the session engine selects
            # (block_q, block_k) from the scored lattice for this runtime
            # seq, pads to the induced bucket, and serves from the bounded
            # executable cache.  Routed calls: ALL prefill self-attention
            # (causal or not) and non-causal encoder self-attention — the
            # whisper/internvl encoders run their bidirectional stacks in
            # "train" mode even while serving, so the non-causal arm is
            # what puts them on the engine.  Causal train-mode attention
            # stays inline (sessions are serving-scoped; training wants
            # the sharding pins of the chunked scan).
            out = engine.dispatch(
                "attention", q, k, v, causal=causal, window=spec.window,
                softcap=cfg.attn_softcap,
            )
        else:
            out = chunked_attention(
                q, k, v,
                causal=causal,
                window=spec.window,
                softcap=cfg.attn_softcap,
                chunk=ATTN_CHUNK,
                rules=rules if mode == "train" else None,
            )
        if mode == "prefill":
            pad = cache_len - s
            k_cache = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_cache = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            new_cache = {"k": k_cache, "v": v_cache}

    y = _merge_heads(out) @ p["wo"]

    if spec.cross_attn:
        assert encoder_out is not None
        xn = norm(x + y, p["norm_x"], cfg)
        qx = _split_heads(xn @ p["xq"], H)
        kx = _split_heads(encoder_out @ p["xk"], KV)
        vx = _split_heads(encoder_out @ p["xv"], KV)
        ox = chunked_attention(qx, kx, vx, causal=False, chunk=ATTN_CHUNK,
                               rules=rules)
        y = y + _merge_heads(ox) @ p["xo"]

    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_forward(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rules: AxisRules,
    *,
    mode: str,
    positions: jax.Array | None = None,
    cache: dict | None = None,
    pos: jax.Array | None = None,
    cache_len: int = 0,
) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention.

    Train/prefill use the naive (decompressed) form; decode uses the
    *absorbed* form against the compressed ``c_kv``+``k_rope`` cache, which
    is the entire point of MLA (cache bytes ∝ kv_lora_rank, not H*hd).
    """
    m = cfg.mla
    assert m is not None
    b, s, d = x.shape
    H = cfg.n_heads
    nope, rope_d, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    scale = (nope + rope_d) ** -0.5

    cq = rmsnorm(x @ p["wdq"], p["q_norm"])
    q = (cq @ p["wuq"]).reshape(b, s, H, nope + rope_d).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    ckv_full = x @ p["wdkv"]  # (b, s, kv_lora + rope_d)
    c_kv = rmsnorm(ckv_full[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = ckv_full[..., m.kv_lora_rank:][:, None]  # (b, 1, s, rope_d)

    if mode == "decode":
        assert cache is not None and pos is not None
        if getattr(pos, "ndim", 0):
            # Per-row positions (mixed-progress batched decode): rotate at
            # and write to each row's OWN position.
            cos, sin = rope_tables(pos[:, None], rope_d, cfg.rope_theta)
            q_rope = apply_rope(q_rope, cos[:, None], sin[:, None])
            k_rope = apply_rope(k_rope, cos[:, None], sin[:, None])
            ckv_c = jax.vmap(
                lambda c, new, p_: jax.lax.dynamic_update_slice(
                    c, new, (p_, 0)
                )
            )(cache["ckv"], c_kv.astype(cache["ckv"].dtype), pos)
            kr_c = jax.vmap(
                lambda c, new, p_: jax.lax.dynamic_update_slice(
                    c, new, (p_, 0)
                )
            )(cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype),
              pos)
        else:
            cos, sin = rope_tables(pos[None], rope_d, cfg.rope_theta)
            q_rope = apply_rope(q_rope, cos[None, None], sin[None, None])
            k_rope = apply_rope(k_rope, cos[None, None], sin[None, None])
            ckv_c = jax.lax.dynamic_update_slice(
                cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, pos, 0)
            )
            kr_c = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype),
                (0, pos, 0),
            )
        # Absorbed attention: score_h(t) = q_nope_h . (W_uk_h c_t) + q_rope_h . kr_t
        #                               = (W_uk_h^T q_nope_h) . c_t + ...
        wuk = p["wuk"].reshape(m.kv_lora_rank, H, nope)
        q_abs = jnp.einsum("bhqn,chn->bhqc", q_nope.astype(jnp.float32),
                           wuk.astype(jnp.float32))
        s_c = jnp.einsum("bhqc,bkc->bhqk", q_abs,
                         ckv_c.astype(jnp.float32))
        s_r = jnp.einsum("bhqr,bkr->bhqk", q_rope.astype(jnp.float32),
                         kr_c.astype(jnp.float32))
        sc = (s_c + s_r) * scale
        S = ckv_c.shape[1]
        if getattr(pos, "ndim", 0):
            mask = jnp.arange(S)[None] <= pos[:, None]  # (b, S)
            sc = jnp.where(mask[:, None, None], sc, -1e30)
        else:
            mask = jnp.arange(S) <= pos
            sc = jnp.where(mask[None, None, None, :], sc, -1e30)
        pr = jax.nn.softmax(sc, axis=-1)
        out_c = jnp.einsum("bhqk,bkc->bhqc", pr, ckv_c.astype(jnp.float32))
        wuv = p["wuv"].reshape(m.kv_lora_rank, H, dv)
        out = jnp.einsum("bhqc,chv->bhqv", out_c, wuv.astype(jnp.float32))
        out = out.astype(x.dtype)
        new_cache: dict | None = {"ckv": ckv_c, "k_rope": kr_c}
    else:
        assert positions is not None
        cos, sin = rope_tables(positions, rope_d, cfg.rope_theta)
        q_rope = apply_rope(q_rope, cos[None, None], sin[None, None])
        k_rope = apply_rope(k_rope, cos[None, None], sin[None, None])
        k_nope = (c_kv @ p["wuk"]).reshape(b, s, H, nope).transpose(0, 2, 1, 3)
        v = (c_kv @ p["wuv"]).reshape(b, s, H, dv).transpose(0, 2, 1, 3)
        qh = jnp.concatenate([q_nope, q_rope], axis=-1)
        kh = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, H, s, rope_d))], axis=-1
        )
        qh = constrain(qh, rules, "batch", "heads_act", None, None)
        out = chunked_attention(qh, kh, v, causal=True, chunk=ATTN_CHUNK,
                                rules=rules if mode == "train" else None)
        new_cache = None
        if mode == "prefill":
            pad = cache_len - s
            new_cache = {
                "ckv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
                "k_rope": jnp.pad(k_rope[:, 0], ((0, 0), (0, pad), (0, 0))),
            }

    y = _merge_heads(out) @ p["wo"]
    return y, new_cache


# ---------------------------------------------------------------------------
# Mamba-1 selective SSM (falcon-mamba, jamba)
# ---------------------------------------------------------------------------


def _ssm_chunk_scan(
    a: jax.Array, bx: jax.Array, h0: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Linear recurrence h_t = a_t * h_{t-1} + bx_t over one chunk.

    a, bx: (b, L, di, ds); h0: (b, di, ds).  Returns (h_all, h_last).
    Uses an associative scan (parallel prefix) — O(L log L) work but O(log L)
    depth, the TPU-friendly formulation of the selective scan.
    """

    def combine(lhs, rhs):
        al, bl = lhs
        ar, br = rhs
        return al * ar, bl * ar + br

    a_cum, b_cum = jax.lax.associative_scan(combine, (a, bx), axis=1)
    h_all = a_cum * h0[:, None] + b_cum
    return h_all, h_all[:, -1]


def mamba_forward(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rules: AxisRules,
    *,
    mode: str,
    cache: dict | None = None,
    pos: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Mamba-1: in_proj -> causal depthwise conv -> selective scan -> gate."""
    ssm = cfg.ssm
    assert ssm is not None
    b, s, d = x.shape
    di, ds, dc = ssm.d_inner, ssm.d_state, ssm.d_conv
    dtr = ssm.dt_rank or d // 16

    xz = x @ p["in_proj"]
    x_in, z = xz[..., :di], xz[..., di:]
    x_in = constrain(x_in, rules, "batch", None, "ssm_inner")

    if mode == "decode":
        assert cache is not None
        if s != 1:
            # The conv-window concat below assumes EXACTLY one new token:
            # with s > 1 it builds a (b, dc-1+s, di) window whose [:, 1:]
            # slice silently writes a mis-sized/mis-aligned conv state back
            # into the cache (state corruption, no shape error downstream).
            raise ValueError(
                "mamba_forward(mode='decode') consumes one token per step; "
                f"got s={s}. Feed multi-token input through mode='prefill' "
                "(which rebuilds the conv state from the tail) instead."
            )
        # Conv state: the last (dc-1) pre-conv inputs, (b, dc-1, di).
        conv_st = cache["conv"]
        window = jnp.concatenate([conv_st, x_in], axis=1)  # (b, dc, di)
        xc = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32),
                        p["conv_w"].astype(jnp.float32)) + p["conv_b"]
        xc = jax.nn.silu(xc)[:, None]  # (b, 1, di)
        new_conv = window[:, 1:]
    else:
        pad = jnp.pad(x_in, ((0, 0), (dc - 1, 0), (0, 0)))
        xc = jax.lax.conv_general_dilated(
            pad.astype(jnp.float32),
            p["conv_w"].astype(jnp.float32)[:, None, :],  # (k, 1, di)
            window_strides=(1,),
            padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=di,
        ) + p["conv_b"]
        xc = jax.nn.silu(xc).astype(x.dtype)
        new_conv = None
        if mode == "prefill":
            # Conv state: the last (dc-1) pre-conv inputs.
            new_conv = x_in[:, s - (dc - 1):, :] if s >= dc - 1 else jnp.pad(
                x_in, ((0, 0), (dc - 1 - s, 0), (0, 0))
            )

    proj = xc.astype(x.dtype) @ p["x_proj"]  # (b, s, dtr + 2*ds)
    dt_r = proj[..., :dtr]
    B = proj[..., dtr: dtr + ds].astype(jnp.float32)
    C = proj[..., dtr + ds:].astype(jnp.float32)
    dt = jax.nn.softplus(
        (dt_r @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"]
    )  # (b, s, di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (di, ds)
    xcf = xc.astype(jnp.float32)

    if mode == "decode":
        assert cache is not None
        h_prev = cache["ssm"]  # (b, di, ds)
        a = jnp.exp(dt[:, 0, :, None] * A)          # (b, di, ds)
        bx = (dt[:, 0] * xcf[:, 0])[..., None] * B[:, 0][:, None, :]
        h = a * h_prev + bx                          # (b, di, ds)
        y = jnp.einsum("bds,bs->bd", h, C[:, 0]) + p["D"] * xcf[:, 0]
        y = y[:, None]
        new_cache = {"conv": new_conv, "ssm": h}
    else:
        chunk = min(cfg.scan_chunk, s)
        s_pad = -s % chunk  # pad to a chunk multiple (padding contributes 0)
        if s_pad:
            pad2 = lambda t: jnp.pad(t, ((0, 0), (0, s_pad)) + ((0, 0),) * (t.ndim - 2))
            dt, xcf, B, C = pad2(dt), pad2(xcf), pad2(B), pad2(C)
        sp = s + s_pad
        n_chunks = sp // chunk

        def chunk_body(h0, xs):
            dt_c, x_c, B_c, C_c = xs  # (b, L, ...)
            a = jnp.exp(dt_c[..., None] * A)             # (b, L, di, ds)
            bx = (dt_c * x_c)[..., None] * B_c[:, :, None, :]
            h_all, h_last = _ssm_chunk_scan(a, bx, h0)
            y_c = jnp.einsum("blds,bls->bld", h_all, C_c)
            return h_last, y_c

        chunk_body = jax.checkpoint(chunk_body)

        def split(t):  # (b, s, ...) -> (n, b, chunk, ...)
            return t.reshape(b, n_chunks, chunk, *t.shape[2:]).swapaxes(0, 1)

        h0 = jnp.zeros((b, di, ds), jnp.float32)
        h_last, ys = jax.lax.scan(
            chunk_body, h0, (split(dt), split(xcf), split(B), split(C))
        )
        y = ys.swapaxes(0, 1).reshape(b, sp, di)[:, :s] + p["D"] * xcf[:, :s]
        new_cache = None
        if mode == "prefill":
            new_cache = {"conv": new_conv, "ssm": h_last}

    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ p["out_proj"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def _glu_act(cfg: ModelConfig, h: jax.Array, g: jax.Array | None) -> jax.Array:
    if cfg.act == "swiglu":
        return jax.nn.silu(g) * h
    if cfg.act == "geglu":
        return jax.nn.gelu(g) * h
    return jax.nn.gelu(h)


def mlp_forward(
    p: dict, x: jax.Array, cfg: ModelConfig, rules: AxisRules
) -> jax.Array:
    h = x @ p["w_in"]
    g = x @ p["w_gate"] if "w_gate" in p else None
    h = _glu_act(cfg, h, g)
    h = constrain(h, rules, "batch", None, "ff")
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# Lazy handle chain: whole-block prefill with zero boundary copies
# ---------------------------------------------------------------------------
# Engine-served block forward where every dispatch output stays a bucket-
# shaped LazyBucket and the next dispatch consumes the buffer directly
# (DESIGN.md §8).  The non-engine glue between dispatches (norms, rope,
# residual adds, head splits) runs row-locally on the raw buffers via
# lazy_map/LazyBucket.map, so nothing forces a realize inside a block.
# Single-host serving path (launch/serve.py prefill="chained"): handles are
# eager-only, so there is no lax.scan and no sharding constraint here — the
# eager per-op reference (``lazy=False``) runs the identical dispatch
# sequence on plain arrays and is the bit-identity baseline.  The
# repro.core.engine imports are deferred into the function bodies to keep
# this module import-light (see the module-top import comment).


def lazy_matmul(engine, x, w, *, lazy: bool = True):
    """``x @ w`` through the engine's gemm with ``x`` (b, s, d) either a
    plain array or a fully-valid seq-axis LazyBucket (extent == buffer
    seq).  A handle flattens to a (b*s, d) row handle and forwards
    bucket-to-bucket; the output re-wraps on the seq axis, clamped back to
    the chain width if the gemm bucket outgrew it (one counted slice)."""
    from repro.core.engine import LazyBucket

    if (
        lazy and isinstance(x, LazyBucket) and x.axis == 1
        and x.extent == x.buffer.shape[1]
    ):
        b, s, d = x.buffer.shape
        flat = x.rewrap(x.buffer.reshape(b * s, d), extent=b * s, axis=0)
        out = engine.dispatch("gemm", flat, w, lazy=True)
        if isinstance(out, LazyBucket):
            out = out.clamp(b * s)
            return x.rewrap(out.buffer.reshape(b, s, -1))
        return out.reshape(b, s, -1)  # engine fell back to a plain array
    if isinstance(x, LazyBucket):
        x = x.realize()
    b, s, d = x.shape
    out = engine.dispatch("gemm", x.reshape(b * s, d), w)
    return out.reshape(b, s, -1)


def attn_forward_lazy(
    engine,
    p: dict,
    x,
    cfg: ModelConfig,
    spec: LayerSpec,
    *,
    positions: jax.Array,
    causal: bool = True,
    lazy: bool = True,
):
    """Prefill GQA attention as a handle chain: q/k/v projections,
    attention and the output projection all forward bucket-to-bucket.

    ``positions`` must cover the BUFFER seq width (rope is row-local, so
    pad rows get real rotations applied to garbage — confined).  Returns
    ``(y, {"k": k, "v": v})`` where k/v are the post-rope head-split
    projections — (b, KV, s, hd) handles on the seq axis, which serving
    consumes directly as kv-cache bucket buffers.
    """
    from repro.core.engine import LazyBucket

    H, KV = cfg.n_heads, cfg.n_kv_heads

    q = lazy_matmul(engine, x, p["wq"], lazy=lazy)
    k = lazy_matmul(engine, x, p["wk"], lazy=lazy)
    v = lazy_matmul(engine, x, p["wv"], lazy=lazy)

    def split(t, n):
        if isinstance(t, LazyBucket):
            return t.rewrap(_split_heads(t.buffer, n), axis=2)
        return _split_heads(t, n)

    q, k, v = split(q, H), split(k, KV), split(v, KV)

    if cfg.use_rope:
        cos, sin = rope_tables(positions, cfg.rotary_dim, cfg.rope_theta)
        cos, sin = cos[None, None], sin[None, None]  # (1, 1, s, rot/2)

        def rope(t):
            return apply_rope(t, cos, sin)

        q = q.map(rope) if isinstance(q, LazyBucket) else rope(q)
        k = k.map(rope) if isinstance(k, LazyBucket) else rope(k)

    out = engine.dispatch(
        "attention", q, k, v, causal=causal, window=spec.window,
        softcap=cfg.attn_softcap, lazy=lazy,
    )
    sp = (x.buffer if isinstance(x, LazyBucket) else x).shape[1]
    if isinstance(out, LazyBucket):
        out = out.clamp(sp)
        merged = out.rewrap(_merge_heads(out.buffer), axis=1)
    else:
        merged = _merge_heads(out)
    y = lazy_matmul(engine, merged, p["wo"], lazy=lazy)
    return y, {"k": k, "v": v}


def mlp_forward_lazy(engine, p: dict, x, cfg: ModelConfig, *,
                     lazy: bool = True):
    """Dense MLP as a handle chain (activation via lazy_map, row-local)."""
    from repro.core.engine import lazy_map

    h = lazy_matmul(engine, x, p["w_in"], lazy=lazy)
    if "w_gate" in p:
        g = lazy_matmul(engine, x, p["w_gate"], lazy=lazy)
        h = lazy_map(lambda a, b: _glu_act(cfg, a, b), h, g)
    else:
        h = lazy_map(lambda a: _glu_act(cfg, a, None), h)
    return lazy_matmul(engine, h, p["w_out"], lazy=lazy)


def block_forward_lazy(
    engine,
    p: dict,
    x,
    cfg: ModelConfig,
    spec: LayerSpec,
    *,
    positions: jax.Array,
    causal: bool = True,
    lazy: bool = True,
):
    """One transformer block (attn mixer + dense/none MLP) as a handle
    chain: the attention→projection→MLP sequence passes LazyBuckets across
    every engine boundary; norms and residual adds ride lazy_map.  Returns
    ``(x, kv)`` with kv the layer's k/v handles for the serving cache."""
    from repro.core.engine import lazy_map

    assert spec.mixer == "attn" and spec.mlp in ("dense", "none") \
        and not spec.cross_attn, "lazy chain serves plain attn blocks only"
    h = lazy_map(lambda t: norm(t, p["norm_mixer"], cfg), x)
    y, kv = attn_forward_lazy(
        engine, p["attn"], h, cfg, spec,
        positions=positions, causal=causal, lazy=lazy,
    )
    x = lazy_map(jnp.add, x, y)
    if spec.mlp != "none":
        h = lazy_map(lambda t: norm(t, p["norm_mlp"], cfg), x)
        y = mlp_forward_lazy(engine, p["mlp"], h, cfg, lazy=lazy)
        x = lazy_map(jnp.add, x, y)
    return x, kv


def _expert_ffn(
    p: dict, buf: jax.Array, cfg: ModelConfig, counts: jax.Array | None = None
) -> jax.Array:
    """buf: (g, E, C, d) -> (g, E, C, d) through per-expert FFNs.

    ``counts`` (optional (g, E) i32) is each expert slab's TRUE row count —
    rows past it are routing pad (zero-filled by ``moe_forward``).  When an
    engine session is installed and the call is eager, the three dense
    einsums collapse to three ``grouped_gemm`` dispatches: ONE bucketed
    masked-tail launch each for all g*E expert slabs, with the capacity as
    the dynamic (bucketed) extent and the per-slab counts riding in as the
    runtime extent vector.  The inline einsums below stay the bit-identical
    fallback for sessionless callers and for traced calls inside scanned
    model blocks (where engine-owned staging buffers must not be captured).
    """
    engine = session.installed_engine()
    if (
        engine is not None
        and counts is not None
        and not isinstance(buf, jax.core.Tracer)
    ):
        g, E, C, d = buf.shape
        # Expert-major group layout: (g, E, C, d) -> (E*g, C, d), so the
        # r = g consecutive groups of each expert share one weight-stack
        # entry (the grouped_gemm contract: weight index = group // r).
        xs = jnp.transpose(buf, (1, 0, 2, 3)).reshape(E * g, C, d)
        cnt = jnp.transpose(
            jnp.asarray(counts, jnp.int32), (1, 0)
        ).reshape(E * g)
        h = engine.dispatch("grouped_gemm", xs, p["w_in"], cnt)
        gate = (
            engine.dispatch("grouped_gemm", xs, p["w_gate"], cnt)
            if "w_gate" in p else None
        )
        h = _glu_act(cfg, h, gate)
        out = engine.dispatch("grouped_gemm", h, p["w_out"], cnt)
        return jnp.transpose(out.reshape(E, g, C, d), (1, 0, 2, 3))

    h = jnp.einsum("gecd,edf->gecf", buf, p["w_in"])
    g = (
        jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
        if "w_gate" in p else None
    )
    h = _glu_act(cfg, h, g)
    return jnp.einsum("gecf,efd->gecd", h, p["w_out"])


def moe_forward(
    p: dict, x: jax.Array, cfg: ModelConfig, rules: AxisRules
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routed MoE with sort-based, capacity-bounded dispatch.

    The batch dim doubles as the GShard "group": routing, sorting and
    capacity-dropping are per-sequence, so the sort never crosses the
    data-parallel shard boundary.  Expert buffers are sharded over the
    expert (EP) axis.  Returns ``(y, aux_load_balance_loss, dropped_frac)``
    — ``dropped_frac`` is the fraction of (token, choice) assignments the
    capacity bound silently zeroed (a dropped assignment contributes 0 to
    its token's weighted combine, NOT a renormalized mix of the surviving
    experts); it is exactly 0 whenever every expert's load fits its
    capacity, which capacity_factor >= 1.0 guarantees only under perfectly
    uniform routing.
    """
    m = cfg.moe
    assert m is not None
    b, s, d = x.shape
    E, k = m.num_experts, m.top_k
    C = max(1, int(math.ceil(s * k * m.capacity_factor / E)))

    # §Perf A2: routing/sort/dispatch must run on seq-REPLICATED activations
    # (one all-gather here); a seq-sharded input turns the per-group argsort
    # into a distributed bitonic sort (~50 GB/dev/layer of all-to-all).
    # Skip at s==1 (decode): the sort is trivial there, and pinning the
    # batch axis forces XLA to all-gather FSDP weights instead of psum'ing
    # tiny decode activations (observed 20x regression on deepseek decode).
    if s > 1:
        x = constrain(x, rules, "batch", None, None)
    xf = x.astype(jnp.float32)
    logits = jnp.einsum("gtd,de->gte", xf, p["router"])  # (b, s, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)  # (b, s, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    # Aux loss (Switch): E * sum_e f_e * P_e over all tokens.
    ids_1h = jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32)
    f_e = jnp.mean(ids_1h, axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e)

    # ---- per-group sort-based dispatch, GATHER-ONLY --------------------
    # No scatter anywhere: XLA's SPMD partitioner replicates vmapped
    # scatters ("involuntary full rematerialization"), which cascaded a
    # batch-replication through the whole layer (§Perf A2').  Gathers and
    # per-row sorts partition cleanly over the batch axis.
    S = s * k
    flat_e = topi.reshape(b, S)                        # (g, S)
    order = jnp.argsort(flat_e, axis=-1, stable=True)  # sorted-pos -> flat
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    # Start offset of each expert's segment in the sorted order.
    first = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left")
    )(sorted_e)                                        # (g, E)

    # Forward map: slot (e, c) <- sorted position first[e] + c.
    p_grid = first[:, :, None] + jnp.arange(C)[None, None, :]  # (g, E, C)
    p_clip = jnp.minimum(p_grid, S - 1)
    e_at_p = jnp.take_along_axis(
        sorted_e, p_clip.reshape(b, E * C), axis=-1
    ).reshape(b, E, C)
    valid = (p_grid < S) & (
        e_at_p == jnp.arange(E)[None, :, None]
    )                                                  # (g, E, C)
    src_flat = jnp.take_along_axis(
        order, p_clip.reshape(b, E * C), axis=-1
    )                                                  # (g, E*C) flat idx
    token_idx = src_flat // k                          # (g, E*C) token idx
    buf = jnp.take_along_axis(x, token_idx[..., None], axis=1)
    buf = jnp.where(valid.reshape(b, E * C, 1), buf, 0).reshape(b, E, C, d)
    # Per-(group, expert) TRUE row counts: ``valid`` marks a contiguous
    # prefix of each slab (the sorted segment, capacity-clipped), so the
    # sum IS the extent the grouped-GEMM kernel masks at.
    counts = jnp.sum(valid.astype(jnp.int32), axis=-1)  # (g, E)
    if s > 1:
        # Prefill: pin the expert buffers to the (batch, expert) sharding
        # so the FFN einsums partition over the EP axis.
        buf = constrain(buf, rules, "batch", "expert", None, None)
    # s == 1 (decode): skip the pin — constraining tiny single-token
    # activations makes XLA all-gather the FSDP-sharded expert weights
    # instead of psum'ing the small activations (same pathology as the
    # routing note above; observed 20x regression on deepseek decode).

    out_buf = _expert_ffn(p, buf, cfg, counts=counts)
    if s > 1:  # prefill: keep the output on the same (batch, expert) pin
        out_buf = constrain(out_buf, rules, "batch", "expert", None, None)
    out_flat = out_buf.reshape(b, E * C, d)

    # Return map: flat position f=(t, j) sits at sorted position inv[f];
    # its slot is (flat_e[f], inv[f] - first[flat_e[f]]).
    inv = jnp.argsort(order, axis=-1)                  # flat -> sorted pos
    first_of = jnp.take_along_axis(first, flat_e, axis=-1)   # (g, S)
    pos_in_e = inv - first_of
    # Capacity bound: assignments past an expert's C-th slot are DROPPED —
    # their contribution to the weighted combine is zero.  Surface the
    # drop rate instead of losing tokens silently.
    kept = pos_in_e < C
    dropped_frac = 1.0 - jnp.mean(kept.astype(jnp.float32))
    out_idx = jnp.minimum(flat_e * C + pos_in_e, E * C - 1)
    y_tok = jnp.take_along_axis(out_flat, out_idx[..., None], axis=1)
    y_tok = jnp.where(kept[..., None], y_tok, 0).astype(jnp.float32)
    y_tok = y_tok * topw.reshape(b, S)[..., None]
    y = jnp.sum(y_tok.reshape(b, s, k, d), axis=2).astype(x.dtype)

    if m.num_shared:
        h = x @ p["shared_in"]
        g = x @ p["shared_gate"] if "shared_gate" in p else None
        h = _glu_act(cfg, h, g)
        y = y + h @ p["shared_out"]
    return y, aux, dropped_frac
