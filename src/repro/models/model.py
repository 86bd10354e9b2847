"""Unified model: dense / MoE / SSM / hybrid / enc-dec / VLM from one config.

A model is ``n_groups`` repetitions of a layer ``pattern``.  Parameters for
each pattern position are stacked over groups and the forward pass is a
``lax.scan`` over groups (compact HLO — essential for lowering 236B-scale
configs in the dry-run).  Each scanned group body is rematerialized
(``jax.checkpoint``) in training mode.

Entry points:
  * :func:`forward`    — logits for train/prefill/decode,
  * :func:`make_cache` / :func:`abstract_cache` / :func:`cache_pspecs`,
  * :func:`loss_fn`    — next-token cross entropy (+ MoE aux loss).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import (
    attn_forward,
    decode_seq_sharded,
    mamba_forward,
    mla_forward,
    mlp_forward,
    moe_forward,
    norm,
)
from repro.models.partitioning import AxisRules, constrain

__all__ = [
    "forward",
    "decode_updates_in_place",
    "loss_fn",
    "make_cache",
    "abstract_cache",
    "cache_pspecs",
]


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _cache_entry_defs(
    cfg: ModelConfig, spec: LayerSpec, batch: int, cache_len: int
) -> dict[str, tuple[tuple[int, ...], Any]]:
    """(shape, dtype) per cache tensor for one pattern position (un-stacked)."""
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    if spec.mixer == "attn":
        # The cache is allocated full-length even for sliding-window layers
        # (decode indexes with the absolute position); a ring-buffer windowed
        # cache is a recorded perf follow-up in EXPERIMENTS.md §Perf.
        shape = (batch, cfg.n_kv_heads, cache_len, hd)
        return {"k": (shape, dt), "v": (shape, dt)}
    if spec.mixer == "mla":
        m = cfg.mla
        return {
            "ckv": ((batch, cache_len, m.kv_lora_rank), dt),
            "k_rope": ((batch, cache_len, m.qk_rope_dim), dt),
        }
    if spec.mixer == "mamba":
        s = cfg.ssm
        return {
            "conv": ((batch, s.d_conv - 1, s.d_inner), dt),
            "ssm": ((batch, s.d_inner, s.d_state), jnp.float32),
        }
    raise ValueError(spec.mixer)


def make_cache(
    cfg: ModelConfig, batch: int, cache_len: int, abstract: bool = False
) -> dict:
    """Decode cache pytree; leaves have a leading group axis."""
    G = cfg.n_groups

    def mk(shape, dt):
        full = (G,) + shape
        if abstract:
            return jax.ShapeDtypeStruct(full, dt)
        return jnp.zeros(full, dt)

    cache: dict[str, Any] = {}
    for p, spec in enumerate(cfg.pattern):
        defs = _cache_entry_defs(cfg, spec, batch, cache_len)
        cache[f"pos{p}"] = {k: mk(s, d) for k, (s, d) in defs.items()}
    if cfg.encoder_decoder:
        eo = (batch, cfg.encoder_seq, cfg.d_model)
        cache["encoder_out"] = (
            jax.ShapeDtypeStruct(eo, jnp.dtype(cfg.dtype)) if abstract
            else jnp.zeros(eo, jnp.dtype(cfg.dtype))
        )
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    return make_cache(cfg, batch, cache_len, abstract=True)


def cache_pspecs(
    cfg: ModelConfig, rules: AxisRules, batch: int, cache_len: int
) -> dict:
    """PartitionSpecs matching make_cache's structure (sanitized against the
    actual shapes, so jit accepts them as in/out shardings).

    KV caches shard on the kv-head axis when it divides the model axis,
    otherwise on the sequence axis (long-context: the cache is the dominant
    HBM consumer and MUST shard on something model-sized).
    """
    batch_ax = rules.rules.get("batch")
    model = rules.rules.get("ff")  # the TP axis name ("model") or None
    kv_ok = rules.rules.get("kv_heads_act") is not None

    out: dict[str, Any] = {}
    for p, spec in enumerate(cfg.pattern):
        defs = _cache_entry_defs(cfg, spec, batch, cache_len)
        if spec.mixer == "attn":
            raw = (
                P(None, batch_ax, model, None, None) if kv_ok
                else P(None, batch_ax, None, model, None)
            )
            entry = {"k": raw, "v": raw}
        elif spec.mixer == "mla":
            entry = {
                "ckv": P(None, batch_ax, model, None),
                "k_rope": P(None, batch_ax, None, None),
            }
        else:  # mamba
            entry = {
                "conv": P(None, batch_ax, None, model),
                "ssm": P(None, batch_ax, model, None),
            }
        out[f"pos{p}"] = {
            k: rules.sanitize(entry[k], (cfg.n_groups,) + defs[k][0])
            for k in entry
        }
    if cfg.encoder_decoder:
        out["encoder_out"] = rules.sanitize(
            P(batch_ax, None, None),
            (batch, cfg.encoder_seq, cfg.d_model),
        )
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _apply_layer(
    cfg: ModelConfig,
    spec: LayerSpec,
    rules: AxisRules,
    p: dict,
    x: jax.Array,
    *,
    mode: str,
    positions: jax.Array | None,
    cache: dict | None,
    pos: jax.Array | None,
    cache_len: int,
    encoder_out: jax.Array | None,
    causal: bool = True,
    use_rope: bool = True,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, dict | None, jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    dropped = jnp.zeros((), jnp.float32)
    h = norm(x, p["norm_mixer"], cfg)
    if spec.mixer == "attn":
        y, new_cache = attn_forward(
            p["attn"], h, cfg, spec, rules,
            mode=mode, positions=positions, cache=cache, pos=pos,
            cache_len=cache_len,
            causal=causal, use_rope=use_rope, encoder_out=encoder_out,
            layer=layer,
        )
    elif spec.mixer == "mla":
        y, new_cache = mla_forward(
            p["mla"], h, cfg, rules,
            mode=mode, positions=positions, cache=cache, pos=pos,
            cache_len=cache_len,
        )
    else:
        y, new_cache = mamba_forward(
            p["mamba"], h, cfg, rules, mode=mode, cache=cache, pos=pos,
        )
    x = x + y
    if spec.mlp != "none":
        h = norm(x, p["norm_mlp"], cfg)
        if spec.mlp == "dense":
            y = mlp_forward(p["mlp"], h, cfg, rules)
        else:
            y, aux, dropped = moe_forward(p["moe"], h, cfg, rules)
        x = x + y
    if mode != "decode":
        # Decode streams are tiny (s=1): pinning their batch axis flips
        # XLA from activation-psum to FSDP weight gathers (§Perf log).
        x = constrain(x, rules, "batch", "seq", None)
    return x, new_cache, aux, dropped


def _encode(
    cfg: ModelConfig, rules: AxisRules, params: dict, frames: jax.Array
) -> jax.Array:
    """Whisper-style bidirectional encoder over (stubbed) frame embeddings."""
    enc = params["encoder"]
    b, s, d = frames.shape
    pos = jnp.arange(s)
    half = d // 2
    freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    x = frames + pe[None].astype(frames.dtype)
    spec = LayerSpec(mixer="attn", mlp="dense")

    def body(x, p):
        x, _, _, _ = _apply_layer(
            cfg, spec, rules, p, x,
            mode="train", positions=pos, cache=None, pos=None,
            cache_len=0, encoder_out=None, causal=False, use_rope=False,
        )
        return x, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return norm(x, enc["final_norm"], cfg)


def decode_updates_in_place(
    cfg: ModelConfig, rules: AxisRules, cache_len: int
) -> bool:
    """True when decode updates the stacked cache in place: every layer is
    self-attention with a k/v cache (no MLA, mamba state, cross-attention
    or encoder output) and the cache is not sequence-sharded.  The stacked
    leaves then ride the layer scan's carry and each layer writes only the
    new token's rows into them; otherwise each layer returns its rewritten
    slice and the scan restacks them into a new cache."""
    return (
        not cfg.encoder_decoder
        and all(
            spec.mixer == "attn" and not spec.cross_attn
            for spec in cfg.pattern
        )
        and not decode_seq_sharded(rules, cache_len)
    )


def forward(
    cfg: ModelConfig,
    rules: AxisRules,
    params: dict,
    tokens: jax.Array,
    *,
    mode: str = "train",
    cache: dict | None = None,
    pos: jax.Array | None = None,
    cache_len: int = 0,
    vision_embeds: jax.Array | None = None,
    encoder_frames: jax.Array | None = None,
    remat: bool = True,
    return_moe_stats: bool = False,
) -> tuple:
    """Run the model.

    Args:
      tokens: (b, s) int32 — s == 1 in decode mode.
      mode: "train" | "prefill" | "decode".
      cache/pos: decode state (cache from make_cache / a prior prefill).
        ``pos`` is a scalar i32 (every batch row at the same position) or
        a (b,) i32 vector giving each row its OWN position — one decode
        step serving rows at mixed progress (continuous batching).
      vision_embeds: (b, vision_prefix, d) precomputed patch embeddings
        (VLM frontend stub) — overwrite the first positions' embeddings.
      encoder_frames: (b, encoder_seq, d) precomputed audio-frame embeddings
        (audio frontend stub) for encoder-decoder configs.
      return_moe_stats: append a routing-stats dict to the return tuple —
        currently ``{"dropped_frac": mean fraction of (token, choice)
        assignments zeroed by the MoE capacity bound, averaged over MoE
        layers}``.  Kept opt-in so the default 3-tuple stays stable.
    Returns:
      (logits, new_cache | None, aux_loss[, moe_stats])
    """
    b, s = tokens.shape
    d = cfg.d_model
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * math.sqrt(d)).astype(x.dtype)
    if not cfg.use_rope:
        # Sinusoidal absolute positions (whisper-style backbone).  Decode
        # ``pos`` may be a scalar (whole batch at one position) or a (b,)
        # per-row vector (mixed-progress batched decode): p_idx is kept
        # 2-D (rows, s) with rows in {1, b} so pe broadcasts either way.
        if mode == "decode":
            p = jnp.asarray(pos)
            p_idx = (p.reshape(1, 1) if p.ndim == 0 else p[:, None])
        else:
            p_idx = jnp.arange(s)[None]
        p_idx = p_idx.astype(jnp.float32)
        half = d // 2
        freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = p_idx[..., None] * freq
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + pe.astype(x.dtype)
    if vision_embeds is not None and mode != "decode":
        nv = vision_embeds.shape[1]
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x[:, nv:]], axis=1)
    if mode != "decode":
        x = constrain(x, rules, "batch", "seq", None)

    encoder_out = None
    if cfg.encoder_decoder:
        if mode == "decode":
            assert cache is not None
            encoder_out = cache["encoder_out"]
        else:
            assert encoder_frames is not None
            encoder_out = _encode(cfg, rules, params, encoder_frames)

    positions = None if mode == "decode" else jnp.arange(s)
    aux_total = jnp.zeros((), jnp.float32)
    dropped_total = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}

    n_pos = len(cfg.pattern)
    in_place = mode == "decode" and decode_updates_in_place(
        cfg, rules, cache_len
    )

    def carried_body(carry, xs):
        # The stacked leaves ride the carry: layer g writes its new K/V
        # rows into them in place and reads its slice back for attention.
        x, aux, dropped, kv = carry
        p_slices, g = xs
        kv = list(kv)
        for i in range(n_pos):
            x, kv[i], aux_i, dropped_i = _apply_layer(
                cfg, cfg.pattern[i], rules, p_slices[i], x,
                mode=mode, positions=None, cache=kv[i], pos=pos,
                cache_len=cache_len, encoder_out=None,
                use_rope=cfg.use_rope, layer=g,
            )
            aux = aux + aux_i
            dropped = dropped + dropped_i
        return (x, aux, dropped, tuple(kv)), None

    def group_body(carry, xs):
        x, aux, dropped = carry
        p_slices, c_slices = xs
        new_c = []
        for i in range(n_pos):
            x, nc, aux_i, dropped_i = _apply_layer(
                cfg, cfg.pattern[i], rules, p_slices[i], x,
                mode=mode, positions=positions,
                cache=c_slices[i] if c_slices is not None else None,
                pos=pos, cache_len=cache_len, encoder_out=encoder_out,
                use_rope=cfg.use_rope,
            )
            new_c.append(nc)
            aux = aux + aux_i
            dropped = dropped + dropped_i
        ys = tuple(new_c) if mode != "train" else None
        return (x, aux, dropped), ys

    if remat and mode == "train":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable
        )

    p_stacked = tuple(params[f"pos{i}"] for i in range(n_pos))
    c_stacked = (
        tuple(cache[f"pos{i}"] for i in range(n_pos))
        if mode == "decode" else None
    )
    if in_place:
        (x, aux_total, dropped_total, kv), _ = jax.lax.scan(
            carried_body, (x, aux_total, dropped_total, c_stacked),
            (p_stacked, jnp.arange(cfg.n_groups, dtype=jnp.int32)),
        )
        for i in range(n_pos):
            new_cache[f"pos{i}"] = kv[i]
    else:
        (x, aux_total, dropped_total), ys = jax.lax.scan(
            group_body, (x, aux_total, dropped_total), (p_stacked, c_stacked)
        )
        if ys is not None:
            for i in range(n_pos):
                new_cache[f"pos{i}"] = ys[i]
            if cfg.encoder_decoder:
                new_cache["encoder_out"] = encoder_out

    x = norm(x, params["final_norm"], cfg)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    logits = constrain(logits, rules, "batch", None, "vocab")
    if cfg.logit_softcap is not None:
        lf = logits.astype(jnp.float32)
        logits = (jnp.tanh(lf / cfg.logit_softcap) * cfg.logit_softcap).astype(
            logits.dtype
        )
    if cfg.vocab_padded != cfg.vocab:
        # Mask the padding columns so softmax/argmax never see them.
        col = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1
        )
        logits = jnp.where(col < cfg.vocab, logits, -1e30)
    ret = (logits, new_cache or None, aux_total / max(cfg.n_layers, 1))
    if return_moe_stats:
        n_moe = cfg.n_groups * sum(
            1 for spec in cfg.pattern if spec.mlp == "moe"
        )
        ret += ({"dropped_frac": dropped_total / max(n_moe, 1)},)
    return ret


def loss_fn(
    cfg: ModelConfig,
    rules: AxisRules,
    params: dict,
    tokens: jax.Array,
    labels: jax.Array,
    *,
    aux_weight: float = 0.01,
    **fwd_kwargs,
) -> tuple[jax.Array, dict]:
    """Mean next-token cross entropy (+ weighted MoE aux loss).

    Metrics carry ``dropped_frac`` next to the aux loss: the capacity bound
    zeroes over-capacity expert assignments SILENTLY in the forward pass,
    so the drop rate must be observable wherever the loss is.
    """
    logits, _, aux, moe_stats = forward(
        cfg, rules, params, tokens, mode="train", return_moe_stats=True,
        **fwd_kwargs
    )
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    xent = jnp.mean(lse - ll)
    total = xent + aux_weight * aux
    return total, {
        "xent": xent, "aux": aux,
        "dropped_frac": moe_stats["dropped_frac"],
    }
