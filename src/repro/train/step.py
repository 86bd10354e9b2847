"""Train / prefill / decode step builders, plus their input specs.

``make_train_step`` builds the full training step: microbatch gradient
accumulation (lax.scan, so the HLO stays one loop), remat'd forward, AdamW
with warmup+cosine LR, optional error-feedback int8 compression of the
cross-pod gradient hop.  These are the functions the multi-pod dry-run
lowers and compiles for every (arch x shape) cell.

Input stand-ins (``*_input_specs``) are ShapeDtypeStructs — the dry-run
never allocates a batch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, ShapeSpec
from repro.models.model import forward, loss_fn, make_cache
from repro.models.partitioning import AxisRules
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.optim.schedule import linear_warmup_cosine

__all__ = [
    "TrainHParams",
    "make_train_step",
    "make_prefill_step",
    "make_decode_step",
    "train_input_specs",
    "serve_input_specs",
]


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    base_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    num_microbatches: int = 1
    adamw: AdamWConfig = AdamWConfig()
    aux_weight: float = 0.01


def _split_batch(batch: dict, num_mb: int) -> dict:
    """(B, ...) -> (num_mb, B/num_mb, ...) for every batch leaf."""

    def split(x):
        b = x.shape[0]
        assert b % num_mb == 0, (b, num_mb)
        return x.reshape(num_mb, b // num_mb, *x.shape[1:])

    return jax.tree.map(split, batch)


def make_train_step(
    cfg: ModelConfig,
    rules: AxisRules,
    hp: TrainHParams,
    grad_pspecs=None,
):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  ``batch`` holds tokens/labels (+ modality extras).

    ``grad_pspecs`` (a PartitionSpec tree matching params) pins the
    microbatch gradient accumulator's sharding: without it XLA may keep the
    accumulator replicated and all-reduce full gradients every microbatch
    (§Perf A4); with it the per-microbatch reduction becomes a
    reduce-scatter onto the FSDP shards.
    """

    def mb_loss(params, mb):
        extras = {
            k: mb[k]
            for k in ("vision_embeds", "encoder_frames")
            if k in mb
        }
        return loss_fn(
            cfg, rules, params, mb["tokens"], mb["labels"],
            aux_weight=hp.aux_weight, **extras,
        )

    grad_fn = jax.value_and_grad(mb_loss, has_aux=True)

    def pin_grads(g):
        if grad_pspecs is None or rules.mesh is None:
            return g
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda t, s: jax.lax.with_sharding_constraint(
                t, NamedSharding(rules.mesh, s)
            ),
            g,
            grad_pspecs,
        )

    def train_step(params, opt_state, batch):
        if hp.num_microbatches <= 1:
            (loss, parts), grads = grad_fn(params, batch)
        else:
            mbs = _split_batch(batch, hp.num_microbatches)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, _parts), g = grad_fn(params, mb)
                g_acc = pin_grads(jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g
                ))
                return (g_acc, l_acc + l), None

            g0 = pin_grads(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ))
            (g_sum, l_sum), _ = jax.lax.scan(acc_body, (g0, 0.0), mbs)
            inv = 1.0 / hp.num_microbatches
            grads = jax.tree.map(lambda g: g * inv, g_sum)
            loss = l_sum * inv
            parts = {}

        lr = linear_warmup_cosine(
            opt_state["step"], hp.base_lr, hp.warmup_steps, hp.total_steps
        )
        params, opt_state = adamw_update(
            hp.adamw, params, grads, opt_state, lr
        )
        metrics = {"loss": loss, "lr": lr}
        metrics.update({k: v for k, v in parts.items()})
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, rules: AxisRules, cache_len: int):
    """prefill(params, batch) -> (last_logits, cache)."""

    def prefill_step(params, batch):
        extras = {
            k: batch[k]
            for k in ("vision_embeds", "encoder_frames")
            if k in batch
        }
        logits, cache, _ = forward(
            cfg, rules, params, batch["tokens"], mode="prefill",
            cache_len=cache_len, **extras,
        )
        # The logits of the last real prompt position (``batch["last"]``,
        # when the prompt is padded to a bucket) predict the first token.
        last = batch.get("last", logits.shape[1] - 1)
        return jax.lax.dynamic_index_in_dim(logits, last, 1, False), cache

    return prefill_step


def make_decode_step(cfg: ModelConfig, rules: AxisRules, cache_len: int):
    """decode(params, cache, tokens(b,1), pos) -> (logits(b,vocab), cache)."""

    def decode_step(params, cache, tokens, pos):
        logits, new_cache, _ = forward(
            cfg, rules, params, tokens, mode="decode",
            cache=cache, pos=pos, cache_len=cache_len,
        )
        return logits[:, 0], new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins) + their PartitionSpecs
# ---------------------------------------------------------------------------


def _batch_axes(rules: AxisRules):
    return rules.rules.get("batch")


def train_input_specs(
    cfg: ModelConfig, shape: ShapeSpec, rules: AxisRules
) -> tuple[dict, dict]:
    """(ShapeDtypeStruct batch, PartitionSpec batch) for a training cell."""
    b, s = shape.global_batch, shape.seq_len
    batch_ax = _batch_axes(rules)
    bspec = rules.sanitize(P(batch_ax), (b,))
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    pspecs = {"tokens": bspec, "labels": bspec}
    if cfg.vision_prefix:
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vision_prefix, cfg.d_model), jnp.dtype(cfg.dtype)
        )
        pspecs["vision_embeds"] = bspec
    if cfg.encoder_decoder:
        specs["encoder_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype)
        )
        pspecs["encoder_frames"] = bspec
    return specs, pspecs


def serve_input_specs(
    cfg: ModelConfig, shape: ShapeSpec, rules: AxisRules
) -> tuple[dict, dict]:
    """Inputs for prefill (full request) or decode (one token)."""
    b, s = shape.global_batch, shape.seq_len
    batch_ax = _batch_axes(rules)
    bspec = rules.sanitize(P(batch_ax), (b,))
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        pspecs = {"tokens": bspec}
        if cfg.vision_prefix:
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.vision_prefix, cfg.d_model), jnp.dtype(cfg.dtype)
            )
            pspecs["vision_embeds"] = bspec
        if cfg.encoder_decoder:
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype)
            )
            pspecs["encoder_frames"] = bspec
        return specs, pspecs
    # decode: one new token against a cache of length s
    specs = {
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    pspecs = {"tokens": bspec, "pos": P()}
    return specs, pspecs
