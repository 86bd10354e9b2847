"""Plain float32 reference of the gated, grouped-query decoder as the
program runs it (phi4-mini's form).

Pre-norm decoder blocks: RMSNorm, causal self-attention with ``heads``
query heads sharing ``kv_heads`` key/value heads (query head h reads kv
head h // (heads / kv_heads)), split-half rotary embeddings over the first
``int(head_dim * partial_rotary_factor)`` dims of each head (the rest pass
through unrotated; the factor is 1.0 where ``sizes`` does not state it), a
SwiGLU MLP ``(silu(h @ w_gate) * (h @ w_in)) @ w_out``, and a tied LM head
over the first ``vocab`` embedding rows.  No biases, no dropout.

It imports nothing of the program under test.  It reads the weights in the
layout ``bench/harness/weights.py`` makes (see
``bench/reference/dense_decoder.py``, whose float32 GEMM, float8 control,
padding and LM-head slicing it shares), computes RMSNorm, SwiGLU and
``heads % kv_heads == 0``, and refuses any other form.  One sequence at a
time, layer by layer, in float32 at ``precision=HIGHEST``: each layer's
weights and each LM-head slice of ``HEAD_BLOCK`` rows are cast to float32
inside their own call, so no float32 copy of the whole tree is ever held.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_decoder import HEAD_BLOCK, PAD, _HI, _embed, _head, _mm

__all__ = ["HEAD_BLOCK", "PAD", "logits", "rotary_dim"]


def rotary_dim(sizes: dict) -> int:
    """Leading dims of each head that rotate."""
    return int(sizes["head_dim"] * sizes.get("partial_rotary_factor", 1.0))


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        w.astype(jnp.float32)
    )


def _rope(x: jax.Array, theta: float, rot: int) -> jax.Array:
    """Split-half rotation of the first ``rot`` dims of (s, heads, hd) at
    frequencies ``theta^(-2i/rot)``; dims ``rot:`` pass through."""
    s, half = x.shape[0], rot // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq  # (s, half)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _block(sizes, p, x, fp8):
    z = dict(sizes)
    s = x.shape[0]
    h_, kv, hd = z["heads"], z["kv_heads"], z["head_dim"]
    rot = rotary_dim(z)
    a = p["attn"]
    h = _rms(x, p["norm_mixer"], z["norm_eps"])
    q = _mm(h, a["wq"], fp8).reshape(s, h_, hd)
    k = _mm(h, a["wk"], fp8).reshape(s, kv, hd)
    v = _mm(h, a["wv"], fp8).reshape(s, kv, hd)
    q, k = _rope(q, z["rope_theta"], rot), _rope(k, z["rope_theta"], rot)
    k = jnp.repeat(k, h_ // kv, axis=1)  # query head h reads kv head h // g
    v = jnp.repeat(v, h_ // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI).reshape(s, -1)
    x = x + _mm(o, a["wo"], fp8)
    m = p["mlp"]
    h = _rms(x, p["norm_mlp"], z["norm_eps"])
    gated = jax.nn.silu(_mm(h, m["w_gate"], fp8)) * _mm(h, m["w_in"], fp8)
    return x + _mm(gated, m["w_out"], fp8)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _final(sizes, w, x):
    return _rms(x, w, dict(sizes)["norm_eps"])


def logits(sizes: dict, weights: dict, tokens, *, fp8: bool = False):
    """(rows, vocab) float32 logits of one sequence, on the device.

    Row i < len(tokens) predicts token i + 1; ``rows`` is len(tokens)
    padded up to a multiple of ``PAD`` (the rows past it are pad).
    ``sizes`` is a configuration's ``as_run`` block; ``weights`` the tree
    described in the module docstring.
    """
    rot = rotary_dim(sizes)
    form = (sizes["norm"], sizes["act"],
            sizes["heads"] % sizes["kv_heads"] == 0,
            0 < rot <= sizes["head_dim"] and rot % 2 == 0)
    if form != ("rmsnorm", "swiglu", True, True):
        raise ValueError(f"this reference computes no decoder of form {form}")
    z = tuple(sorted(sizes.items()))
    toks = np.asarray(tokens, np.int32).reshape(-1)
    n = toks.shape[0]
    padded = np.zeros((-(-n // PAD) * PAD,), np.int32)
    padded[:n] = toks
    x = _embed(z, weights["embed"], jnp.asarray(padded), fp8)
    layers = weights["pos0"]
    for i in range(sizes["layers"]):
        p = jax.tree.map(lambda t, i=i: t[i], layers)
        x = _block(z, p, x, fp8)
    x = _final(z, weights["final_norm"], x)
    vocab = sizes["vocab"]
    parts = [
        _head(x, weights["embed"][lo:min(lo + HEAD_BLOCK, vocab)], fp8)
        for lo in range(0, vocab, HEAD_BLOCK)
    ]
    return jnp.concatenate(parts, axis=-1)
