"""Plain float32 reference of the GPT-2-style decoder as the program runs it.

Pre-norm decoder blocks: LayerNorm, causal multi-head self-attention with
split-half rotary embeddings over the whole head, a tanh-GELU MLP, and a
tied LM head over the first ``vocab`` embedding rows.  No biases, no
dropout.

It imports nothing of the program under test.  It reads the weights in the
layout that ``bench/harness/weights.py`` makes them in:

    embed (vocab_padded, d)          final_norm (d,)
    pos0/norm_mixer (L, d)           pos0/norm_mlp (L, d)
    pos0/attn/{wq (L, d, H*hd), wk, wv (L, d, KV*hd), wo (L, H*hd, d)}
    pos0/mlp/{w_in (L, d, f), w_out (L, f, d)}
    pos0/mlp/w_gate (L, d, f)        gated MLPs (swiglu, geglu) only

This module computes that layout with LayerNorm, KV = H and no gate, and
refuses any other form.  It computes one sequence at a time, layer by
layer, in float32 at ``precision=HIGHEST`` (a TPU otherwise multiplies
float32 in bfloat16).  Sequences are padded to a multiple of ``PAD`` rows
at the end: causal attention keeps the real rows exact, and few distinct
shapes compile.

``fp8=True`` is the control: every weight GEMM takes its operands through
float8 e4m3 (per-output-channel weight scales, per-row activation scales),
the precision one step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512
HEAD_BLOCK = 16384  # vocab rows of the LM head per call
_HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Split-half rotation of (s, heads, hd) over the whole head."""
    s, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq  # (s, half)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _gelu_tanh(h: jax.Array) -> jax.Array:
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * h * (1.0 + jnp.tanh(c * (h + 0.044715 * h ** 3)))


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _embed(sizes, embed, tokens, fp8):
    del sizes, fp8
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _block(sizes, p, x, fp8):
    z = dict(sizes)
    s = x.shape[0]
    h_, hd = z["heads"], z["head_dim"]
    a = p["attn"]
    h = _norm(x, p["norm_mixer"], z["norm_eps"])
    q = _mm(h, a["wq"], fp8).reshape(s, h_, hd)
    k = _mm(h, a["wk"], fp8).reshape(s, h_, hd)
    v = _mm(h, a["wv"], fp8).reshape(s, h_, hd)
    q, k = _rope(q, z["rope_theta"]), _rope(k, z["rope_theta"])
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI).reshape(s, -1)
    x = x + _mm(o, a["wo"], fp8)
    m = p["mlp"]
    h = _norm(x, p["norm_mlp"], z["norm_eps"])
    x = x + _mm(_gelu_tanh(_mm(h, m["w_in"], fp8)), m["w_out"], fp8)
    return x


@functools.partial(jax.jit, static_argnames=("sizes", "fp8"))
def _final(sizes, w, x, fp8):
    z = dict(sizes)
    del fp8
    return _norm(x, w, z["norm_eps"])


@functools.partial(jax.jit, static_argnames=("fp8",))
def _head(x, rows, fp8):
    return _mm(x, rows.T, fp8)


def logits(sizes: dict, weights: dict, tokens, *, fp8: bool = False):
    """(rows, vocab) float32 logits of one sequence, on the device.

    Row i < len(tokens) predicts token i + 1; ``rows`` is len(tokens)
    padded up to a multiple of ``PAD`` (the rows past it are pad).
    ``sizes`` is a configuration's ``as_run`` block; ``weights`` the tree
    described in the module docstring.
    """
    form = (sizes["norm"], sizes["act"], sizes["kv_heads"] == sizes["heads"])
    if form != ("layernorm", "gelu_tanh", True):
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
    x = _final(z, weights["final_norm"], x, fp8)
    vocab = sizes["vocab"]
    parts = [
        _head(x, weights["embed"][lo:min(lo + HEAD_BLOCK, vocab)], fp8)
        for lo in range(0, vocab, HEAD_BLOCK)
    ]
    return jnp.concatenate(parts, axis=-1)
