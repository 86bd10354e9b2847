"""Weights and random streams drawn from a run's ``--seed``.

The weights are made on the device in one jitted call, in the dtype they
are served in, in the parameter layout of the program's uniform
attention-plus-dense decoders (see ``bench/reference/dense_decoder.py``):
one stacked block, grouped-query attention, and an MLP that is gated
(``w_gate`` beside ``w_in``) when ``act`` is ``swiglu`` or ``geglu``.
``system.build`` checks the tree's form against the program's own schema;
its values stay the harness's, so the plain reference reads one set of
numbers that neither the program nor its initialiser made.

Leaf i of the flattened tree (keys in sorted order) is drawn from
``fold_in(key, i)``: a decoder without the gated leaf has the tree, and
so the numbers, it had before the gated leaf existed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Stream ``stream`` of ``seed``; any whole number is a seed."""
    return np.random.default_rng(np.random.SeedSequence([abs(seed), stream]))


GATED = ("swiglu", "geglu")


def shapes(sizes: dict) -> dict:
    """{path: (shape, fan_in)} of every leaf; fan_in None marks a norm."""
    L, d, f = sizes["layers"], sizes["d_model"], sizes["d_ff"]
    q = sizes["heads"] * sizes["head_dim"]
    kv = sizes["kv_heads"] * sizes["head_dim"]
    mlp = {"w_in": ((L, d, f), d), "w_out": ((L, f, d), f)}
    if sizes["act"] in GATED:
        mlp["w_gate"] = ((L, d, f), d)
    return {
        "embed": ((sizes["vocab_padded"], d), d),
        "final_norm": ((d,), None),
        "pos0": {
            "norm_mixer": ((L, d), None),
            "norm_mlp": ((L, d), None),
            "attn": {
                "wq": ((L, d, q), d), "wk": ((L, d, kv), d),
                "wv": ((L, d, kv), d), "wo": ((L, q, d), q),
            },
            "mlp": mlp,
        },
    }


def abstract(sizes: dict) -> dict:
    """The form of ``make``'s tree, as ``jax.ShapeDtypeStruct`` leaves."""
    dt = jnp.dtype(sizes["dtype"])
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(leaf[0], dt),
                        shapes(sizes), is_leaf=_is_leaf)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.lru_cache(maxsize=4)
def _maker(frozen_sizes: tuple, dtype: str):
    leaves, treedef = jax.tree.flatten(
        shapes(dict(frozen_sizes)), is_leaf=_is_leaf
    )

    def build(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        out = []
        for i, (shape, fan_in) in enumerate(leaves):
            if fan_in is None:
                out.append(jnp.ones(shape, dtype))
            else:
                w = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                )
                out.append((w / math.sqrt(fan_in)).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)


def make(sizes: dict, seed: int, dtype: str | None = None) -> dict:
    """The weight tree for ``seed``, made on the default device."""
    words = rng(seed, 0).integers(0, 2**31 - 1, size=2, dtype=np.int64)
    build = _maker(
        tuple(sorted(sizes.items())), jnp.dtype(dtype or sizes["dtype"]).name
    )
    return build(jnp.asarray(words, jnp.int32))
