"""The plain reference against the program's own forward, float32, smoke size.

Both run the same float32 weights; the program prefills a prompt and then
decodes teacher-forced through its cache, the reference runs the whole
sequence once.  They differ only in the order of float32 roundings.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import system, weights
from bench.reference import dense_decoder
from repro.launch.mesh import make_host_mesh
from repro.models.model import forward, make_cache
from repro.models.partitioning import make_rules
from repro.models.registry import get_smoke_config


# float32 on both sides, different summation order: 1e-4 of the logit
# scale is ~100x float32 epsilon after two layers.
REL_TOL = 1e-4


def test_reference_matches_program_forward():
    cfg = dataclasses.replace(get_smoke_config("paper-gpt2-124m"),
                              dtype="float32")
    sizes = system.program_sizes(cfg)
    w = weights.make(sizes, seed=2**33 + 7, dtype="float32")
    rules = make_rules(make_host_mesh(), n_heads=cfg.n_heads,
                       n_kv_heads=cfg.n_kv_heads)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (1, 40))
    n_prompt, cache_len = 29, 64
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(dense_decoder.logits(sizes, w, toks[0]))
        assert ref.shape[0] % dense_decoder.PAD == 0
        ref = ref[:toks.shape[1]]
        pre, cache, _ = forward(
            cfg, rules, w, jnp.asarray(toks[:, :n_prompt]), mode="prefill",
            cache_len=cache_len,
        )
        got = [np.asarray(pre[0])]
        full = make_cache(cfg, 1, cache_len)
        cache = jax.tree.map(
            lambda c, f: jax.lax.dynamic_update_slice(f, c, (0,) * f.ndim),
            cache, full,
        )
        for pos in range(n_prompt, toks.shape[1]):
            step, cache, _ = forward(
                cfg, rules, w, jnp.asarray(toks[:, pos:pos + 1]),
                mode="decode", cache=cache, pos=jnp.asarray(pos, jnp.int32),
                cache_len=cache_len,
            )
            got.append(np.asarray(step[0]))
    got = np.concatenate(got)[:, :cfg.vocab]
    scale = np.abs(ref).max()
    np.testing.assert_array_less(
        np.abs(got - ref).max(), REL_TOL * scale
    )
    assert ref.shape == (toks.shape[1], cfg.vocab)
