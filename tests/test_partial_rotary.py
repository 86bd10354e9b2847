"""Partial rotary embedding (``ModelConfig.partial_rotary_factor``).

At factor f the first ``int(head_dim * f)`` dims of each head rotate by the
split-half convention at frequencies ``theta^(-2i/rot)``, and the rest pass
through unchanged.  Every path that applies RoPE is held to a plain float32
split rotation, at 0.75 (phi4-mini) and at 1.0 (every other config): AOT
prefill with and without an engine installed, the in-place decode (stacked
cache, scalar and per-row positions) and the restack decode.  At 1.0 the
tables and the rotation are the ones the program had before the factor
existed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models.layers import (
    _split_heads,
    apply_rope,
    attn_forward,
    rope_tables,
)
from repro.models.partitioning import make_rules
from repro.models.registry import get_smoke_config
from repro.vortex import Engine, use

FACTORS = [0.75, 1.0]
THETA = 10000.0
# float32 on both sides; the tables' cos/sin may differ from numpy's by an
# ulp, and a rotated value sums two products: a few ulps of the inputs.
TOL = dict(rtol=2e-6, atol=2e-6)


def _cfg(factor):
    return dataclasses.replace(
        get_smoke_config("phi4-mini-3.8b"), dtype="float32",
        partial_rotary_factor=factor,
    )


def _plain(x, positions, rot):
    """Split-half rotation of x[..., :rot] (float32, numpy); x (..., s, hd),
    positions broadcast against its seq axis."""
    x = np.asarray(x, np.float32)
    half = rot // 2
    freq = (THETA ** (-2.0 * np.arange(half) / rot)).astype(np.float32)
    ang = np.asarray(positions, np.float32)[..., None] * freq
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return np.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1
    )


def _weights(cfg, seed):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    shapes = {"wq": (d, cfg.n_heads * hd), "wk": (d, cfg.n_kv_heads * hd),
              "wv": (d, cfg.n_kv_heads * hd), "wo": (cfg.n_heads * hd, d)}
    rng = np.random.default_rng(seed)
    return {n: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[0]), jnp.float32)
            for n, s in shapes.items()}


def _rules(cfg):
    return make_rules(make_host_mesh(), n_heads=cfg.n_heads,
                      n_kv_heads=cfg.n_kv_heads)


def test_tables_at_full_head_are_unchanged():
    """At 1.0 the tables are bit-equal to the whole-head formula the
    program used before (frequencies theta^(-i/half))."""
    cfg = _cfg(1.0)
    hd = cfg.resolved_head_dim
    assert cfg.rotary_dim == hd
    pos = jnp.arange(300)
    half = hd // 2
    freq = THETA ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = rope_tables(pos, cfg.rotary_dim, THETA)
    assert np.array_equal(cos, jnp.cos(ang))
    assert np.array_equal(sin, jnp.sin(ang))


def test_full_head_rotation_lowers_as_before():
    """At 1.0 ``apply_rope`` lowers to the program the whole-head rotation
    lowered to before the factor existed, so served programs compile to
    the same HLO."""

    def before(x, cos, sin):
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
        o1 = xf1 * cos - xf2 * sin
        o2 = xf2 * cos + xf1 * sin
        return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)

    x = jax.ShapeDtypeStruct((2, 12, 40, 64), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((1, 1, 40, 32), jnp.float32)
    got = jax.jit(apply_rope).lower(x, t, t).as_text()
    want = jax.jit(before).lower(x, t, t).as_text()
    assert got.replace("apply_rope", "f") == want.replace("before", "f")


@pytest.mark.parametrize("factor,rot", [(0.75, 6), (1.0, 8), (0.5, 4)])
def test_apply_rope_rotates_only_the_leading_dims(factor, rot):
    cfg = _cfg(factor)
    assert cfg.rotary_dim == rot
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 17, 8)),
                    jnp.float32)
    pos = jnp.arange(17)
    cos, sin = rope_tables(pos, cfg.rotary_dim, THETA)
    got = np.asarray(apply_rope(x, cos, sin))
    # The passthrough dims are the input's bits; the rotated ones are
    # exactly the full-width rotation of the leading slice.
    assert np.array_equal(got[..., rot:], np.asarray(x)[..., rot:])
    assert np.array_equal(got[..., :rot],
                          np.asarray(apply_rope(x[..., :rot], cos, sin)))
    np.testing.assert_allclose(got, _plain(x, pos, rot), **TOL)


@pytest.mark.parametrize("factor", [0.625, 0.1], ids=["odd", "none"])
def test_rotary_factor_must_rotate_an_even_width(factor):
    # hd 8: 0.625 rotates 5 dims, 0.1 rotates none.
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        _cfg(factor)


@pytest.mark.parametrize("factor", FACTORS)
def test_prefill_keys_rotated(factor):
    cfg = _cfg(factor)
    p = _weights(cfg, 1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 19, 48)),
                    jnp.float32)
    _, cache = jax.jit(lambda p, x: attn_forward(
        p, x, cfg, cfg.pattern[0], _rules(cfg), mode="prefill",
        positions=jnp.arange(19), cache_len=32,
    ))(p, x)
    raw = np.asarray(_split_heads(x @ p["wk"], cfg.n_kv_heads))
    got = np.asarray(cache["k"])[:, :, :19]
    np.testing.assert_allclose(got, _plain(raw, np.arange(19), cfg.rotary_dim),
                               **TOL)


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("in_place", [True, False], ids=["inplace", "restack"])
@pytest.mark.parametrize("factor", FACTORS)
def test_decode_key_rotated_at_its_position(factor, in_place, per_row):
    cfg = _cfg(factor)
    p = _weights(cfg, 3)
    b, S, g = 3, 32, 1
    pos = np.array([5, 17, 30], np.int32) if per_row else np.int32(11)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((b, 1, 48)),
                    jnp.float32)
    shape = (b, cfg.n_kv_heads, S, cfg.resolved_head_dim)
    if in_place:
        shape = (cfg.n_groups,) + shape
    cache = {"k": jnp.zeros(shape, jnp.float32),
             "v": jnp.zeros(shape, jnp.float32)}
    _, new = jax.jit(lambda p, x, c, pos: attn_forward(
        p, x, cfg, cfg.pattern[0], _rules(cfg), mode="decode", cache=c,
        pos=pos, cache_len=S,
        layer=jnp.int32(g) if in_place else None,
    ))(p, x, cache, jnp.asarray(pos))
    k = np.asarray(new["k"][g] if in_place else new["k"])
    rows = np.broadcast_to(pos, (b,))
    got = np.stack([k[r, :, rows[r]] for r in range(b)])  # (b, KV, hd)
    raw = np.asarray(_split_heads(x @ p["wk"], cfg.n_kv_heads))[:, :, 0]
    want = _plain(raw, rows[:, None], cfg.rotary_dim)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("factor", FACTORS)
def test_engine_served_prefill_keys_rotated(factor):
    """``test_prefill_keys_rotated`` with an engine installed while the
    prefill is traced: attention is the traced engine dispatch that the
    served AOT prefill program runs, and the cache keys are still the
    rotated projections."""
    cfg = _cfg(factor)
    p = _weights(cfg, 5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 24, 48)),
                    jnp.float32)
    eng = Engine("host_cpu", empirical_levels=())
    with use(eng):
        _, cache = jax.jit(lambda p, x: attn_forward(
            p, x, cfg, cfg.pattern[0], _rules(cfg), mode="prefill",
            positions=jnp.arange(24), cache_len=32,
        ))(p, x)
    assert eng.stats()["attention"]["traced_calls"] == 1
    raw = np.asarray(_split_heads(x @ p["wk"], cfg.n_kv_heads))
    got = np.asarray(cache["k"])[:, :, :24]
    np.testing.assert_allclose(got, _plain(raw, np.arange(24), cfg.rotary_dim),
                               **TOL)
