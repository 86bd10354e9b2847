"""Prefill+decode must reproduce the full-forward logits (KV-cache, MLA
absorbed decode, mamba recurrent state, sliding windows, cross-attention).
MoE archs are tested with a no-drop capacity factor, since capacity dropping
legitimately perturbs train-mode outputs.

De-flaked (ISSUE 5): the per-arch sweep runs in float32, where the only
nondeterminism left (XLA's threaded reduction order under CPU contention)
is ~1e-6 relative — far under the gate — so the comparison is strict and
deterministic; the historical bf16 run, whose tolerance cliff made the p90
gate contention-sensitive, is kept as ONE smoke behind the ``contention``
marker (deselected from tier-1 via pyproject addopts).  The engine-side
decode determinism claim — same kv bucket => same executable — is asserted
structurally from DispatchStats/cache_info in
``test_decode_bucket_identity`` (and differentially in
tests/test_decode_engine.py), not from wall-clock-sensitive numerics.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.models import model as M
from repro.models.params import init_params
from repro.models.partitioning import make_rules
from repro.models.registry import _MODULES, get_smoke_config

ARCHS = list(_MODULES)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def _no_drop(cfg):
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg,
        moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)
        ),
    )


def _decode_inputs(cfg, key, b=2, prefill_len=32, extra=3):
    total = prefill_len + extra
    tokens = jax.random.randint(key, (b, total), 0, cfg.vocab)
    kw = {}
    if cfg.vision_prefix:
        kw["vision_embeds"] = jax.random.normal(
            key, (b, cfg.vision_prefix, cfg.d_model)
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.encoder_decoder:
        kw["encoder_frames"] = jax.random.normal(
            key, (b, cfg.encoder_seq, cfg.d_model)
        ).astype(jnp.dtype(cfg.dtype))
    return tokens, total, kw


def _run_decode_vs_full(cfg, mesh, gate, per_row=False):
    """Decode the last tokens one by one against the train-mode logits,
    calling ``gate(full_logits_at_pos, decode_logits)`` per step.

    ``per_row``: each step is one launch with a (b,) position vector, row
    r two positions behind row r-1 (it rewrites the last prompt rows it
    re-feeds), as the scheduler's mixed-progress step serves rows."""
    rules = make_rules(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    b, prefill_len, extra = 2, 32, 3
    tokens, total, kw = _decode_inputs(cfg, key, b, prefill_len, extra)

    full, _, _ = M.forward(cfg, rules, params, tokens, mode="train", **kw)
    _, cache, _ = M.forward(
        cfg, rules, params, tokens[:, :prefill_len], mode="prefill",
        cache_len=total, **kw,
    )
    rows = jnp.arange(b)
    for i in range(extra):
        if per_row:
            pos = prefill_len + i - 2 * rows
            tok = tokens[rows, pos][:, None]
        else:
            pos = jnp.asarray(prefill_len + i, jnp.int32)
            tok = tokens[:, prefill_len + i: prefill_len + i + 1]
        dec, cache, _ = M.forward(
            cfg, rules, params, tok, mode="decode",
            cache=cache, pos=pos.astype(jnp.int32), cache_len=total,
        )
        want = full[rows, pos] if per_row else full[:, int(pos)]
        gate(want, dec[:, 0], np.asarray(pos).tolist())


# Mixed per-row positions through the model's own decode: the in-place
# path with sliding windows and softcaps (gemma2), and latent attention's
# restack path (deepseek-v2).  The scheduler covers paper-gpt2.
PER_ROW_ARCHS = ("gemma2-9b", "deepseek-v2-236b")
DECODE_CASES = [pytest.param(a, False, id=a) for a in ARCHS] + [
    pytest.param(a, True, id=f"{a}-per_row") for a in PER_ROW_ARCHS
]


@pytest.mark.parametrize("arch,per_row", DECODE_CASES)
def test_decode_matches_full_forward(arch, per_row, mesh):
    """float32 end-to-end: the comparison is deterministic, so the gate is
    strict — a real decode/cache bug moves logits by orders of magnitude
    more than f32 reduction-order noise."""
    cfg = dataclasses.replace(
        _no_drop(get_smoke_config(arch)), dtype="float32"
    )

    def gate(full_pos, dec, pos):
        a = np.asarray(full_pos, np.float32)
        b_ = np.asarray(dec, np.float32)
        err = np.abs(a - b_) / (np.max(np.abs(a)) + 1e-9)
        assert float(np.max(err)) < 2e-3, (arch, pos, float(np.max(err)))

    _run_decode_vs_full(cfg, mesh, gate, per_row)


@pytest.mark.contention
def test_decode_matches_full_forward_bf16_smoke(mesh):
    """The historical bf16 comparison for ONE arch: its p90/severe gate is
    contention-sensitive on shared CPUs (threaded bf16 reductions reorder),
    so it lives behind the ``contention`` marker as an opt-in timing smoke
    (`pytest -m contention`), out of tier-1."""
    cfg = _no_drop(get_smoke_config("paper-gpt2-124m"))

    def gate(full_pos, dec, pos):
        a = np.asarray(full_pos, np.float32)
        b_ = np.asarray(dec, np.float32)
        err = np.abs(a - b_) / (np.max(np.abs(a)) + 1e-9)
        p90 = float(np.percentile(err, 90))
        severe = float(np.mean(err > 0.25))
        assert p90 < 0.03 and severe < 0.02, (pos, p90, severe)

    _run_decode_vs_full(cfg, mesh, gate)


def test_decode_bucket_identity():
    """Deterministic replacement for wall-clock decode gating: every
    decode dispatch at the SAME cache length serves from the SAME compiled
    executable (no per-kv_len growth), asserted from DispatchStats and the
    executable cache — and a different kv bucket adds exactly one."""
    from repro.vortex import Engine

    eng = Engine("host_cpu", empirical_levels=())
    rng = np.random.default_rng(0)

    def args(S, kv_len):
        return (
            jnp.asarray(rng.normal(size=(1, 4, 1, 32)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 2, S, 32)), jnp.float32),
            jnp.asarray(rng.normal(size=(1, 2, S, 32)), jnp.float32),
            kv_len,
        )

    kern = eng.op_kernel("decode_attention", args(8, 8), {})
    S = kern.workload.dynamic_bucket(kern.select(64))  # a bucket length
    for kv_len in range(1, S + 1, max(S // 7, 1)):
        eng.dispatch("decode_attention", *args(S, kv_len))
    d = eng.stats()["decode_attention"]
    assert d["launches"] == d["calls"], "one launch per decode step"
    assert d["padded_calls"] == 0
    assert d["exec_entries"] == 1, (
        "same kv bucket must serve every kv_len from ONE executable"
    )
    # Crossing into another bucket compiles exactly one more program.
    S2 = kern.workload.dynamic_bucket(kern.select(S + 1))
    assert S2 > S
    eng.dispatch("decode_attention", *args(S2, S + 1))
    assert eng.stats()["decode_attention"]["exec_entries"] == 2


def test_windowed_decode_ignores_out_of_window(mesh):
    """A sliding-window layer's decode must not attend past the window."""
    cfg = get_smoke_config("h2o-danube-3-4b")
    rules = make_rules(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    b, s = 1, 40  # window is 16 in the smoke config
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    _, cache, _ = M.forward(
        cfg, rules, params, tokens, mode="prefill", cache_len=64
    )
    # Corrupt cache entries strictly outside the window of position s.
    w = cfg.pattern[0].window
    corrupted = jax.tree.map(lambda x: x, cache)
    for p in corrupted:
        if p.startswith("pos"):
            k = corrupted[p]["k"]
            noise = jnp.asarray(
                np.random.default_rng(0).normal(size=k[..., : s - w, :].shape),
                k.dtype,
            ) * 100
            corrupted[p]["k"] = k.at[..., : s - w, :].set(noise)
    tok = tokens[:, :1]
    out_clean, _, _ = M.forward(
        cfg, rules, params, tok, mode="decode", cache=cache,
        pos=jnp.asarray(s, jnp.int32), cache_len=64,
    )
    out_corr, _, _ = M.forward(
        cfg, rules, params, tok, mode="decode", cache=corrupted,
        pos=jnp.asarray(s, jnp.int32), cache_len=64,
    )
    np.testing.assert_allclose(
        np.asarray(out_clean, np.float32),
        np.asarray(out_corr, np.float32),
        rtol=1e-5, atol=1e-5,
    )
