"""The phi4-batch cell's own pieces at smoke size on the CPU: the gated,
grouped-query reference against a naive loop, the cell's rehearsal and its
float8 control, and its per-layer readers on a window whose every number
is known.

A ``--cpu-rehearsal`` takes its sizes from the program's smoke preset
through ``system.program_sizes``, which does not state
``partial_rotary_factor``; the reference would then rotate whole heads.
The rehearsals here add the preset's factor to those sizes, as the cell's
``as_run`` block states it at published widths.
"""
from __future__ import annotations

import json
import math
import types

import numpy as np
import pytest

from bench import run
from bench.harness import spec, system, weights
from bench.harness.context import Context
from bench.harness.loop import Rec, Window
from bench.reference import gqa_decoder

SMOKE = {
    "layers": 2, "d_model": 48, "heads": 6, "kv_heads": 2, "head_dim": 8,
    "d_ff": 32, "vocab": 300, "vocab_padded": 512, "norm": "rmsnorm",
    "norm_eps": 1e-6, "act": "swiglu", "rope_theta": 10000.0,
    "partial_rotary_factor": 0.75, "dtype": "float32",
}


def _naive(sizes, w, toks):
    """float64, one token and one head at a time, each pair rotated by
    its own angle."""
    z = sizes
    f64 = {k: np.asarray(v, np.float64) for k, v in
           [("embed", w["embed"]), ("final_norm", w["final_norm"])]}
    lay = {k: np.asarray(v, np.float64) for k, v in
           [("nm", w["pos0"]["norm_mixer"]), ("nl", w["pos0"]["norm_mlp"]),
            ("wq", w["pos0"]["attn"]["wq"]), ("wk", w["pos0"]["attn"]["wk"]),
            ("wv", w["pos0"]["attn"]["wv"]), ("wo", w["pos0"]["attn"]["wo"]),
            ("wg", w["pos0"]["mlp"]["w_gate"]), ("wi", w["pos0"]["mlp"]["w_in"]),
            ("wu", w["pos0"]["mlp"]["w_out"])]}
    hd, H, KV = z["head_dim"], z["heads"], z["kv_heads"]
    rot = int(hd * z.get("partial_rotary_factor", 1.0))
    n = len(toks)

    def rms(x, g):
        return x / math.sqrt(np.mean(x * x) + z["norm_eps"]) * g

    def rope(v, t):
        out = v.copy()
        for i in range(rot // 2):
            a = t * z["rope_theta"] ** (-2.0 * i / rot)
            x1, x2 = v[i], v[i + rot // 2]
            out[i] = x1 * math.cos(a) - x2 * math.sin(a)
            out[i + rot // 2] = x2 * math.cos(a) + x1 * math.sin(a)
        return out

    xs = [f64["embed"][t].copy() for t in toks]
    for li in range(z["layers"]):
        hs = [rms(x, lay["nm"][li]) for x in xs]
        outs = [np.zeros(H * hd) for _ in range(n)]
        for h in range(H):
            g = h // (H // KV)
            q = [rope(x @ lay["wq"][li][:, h * hd:(h + 1) * hd], t)
                 for t, x in enumerate(hs)]
            k = [rope(x @ lay["wk"][li][:, g * hd:(g + 1) * hd], t)
                 for t, x in enumerate(hs)]
            v = [x @ lay["wv"][li][:, g * hd:(g + 1) * hd] for x in hs]
            for t in range(n):
                s = np.array([q[t] @ k[u] for u in range(t + 1)]) / math.sqrt(hd)
                p = np.exp(s - s.max())
                p /= p.sum()
                outs[t][h * hd:(h + 1) * hd] = sum(p[u] * v[u]
                                                   for u in range(t + 1))
        xs = [x + o @ lay["wo"][li] for x, o in zip(xs, outs)]
        for t in range(n):
            h = rms(xs[t], lay["nl"][li])
            a = h @ lay["wg"][li]
            xs[t] = xs[t] + (a / (1 + np.exp(-a)) * (h @ lay["wi"][li])) @ \
                lay["wu"][li]
    head = f64["embed"][:z["vocab"]]
    return np.stack([rms(x, f64["final_norm"]) @ head.T for x in xs])


@pytest.mark.parametrize("factor", [0.75, 1.0])
def test_reference_matches_naive_loop(factor):
    sizes = {**SMOKE, "partial_rotary_factor": factor}
    w = weights.make(sizes, 2**33 + 3, dtype="float32")
    toks = np.random.default_rng(1).integers(0, sizes["vocab"], 23)
    import jax

    with jax.default_matmul_precision("highest"):
        got = np.asarray(gqa_decoder.logits(sizes, w, toks))
    assert got.shape == (gqa_decoder.PAD, sizes["vocab"])
    want = _naive(sizes, w, toks)
    # float32 against float64 over two layers: ~1e-6 of the logit scale.
    np.testing.assert_allclose(got[:len(toks)], want,
                               atol=1e-5 * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("change", [{"norm": "layernorm"},
                                    {"act": "gelu_tanh"},
                                    {"kv_heads": 4},
                                    {"partial_rotary_factor": 0.625}],
                         ids=["layernorm", "gelu", "kv_heads", "odd_rotary"])
def test_reference_refuses_another_form(change):
    sizes = {**SMOKE, **change}
    with pytest.raises(ValueError, match="no decoder of form"):
        gqa_decoder.logits(sizes, {}, [1, 2, 3])


def test_cell_states_the_rotary_factor():
    cell = spec.load("phi4-batch")
    assert cell.config["reference"] == "gqa_decoder"
    assert cell.sizes["partial_rotary_factor"] == 0.75
    cfg = system.program_config(cell.config["program_arch"], cell.sizes)
    assert cfg.rotary_dim == 96


@pytest.fixture
def rehearse(monkeypatch, capsys, tmp_path):
    real = system.program_sizes
    monkeypatch.setattr(system, "program_sizes", lambda cfg: {
        **real(cfg), "partial_rotary_factor": cfg.partial_rotary_factor})
    # A trace directory of its own: the checkout's is shared with every
    # other process rehearsing a traced cell at the same time.
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")

    def go(*args):
        rc = run.main(["--workload", "phi4-batch", "--seconds", "2",
                       "--cpu-rehearsal", *args])
        assert rc == 0
        lines = capsys.readouterr().out.strip().splitlines()
        return json.loads(lines[-2]), json.loads(lines[-1])

    return go


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_serves_every_request(rehearse, trace):
    diag, out = rehearse("--seed", str(2**33 + 5), "--trace", str(trace))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(k.startswith("cpu.") for k in out["metrics"])
    if trace:
        assert out["metrics"]["cpu.kv_pool.parked_mb"]["value"] == 0.0
    assert diag["info"]["kv_pool"]["retired"] > 0


@pytest.mark.parametrize("seed", [21, 2**31 + 3, 977])
def test_control_is_not_correct(rehearse, seed):
    diag, out = rehearse("--seed", str(seed), "--trace", "0", "--control")
    limit = spec.load("phi4-batch").limits["logit_gap"]["limit"]
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > limit
    assert diag["check"]["control"] is True
    assert diag["check"]["program_logit_gap"] <= limit


# -- the readers ----------------------------------------------------------

PHI4 = system.program_sizes(
    system.program_config("phi4-mini-3.8b", None))
V5E = spec.peaks("TPU v5 lite")


def _launch(kvb, pos, parked=None):
    rec = {"kvb": kvb, "pos": np.asarray(pos), "slots": np.arange(len(pos))}
    if parked is not None:
        rec["kv_parked_bytes"] = parked
    return rec


def _ctx(steps, recs=(), trace=None, span=(2.0, 5.0)):
    positions, rows = [], []
    for start, end, launch in steps:
        i = len(positions)
        positions.append(launch)
        rows.append((start, end, i, i + 1))
    win = Window(t0=0.0, t1=10.0, end=11.0, recs=list(recs), steps=rows,
                 lateness=[], trace=span if trace is not None else None)
    return Context(sizes=PHI4, peaks=V5E, window=win, positions=positions,
                   prefill_bucket={}, trace=trace)


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_parked_mb_is_the_window_max():
    steps = [(-1.0, -0.5, _launch(2048, [5], 9e9)),   # before the window
             (1.0, 1.1, _launch(2048, [5], 0)),
             (2.0, 2.1, _launch(2048, [5, 6], 268_435_456)),
             (3.0, 3.1, _launch(2048, [5], 1e6)),
             (10.5, 11.0, _launch(2048, [5], 9e9))]   # after it
    assert _read("kv_pool.parked_mb", _ctx(steps)) == pytest.approx(268.435456)


def test_parked_mb_none_without_the_counter():
    assert _read("kv_pool.parked_mb", _ctx([])) is None
    assert _read("kv_pool.parked_mb",
                 _ctx([(1.0, 1.1, _launch(2048, [5]))])) is None


def _trace(prefill_s, decode_s, kernel_s):
    return types.SimpleNamespace(
        program_s={"prefill": prefill_s, "decode": decode_s},
        kernel_time=lambda program, op: kernel_s[program],
    )


def test_phi4_readers_are_the_cells_readings():
    recs = [Rec(rid=0, due=0.0, prompt=np.zeros(1024, np.int32), max_new=8,
                counted=True, stamps=[3.0, 3.1])]
    steps = [(2.5, 2.6, _launch(2048, [1023, 99])),
             (3.0, 3.1, _launch(2048, [1024, 100]))]
    ctx = _ctx(steps, recs, trace=_trace(0.2, 0.07, {"prefill": 0.01,
                                                     "decode": 0.004}))
    for name in ("mfu.prefill", "mfu.decode", "decode_attention_roofline"):
        assert _read("phi4." + name, ctx) == _read(name, ctx) is not None
    # By hand: 2 launches of 2 rows; weights (3,221,225,472 per layer
    # stack) and the head (3072 x 200064) per row, attention at each row's
    # kv_len over 24 heads of 128, 32 layers.
    per_row = 2 * (3_221_225_472 + 3072 * 200064)
    kv = 1024 + 100 + 1025 + 101
    flops = 4 * per_row + 32 * 2 * 2 * kv * 128 * 24
    assert _read("phi4.mfu.decode", ctx) == pytest.approx(
        100 * flops / (0.07 * 197e12))
    # K/V bytes by 8 kv heads; q and out by 24 query heads (bf16).
    need = sum(max(2 * 2 * k * 128 * 24 / 197e12,
                   2 * (2 * 8 * 128 * k + 2 * 24 * 128 * 2) / 819e9)
               for k in (1024 + 100, 1025 + 101))
    assert _read("phi4.decode_attention_roofline", ctx) == pytest.approx(
        100 * 32 * need / 0.004)


def test_phi4_readers_none_without_a_trace():
    ctx = _ctx([(3.0, 3.1, _launch(2048, [5]))])
    for name in ("phi4.mfu.prefill", "phi4.mfu.decode",
                 "phi4.decode_attention_roofline"):
        assert _read(name, ctx) is None
