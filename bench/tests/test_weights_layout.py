"""The harness's weights, set-up checks and flop counts for every decoder it
serves: the tree has the program's layout (gated MLP and grouped-query
attention included), set-up refuses a tree or an ``as_run`` block that is
not the program's, and the matmul count includes a gated MLP's third
matrix.  Shapes only at published sizes: nothing is allocated there.
"""
from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

from bench.harness import context, loop, spec, system, traffic, weights
from repro.models.params import abstract_params
from repro.models.registry import ARCH_IDS, get_config, get_smoke_config

ALL_ARCHS = ("paper-gpt2-124m",) + ARCH_IDS
SERVED = [a for a in ALL_ARCHS if system.serves(get_config(a))]


def _config(arch: str, smoke: bool):
    return get_smoke_config(arch) if smoke else get_config(arch)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_served_family(arch):
    want = arch in ("paper-gpt2-124m", "phi4-mini-3.8b", "starcoder2-15b")
    assert system.serves(get_config(arch)) is want
    assert system.serves(get_smoke_config(arch)) is want


@pytest.mark.parametrize("smoke", [True, False], ids=["smoke", "published"])
@pytest.mark.parametrize("arch", SERVED)
def test_tree_is_program_layout(arch, smoke):
    cfg = _config(arch, smoke)
    sizes = system.program_sizes(cfg)
    made = jax.eval_shape(lambda: weights.make(sizes, 2**33 + 1))
    want = system._form(abstract_params(cfg))
    assert system._form(made) == want
    assert system._form(weights.abstract(sizes)) == want
    assert {dt for _, dt in want.values()} == {"bfloat16"}
    # The whole set-up check passes: every as_run key and the tree's form.
    assert system.program_config(arch, sizes, smoke=smoke) == cfg
    system.check_tree(cfg, made)


# sha256 over (path, bfloat16 bits) of every leaf of the paper-gpt2-124m
# smoke tree, as the harness made it before the gated leaf was added.
GPT2_SMOKE_DIGESTS = {
    7: "ff4bbc2273d767d225d61d2425ae82907de46122913274c14d434533dd84bbe4",
    2**33 + 5:
        "0bb80a2aa9d8305c10a206e5ae031a822886b7a0e409e4339dc1358d107dd67f",
}


@pytest.mark.parametrize("seed", sorted(GPT2_SMOKE_DIGESTS))
def test_gpt2_weights_unchanged(seed):
    cfg = get_smoke_config("paper-gpt2-124m")
    w = weights.make(system.program_sizes(cfg), seed)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == GPT2_SMOKE_DIGESTS[seed]


def _drop_gate(tree):
    tree["pos0"]["mlp"].pop("w_gate")


def _add_gate(tree):
    tree["pos0"]["mlp"]["w_gate"] = tree["pos0"]["mlp"]["w_in"]


def _transpose_wk(tree):
    wk = tree["pos0"]["attn"]["wk"]
    shape = (wk.shape[0], wk.shape[2], wk.shape[1])
    tree["pos0"]["attn"]["wk"] = jax.ShapeDtypeStruct(shape, wk.dtype)


def _float32_embed(tree):
    tree["embed"] = jax.ShapeDtypeStruct(tree["embed"].shape, np.float32)


@pytest.mark.parametrize("arch,edit,named", [
    ("phi4-mini-3.8b", _drop_gate, "missing ['pos0/mlp/w_gate']"),
    ("paper-gpt2-124m", _add_gate, "extra ['pos0/mlp/w_gate']"),
    ("phi4-mini-3.8b", _transpose_wk, "'pos0/attn/wk'"),
    ("starcoder2-15b", _float32_embed, "'embed'"),
], ids=["missing", "extra", "shape", "dtype"])
def test_build_refuses_another_tree(arch, edit, named, monkeypatch):
    import repro.launch.serve as serve

    def no_server(*a, **k):
        raise AssertionError("the server was built from a refused tree")

    monkeypatch.setattr(serve, "VortexServer", no_server)
    cfg = get_smoke_config(arch)
    tree = weights.abstract(system.program_sizes(cfg))
    edit(tree)
    with pytest.raises(SystemExit) as e:
        system.build(cfg, tree, MIX)
    assert named in str(e.value)


@pytest.mark.parametrize("change,named", [
    ({"rope_fraction": 0.75}, "does not have ['rope_fraction']"),
    ({"kv_heads": None}, "lacks ['kv_heads']"),
    ({"kv_heads": 4}, "'kv_heads': (4, 12)"),
    ({"tie_embeddings": False}, "'tie_embeddings': (False, True)"),
    ({"norm_eps": 1e-5}, "'norm_eps': (1e-05, 1e-06)"),
], ids=["unknown", "missing", "mapped", "attribute", "norm_eps"])
def test_program_config_refuses_another_as_run(change, named):
    sizes = dict(spec.load("gpt2-chat").sizes)
    for k, v in change.items():
        if v is None:
            sizes.pop(k)
        else:
            sizes[k] = v
    with pytest.raises(SystemExit) as e:
        system.program_config("paper-gpt2-124m", sizes)
    assert named in str(e.value)


@pytest.mark.parametrize("arch", ["internvl2-26b", "whisper-small",
                                  "gemma2-9b", "falcon-mamba-7b"])
def test_program_config_refuses_another_family(arch):
    with pytest.raises(SystemExit, match="uniform attention-plus-dense"):
        system.program_config(arch, None, smoke=True)


@pytest.mark.parametrize("arch,want", [
    # 12 x (4 x 768 x 768 + 2 x 768 x 3072)
    ("paper-gpt2-124m", 84_934_656),
    # 32 x (2 x 3072 x 3072 + 2 x 3072 x 1024 + 3 x 3072 x 8192)
    ("phi4-mini-3.8b", 3_221_225_472),
    # 40 x (2 x 6144 x 6144 + 2 x 6144 x 512 + 2 x 6144 x 24576)
    ("starcoder2-15b", 15_351_152_640),
])
def test_layer_matmul_params(arch, want):
    sizes = system.program_sizes(get_config(arch))
    ctx = context.Context(sizes=sizes, peaks=None, window=None,
                          positions=[], prefill_bucket={})
    assert ctx.layer_matmul_params() == want


# A small closed-loop mix, shaped like a mix file's ``rehearsal`` block.
MIX = {
    "arrivals": {"kind": "closed", "clients": 4},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 48},
    "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
    "server": {"batch_rows": 4, "max_cache": 64},
    "lead_s": 0.3,
    "cap_s": 60,
}


def test_gated_decoder_runs_through_the_harness():
    """phi4-mini's smoke preset (SwiGLU, grouped-query attention) through
    build, warm-up and a 2 s window on the CPU: every request finishes and
    every cache lease is settled.  No reference judges the tokens here."""
    seed = 2**31 + 9
    cfg = system.program_config("phi4-mini-3.8b", None, smoke=True)
    sizes = system.program_sizes(cfg)
    server, sched = system.build(cfg, weights.make(sizes, seed), MIX)
    system.warm(server, sched, MIX, sizes["vocab"])
    gen = traffic.Generator(MIX, sizes["vocab"], MIX["server"]["max_cache"],
                            weights.rng(seed, 1))
    win = loop.run(sched, gen, 2.0, lead_s=MIX["lead_s"], cap_s=MIX["cap_s"])
    loop.finish(sched, win)
    counted = [r for r in win.recs if r.counted]
    assert counted
    assert [r.failed for r in counted if r.failed] == []
    assert all(r.done for r in counted)
    assert server.kv_pool.leases_active == 0
