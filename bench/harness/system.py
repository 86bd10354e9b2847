"""The system under test: ``VortexServer(prefill="aot")`` under
``ContinuousScheduler``, built from a configuration and warmed for a mix.

Set-up checks, before anything compiles, that the program's ModelConfig is
a decoder this harness serves (``serves``), that it agrees with every key
of the configuration's ``as_run`` block, and that the weight tree has the
form of the program's own parameter schema.
"""
from __future__ import annotations

from unittest import mock

import jax
import numpy as np

from bench.harness import traffic
from bench.harness import weights as harness_weights


def program_sizes(cfg) -> dict:
    """The ``as_run`` block of a program ModelConfig (smoke rehearsals).

    Its keys are the ones the harness maps to program attributes of
    another name or form (the program hard-codes its norm eps); any other
    ``as_run`` key names a ModelConfig attribute itself.
    """
    return {
        "layers": cfg.n_layers, "d_model": cfg.d_model,
        "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
        "vocab": cfg.vocab, "vocab_padded": cfg.vocab_padded,
        "norm": cfg.norm, "norm_eps": 1e-6,
        "act": "gelu_tanh" if cfg.act == "gelu" else cfg.act,
        "rope_theta": cfg.rope_theta,
        "dtype": cfg.dtype,
    }


def serves(cfg) -> bool:
    """Whether ``cfg`` is a decoder this harness makes, feeds and counts:
    RoPE, every layer full causal self-attention with a dense MLP, no
    image prefix and no encoder."""
    return (cfg.use_rope and not cfg.vision_prefix
            and not cfg.encoder_decoder
            and all(s.mixer == "attn" and s.mlp == "dense" and not s.window
                    and not s.cross_attn for s in cfg.pattern))


def program_config(arch: str, sizes: dict | None, *, smoke: bool = False):
    """The program's ModelConfig for ``arch``, checked against ``sizes``."""
    from repro.models.registry import get_config, get_smoke_config

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if not serves(cfg):
        raise SystemExit(
            f"program config {arch!r} is not a uniform attention-plus-dense "
            "decoder with RoPE, no window, no image prefix and no encoder"
        )
    if sizes is None:
        return cfg
    have = program_sizes(cfg)
    missing = sorted(set(have) - set(sizes))
    unknown, bad = [], {}
    for k, v in sizes.items():
        if k in have:
            mine = have[k]
        elif hasattr(cfg, k) and not callable(getattr(cfg, k)):
            mine = getattr(cfg, k)
        else:
            unknown.append(k)
            continue
        if v != mine:
            bad[k] = (v, mine)
    if missing or unknown or bad:
        raise SystemExit(
            f"program config {arch!r} is not the configuration as run: "
            f"as_run keys the program config does not have {unknown}; "
            f"as_run lacks {missing}; (file, program) = {bad}"
        )
    check_tree(cfg, harness_weights.abstract(sizes))
    return cfg


def _form(tree) -> dict:
    """{path: (shape, dtype)} of every leaf of a tree of dicts."""
    return {
        "/".join(str(k.key) for k in path): (tuple(x.shape), str(x.dtype))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def check_tree(cfg, tree) -> None:
    """Exit unless ``tree`` has the form of the program's parameters for
    ``cfg``: the same paths, shapes and dtypes.  Values are not compared."""
    from repro.models.params import abstract_params

    want, got = _form(abstract_params(cfg)), _form(tree)
    missing = sorted(set(want) - set(got))
    extra = sorted(set(got) - set(want))
    differ = {p: (got[p], want[p]) for p in sorted(set(got) & set(want))
              if got[p] != want[p]}
    if missing or extra or differ:
        raise SystemExit(
            f"the weight tree is not the program's layout for {cfg.name!r}: "
            f"missing {missing}; extra {extra}; (harness, program) = {differ}"
        )


def build(cfg, weights: dict, mix: dict):
    """Server and scheduler over ``weights`` (the server's own initialiser
    is bypassed, so no second copy of the weights is ever made)."""
    check_tree(cfg, weights)
    import repro.launch.serve as serve
    from repro.launch.mesh import make_host_mesh
    from repro.launch.scheduler import ContinuousScheduler

    srv = mix["server"]
    with mock.patch.object(serve, "init_params", lambda c, k: weights):
        server = serve.VortexServer(
            cfg, make_host_mesh(), max_cache=srv["max_cache"], prefill="aot"
        )
    sched = ContinuousScheduler(server, batch_rows=srv["batch_rows"])
    return server, sched


def prefill_buckets(server, mix: dict) -> dict[int, int]:
    """{bucket: longest prompt length the mix can draw in it}."""
    lo, hi = traffic.lengths(mix["prompt_tokens"])
    out: dict[int, int] = {}
    for s in range(lo, hi + 1):
        out[server.prefill_seq_bucket(s)] = s
    return out


def warm(server, sched, mix: dict, vocab: int) -> dict:
    """Touch every program and shape the mix's window will use, through
    the scheduler's own entry points, and leave it in steady state.

    The shared cache only grows, so a running server holds it at the
    bucket its longest request grew it to.  The mix's longest request
    (longest prompt, longest output) goes first and leaves the cache
    there; then one request per prefill bucket is admitted into it,
    compiling each prefill program, the copy of its cache row into the
    shared cache, and the decode program at the steady bucket.
    """
    rng = np.random.default_rng(0)  # token ids do not change shapes

    def req(s: int, max_new: int):
        from repro.launch.serve import Request

        toks = rng.integers(0, vocab, size=(1, s), dtype=np.int32)
        return Request(tokens=toks, max_new=max_new)

    buckets = prefill_buckets(server, mix)
    _, p_hi = traffic.lengths(mix["prompt_tokens"])
    _, o_hi = traffic.lengths(mix["output_tokens"])
    sched.submit(req(p_hi, o_hi))
    _raise_errors(sched.drain())
    lengths = sorted(buckets.values())
    for i in range(0, len(lengths), sched.batch_rows):
        for s in lengths[i:i + sched.batch_rows]:
            sched.submit(req(s, 2))
        _raise_errors(sched.drain())
    return {"prefill_buckets": sorted(buckets), "kv_bucket": sched.kvb}


def _raise_errors(results: dict) -> None:
    for rid, got in results.items():
        if isinstance(got, Exception):
            raise RuntimeError(f"warm-up request {rid} failed: {got}")
