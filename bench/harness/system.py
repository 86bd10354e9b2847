"""The system under test: ``VortexServer(prefill="aot")`` under
``ContinuousScheduler``, built from a configuration and warmed for a mix.
"""
from __future__ import annotations

from unittest import mock

import numpy as np

from bench.harness import traffic

# Structural sizes the program's ModelConfig must agree on with the
# configuration's ``as_run`` block (the program hard-codes its norm eps).
_CHECKED = ("layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "vocab_padded", "norm", "act", "rope_theta", "dtype")


def program_sizes(cfg) -> dict:
    """The ``as_run`` block of a program ModelConfig (smoke rehearsals)."""
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


def program_config(arch: str, sizes: dict | None, *, smoke: bool = False):
    """The program's ModelConfig for ``arch``, checked against ``sizes``."""
    from repro.models.registry import get_config, get_smoke_config

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if sizes is not None:
        have = program_sizes(cfg)
        bad = {k: (sizes[k], have[k]) for k in _CHECKED if sizes[k] != have[k]}
        if bad or not cfg.use_rope or any(
            s.mixer != "attn" or s.mlp != "dense" or s.window or s.cross_attn
            for s in cfg.pattern
        ):
            raise SystemExit(
                f"program config {arch!r} is not the configuration as run: "
                f"(file, program) = {bad}"
            )
    return cfg


def build(cfg, weights: dict, mix: dict):
    """Server and scheduler over ``weights`` (the server's own initialiser
    is bypassed, so no second copy of the weights is ever made)."""
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
