"""phi4-mini's smoke preset served through ``VortexServer(prefill="aot")``:
the logits of prefill and of every decode step through the cache, against
the plain reference ``bench/reference/gqa_decoder.py`` on the same seeded
weights.  The smoke preset keeps phi4's form: RMSNorm, SwiGLU, 6 query
heads over 2 key/value heads (group 3) and partial rotary (6 of 8 dims).

Both the scheduler's path (``ContinuousScheduler``: b=1 prefill, the slot
copy, mixed-progress decode at per-row positions) and ``generate()``'s
(``score``: the same prefill programs, decode at one position) are read.
"""
import dataclasses

import jax
import numpy as np
import pytest

from bench.harness import system, weights
from bench.reference import gqa_decoder
from repro.launch.serve import Request
from repro.models.registry import get_smoke_config

# float32 weights and activations on both sides; they differ in the order
# of float32 sums (and the program's fused attention), about 1e-6 of the
# logit scale per op.  1e-4 of the scale leaves that ~100x room after two
# layers, while a wrong rotation, group mapping or gate moves logits by
# O(scale).
REL_TOL = 1e-4
MIX = {"server": {"batch_rows": 4, "max_cache": 128}}
PROMPTS = [(5, 9), (37, 12), (70, 6), (18, 20), (101, 8)]  # (prompt, max_new)


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_smoke_config("phi4-mini-3.8b"),
                              dtype="float32")
    sizes = {**system.program_sizes(cfg),
             "partial_rotary_factor": cfg.partial_rotary_factor}
    w = weights.make(sizes, 2**33 + 21, dtype="float32")
    server, sched = system.build(cfg, w, MIX)
    return cfg, sizes, w, server, sched


def _reference(sizes, w, seq):
    with jax.default_matmul_precision("highest"):
        return np.asarray(gqa_decoder.logits(sizes, w, seq))[:len(seq)]


def _close(got, want, vocab):
    got = np.asarray(got, np.float32)[..., :vocab]
    scale = np.abs(want).max()
    np.testing.assert_array_less(np.abs(got - want).max(), REL_TOL * scale)


def test_scheduler_logits_match_reference(served):
    cfg, sizes, w, server, sched = served
    launches = []
    prefills = {}
    real_decode = server._decode_exec_vec_for
    real_prefill = server._prefill_exec_for

    def decode_for(bp, kvb):
        exe = real_decode(bp, kvb)

        def call(params, cache, tok, pos):
            logits, cache = exe(params, cache, tok, pos)
            seated = {i: (r.rid, r.pos_next) for i, r in enumerate(sched.rows)
                      if r is not None}
            launches.append((seated, np.asarray(logits)))
            return logits, cache

        return call

    def prefill_for(bp, sp, batch):
        exe = real_prefill(bp, sp, batch)

        def call(params, batch):
            logits, cache = exe(params, batch)
            prefills[len(prefills)] = np.asarray(logits)[0]
            return logits, cache

        return call

    server._decode_exec_vec_for = decode_for
    server._prefill_exec_for = prefill_for
    rng = np.random.default_rng(7)
    try:
        reqs = [Request(tokens=rng.integers(0, cfg.vocab, (1, s),
                                            dtype=np.int32), max_new=n)
                for s, n in PROMPTS]
        rids = [sched.submit(r) for r in reqs]
        out = sched.drain()
    finally:
        del server._decode_exec_vec_for, server._prefill_exec_for
    mixed = [s for s, _ in launches
             if len({p for _, p in s.values()}) >= 2]
    assert mixed, "no launch served rows at different positions"
    for n, (rid, req) in enumerate(zip(rids, reqs)):
        toks = out[rid][0]
        s = req.tokens.shape[1]
        seq = np.concatenate([req.tokens[0], toks[:-1]])
        ref = _reference(sizes, w, seq)
        # Admissions run in submission order, one prefill each.
        _close(prefills[n], ref[s - 1], cfg.vocab)
        rows = [(p, lg[slot]) for seated, lg in launches
                for slot, (r, p) in seated.items() if r == rid]
        assert len(rows) == req.max_new - 1
        for p, lg in rows:
            _close(lg, ref[p], cfg.vocab)


def test_generate_logits_match_reference(served):
    cfg, sizes, w, server, _ = served
    rng = np.random.default_rng(8)
    toks = rng.integers(0, cfg.vocab, (1, 60), dtype=np.int32)
    n_prompt = 41
    got = server.score(toks, n_prompt)[0]  # rows predict n_prompt - 1 + j
    ref = _reference(sizes, w, toks[0])
    _close(got, ref[n_prompt - 1:], cfg.vocab)
