"""The served prefill: one AOT program per (batch, sequence) bucket.

For each attention-plus-dense decoder the served path runs, the prefill
program is held to the sessionless ``forward``:

  * the logits that predict the first new token come from the last REAL
    prompt position, not the bucket's last (pad) row, and teacher-forced
    decode after it matches ``forward`` position by position — for a
    prompt under the 128 floor, one exactly at a bucket, and one a row
    past a bucket with a padded batch bucket;
  * the cache the program returns holds, at each real prompt row of each
    real batch row, the keys and values ``forward`` computes;
  * ``prefill`` names that one path: any other value raises.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.launch.serve import Request, VortexServer
from repro.models.model import forward
from repro.models.registry import get_smoke_config

ARCHS = [
    "paper-gpt2-124m",
    "phi4-mini-3.8b",
    "gemma2-9b",
    "h2o-danube-3-4b",
    "starcoder2-15b",
]
# (batch, prompt): under the 128 floor, exactly at a bucket, a row past a
# bucket with batch 3 padded to 4.
SHAPES = [(1, 17), (2, 128), (3, 129)]
N_DEC = 3
# Served and reference logits are bfloat16 programs that differ only in
# the attention executables' rounding.
LOGIT_ATOL = 2e-2
# The first layer's cache rows are bit-equal; later layers inherit the
# attention's bfloat16 rounding, at most one ulp of the largest keys and
# values these caches hold (|x| < 8).
KV_ATOL = 2.0 ** -5


@pytest.fixture(scope="module", params=ARCHS)
def server(request):
    cfg = get_smoke_config(request.param)
    return VortexServer(cfg, make_host_mesh(), max_cache=256)


def _tokens(cfg, b, n, seed):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, (b, n)
    ).astype(np.int32)


@pytest.mark.parametrize("b,s", SHAPES, ids=[f"b{b}-s{s}" for b, s in SHAPES])
def test_first_token_comes_from_the_last_prompt_position(server, b, s):
    """Served logits (prefill, then teacher-forced decode) match the
    sessionless forward position by position, and generate()'s first
    token is their argmax."""
    cfg = server.cfg
    toks = _tokens(cfg, b, s + N_DEC, seed=3)
    ref = forward(
        cfg, server.rules, server.params, jnp.asarray(toks),
        mode="prefill", cache_len=s + N_DEC,
    )[0]
    ref = np.asarray(ref[:, s - 1:, :cfg.vocab], np.float32)
    got = server.score(toks, s)[..., :cfg.vocab]
    assert got.shape == ref.shape == (b, N_DEC + 1, cfg.vocab)
    np.testing.assert_allclose(got, ref, atol=LOGIT_ATOL, rtol=0)
    first = server.generate(Request(tokens=toks[:, :s], max_new=1))
    np.testing.assert_array_equal(first[:, 0], got[:, 0].argmax(-1))


def test_prefill_cache_rows_match_forward(server):
    """At a padded batch and sequence bucket, every real prompt row of the
    returned cache holds forward's keys and values.  Rows past the prompt
    are masked by ``kv_len`` and not checked."""
    cfg = server.cfg
    b, s = SHAPES[-1]
    toks = _tokens(cfg, b, s, seed=5)
    bp, sp, _, cache = server._prefill(toks)
    assert bp > b and sp > s  # both dims really are padded
    ref = forward(
        cfg, server.rules, server.params, jnp.asarray(toks),
        mode="prefill", cache_len=s,
    )[1]
    assert set(ref) == set(cache)
    for key, entry in ref.items():
        assert set(entry) == {"k", "v"}
        for name, want in entry.items():
            got = cache[key][name]  # (groups, bp, kv heads, kv bucket, hd)
            assert got.shape[3] == server.kv_bucket(sp)
            np.testing.assert_allclose(
                np.asarray(got[:, :b, :, :s], np.float32),
                np.asarray(want, np.float32),
                atol=KV_ATOL, rtol=0, err_msg=f"{key}/{name}",
            )


def test_prefill_knob_validated():
    cfg = get_smoke_config("paper-gpt2-124m")
    VortexServer(cfg, make_host_mesh(), prefill="aot")
    for value in ("chained", "nope"):
        with pytest.raises(ValueError, match="prefill"):
            VortexServer(cfg, make_host_mesh(), prefill=value)
