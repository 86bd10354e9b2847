"""Whether what the window served is correct.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed, is run through the plain
float32 reference: each prompt with its served tokens, once.  At every
served position the reference's logits say how far the served token lies
below its own best choice.  The number compared is the widest such gap
over the sample (``logit_gap``).  A greedy server that computes what the
reference computes, in the precision the configuration states, keeps it
small; one that computes something else, or in a lower precision, does not.

The control (``--control``) puts the reference computed in float8 in the
program's place: at the same positions the tokens judged are the ones the
float8 logits put first, against the same limit, so a control run has to
come out not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sample(recs: list, rng: np.random.Generator, served_tokens: int,
           max_requests: int) -> list:
    """The longest finished request, then others drawn from ``rng`` until
    ``served_tokens`` served tokens or ``max_requests`` requests."""
    done = [r for r in recs if r.counted and r.done and r.failed is None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new, r.rid))
    pick = [longest]
    for i in rng.permutation(len(done)):
        if (sum(len(r.tokens) for r in pick) >= served_tokens
                or len(pick) >= max_requests):
            break
        if done[i] is not longest:
            pick.append(done[i])
    return pick


@jax.jit
def _gap(ref, toks):
    """Per row: the reference's best logit minus its logit at ``toks``."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
    return best - got


_argmax = jax.jit(lambda x: jnp.argmax(x, axis=-1))


def gaps(ref_mod, sizes: dict, weights: dict, rec, *, control: bool) -> dict:
    """Per-position gaps of one request: the served tokens' and, with
    ``control``, the float8 reference's first choices'.  Row s - 1 + i of
    the reference (s prompt tokens) predicts served token i; everything
    on the device runs at the padded length, so few shapes compile."""
    n = len(rec.tokens)
    s = len(rec.prompt)
    seq = np.concatenate([rec.prompt, np.asarray(rec.tokens[:-1], np.int32)])
    ref = ref_mod.logits(sizes, weights, seq)
    want = np.zeros((ref.shape[0],), np.int32)
    want[s - 1:s - 1 + n] = rec.tokens
    rows = slice(s - 1, s - 1 + n)
    out = {
        "served": np.asarray(_gap(ref, jnp.asarray(want)))[rows],
        "agree": float(np.mean(np.asarray(_argmax(ref))[rows] == rec.tokens)),
    }
    if control:
        low = _argmax(ref_mod.logits(sizes, weights, seq, fp8=True))
        out["control"] = np.asarray(_gap(ref, low))[rows]
    return out


def judge(ref_mod, sizes: dict, weights: dict, picked: list, limits: dict,
          *, control: bool = False) -> tuple[bool, dict, dict]:
    """(correct, numbers compared with their limits, diagnostics).

    With ``control`` the float8 reference's tokens are judged in the
    program's place; the served tokens' gap is then only a diagnostic."""
    limit = limits["logit_gap"]["limit"]
    judged, served, agree = [], [], []
    for rec in picked:
        g = gaps(ref_mod, sizes, weights, rec, control=control)
        served.append(g["served"].max())
        judged.append(g["control" if control else "served"].max())
        agree.append(g["agree"])
    diag = {
        "requests": len(picked),
        "served_tokens": int(sum(len(r.tokens) for r in picked)),
        "argmax_agreement": float(np.mean(agree)) if agree else None,
    }
    if not picked:
        return False, {"logit_gap": {"value": None, "limit": limit}}, diag
    value = float(max(judged))
    if control:
        diag["control"] = True
        diag["program_logit_gap"] = float(max(served))
    return value <= limit, {"logit_gap": {"value": value, "limit": limit}}, diag
