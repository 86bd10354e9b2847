"""The one traffic generator: a mix's data file in, requests out.

A mix (``bench/traffic/<name>.json``) gives:

  arrivals        {"kind": "poisson", "rate_per_s": r}  open loop, or
                  {"kind": "closed", "clients": n}       closed loop
  prompt_tokens,  {"dist": "lognormal", "median": m, "sigma": s,
  output_tokens    "min": lo, "max": hi}
  server          {"batch_rows": b, "max_cache": c}
  check           {"served_tokens": t, "max_requests": k}  (harness/check.py)
  lead_s, cap_s   uncounted traffic before the window; the wait for its
                  last counted request after it (harness/loop.py)
  trace_from,     where the traced span starts (share of the window) and
  trace_seconds   how long it lasts
  rehearsal       keys replaced in a --cpu-rehearsal run (smoke sizes)

Every seed gets the same sizes and gaps in another order: draws come in
blocks of ``BLOCK``, and each block holds the distribution's quantiles at
(i + 0.5) / BLOCK, permuted by the seed.  So two seeds differ in which
request is long and when it comes, not in how much work they bring.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

BLOCK = 64
_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Draw:
    prompt: np.ndarray  # (prompt_len,) int32 token ids
    max_new: int


def _quantiles(spec: dict) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    u = (np.arange(BLOCK) + 0.5) / BLOCK
    z = np.array([_NORMAL.inv_cdf(x) for x in u])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


class Stratified:
    """An endless stream of a fixed block of values, reshuffled per block."""

    def __init__(self, values: np.ndarray, rng: np.random.Generator):
        self._values = values
        self._rng = rng
        self._buf: list = []

    def next(self):
        if not self._buf:
            self._buf = list(self._rng.permutation(self._values))
        return self._buf.pop()


class Generator:
    """Requests of one mix for one seed: sizes, token ids and arrival gaps."""

    def __init__(self, mix: dict, vocab: int, max_cache: int,
                 rng: np.random.Generator):
        self.mix = mix
        self.vocab = vocab
        self.max_cache = max_cache
        self._rng = rng
        self._prompt = Stratified(_quantiles(mix["prompt_tokens"]), rng)
        self._output = Stratified(_quantiles(mix["output_tokens"]), rng)
        arr = mix["arrivals"]
        self.closed = arr["kind"] == "closed"
        if arr["kind"] == "poisson":
            u = (np.arange(BLOCK) + 0.5) / BLOCK
            self._gap = Stratified(-np.log1p(-u) / arr["rate_per_s"], rng)
        elif not self.closed:
            raise ValueError(f"unknown arrival kind {arr['kind']!r}")
        worst = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] - 1
        if worst > max_cache:
            raise ValueError(
                f"mix needs {worst} cache rows, the server holds {max_cache}"
            )

    @property
    def clients(self) -> int:
        return self.mix["arrivals"]["clients"] if self.closed else 0

    def request(self) -> Draw:
        s = int(self._prompt.next())
        toks = self._rng.integers(0, self.vocab, size=s, dtype=np.int32)
        return Draw(prompt=toks, max_new=int(self._output.next()))

    def gap(self) -> float:
        """Seconds to the next open-loop arrival."""
        return float(self._gap.next())


def lengths(spec: dict) -> tuple[int, int]:
    """Smallest and largest length a length spec can draw."""
    q = _quantiles(spec)
    return int(q.min()), int(q.max())


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), as a float."""
    if not len(values):
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
