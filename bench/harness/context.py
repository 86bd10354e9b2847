"""What a per-layer metric reader gets: the window's records, the
scheduler's per-launch positions, the reduced trace, sizes and peaks.

Readers return a number, or None where the run holds nothing to read (no
trace, no admission in the traced span, a kernel that is not on the path).

The arithmetic from shapes holds for every decoder ``system.serves``:
attention projections at ``heads`` query and ``kv_heads`` key/value heads,
and a gated MLP (``swiglu``, ``geglu``) counted with its third matrix.
The attention kernels' own counts (``bench/kernels/``) already read K and
V bytes by ``kv_heads``.
"""
from __future__ import annotations

import dataclasses

from bench.harness import spec
from bench.harness.weights import GATED


@dataclasses.dataclass
class Context:
    sizes: dict
    peaks: dict
    window: object                 # loop.Window
    positions: list                # sched.step_positions entries, all of them
    prefill_bucket: dict           # prompt length -> sequence bucket
    trace: object | None = None    # trace.Summary of the traced span

    # -- the window ---------------------------------------------------------

    @property
    def seconds(self) -> float:
        return self.window.t1 - self.window.t0

    def window_steps(self) -> list:
        """Steps that started inside the window."""
        w = self.window
        return [s for s in w.steps if w.t0 <= s[0] < w.t1]

    def admitted_in(self, a: float, b: float) -> list:
        """Requests whose first token a step ending in [a, b] produced."""
        return [r for r in self.window.recs
                if r.stamps and a <= r.stamps[0] <= b]

    def launches_in(self, a: float, b: float) -> list:
        """Per-launch position entries of the steps inside [a, b]."""
        out = []
        for start, end, i, j in self.window.steps:
            if start >= a and end <= b:
                out.extend(self.positions[i:j])
        return out

    # -- the traced span ----------------------------------------------------

    def traced(self) -> tuple[float, float] | None:
        return self.window.trace if self.trace is not None else None

    # -- arithmetic from shapes ---------------------------------------------

    def layer_matmul_params(self) -> int:
        z = self.sizes
        q = z["heads"] * z["head_dim"]
        kv = z["kv_heads"] * z["head_dim"]
        mlp = (3 if z["act"] in GATED else 2) * z["d_model"] * z["d_ff"]
        return z["layers"] * (2 * z["d_model"] * q + 2 * z["d_model"] * kv + mlp)

    def head_params(self) -> int:
        return self.sizes["d_model"] * self.sizes["vocab"]

    def kernel(self, name: str):
        return spec.kernel(name)
