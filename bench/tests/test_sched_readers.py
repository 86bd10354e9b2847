"""The scheduler readers (``sched.admit_ms``, ``sched.host_ms_per_step``)
on a window whose every number is known: their arithmetic, the steps they
leave out because the profiler ran through them, and what they return
where there is nothing to read."""
from __future__ import annotations

import pytest

from bench.harness import spec
from bench.harness.context import Context
from bench.harness.loop import Window

TRACED = (4.0, 6.0)


def _launch(t0, t1, blocked, admits=()):
    return {"kvb": 1024, "pos": [5], "slots": [0], "t0": t0, "t1": t1,
            "blocked_s": blocked, "phases": {},
            "admits": [{"admit_s": s, "queued_s": 0.0} for s in admits]}


# (start, end, launch or None): one harness pass of step() each.
STEPS = [
    (1.0, 1.5, _launch(1.0, 1.5, 0.2, admits=(0.1, 0.3))),
    (2.0, 2.4, None),                                   # no launch
    (3.9, 4.1, _launch(3.9, 4.1, 0.0, admits=(5.0,))),  # runs into the trace
    (5.0, 5.5, _launch(5.0, 5.5, 0.0, admits=(5.0,))),  # inside the trace
    (7.0, 7.2, _launch(7.0, 7.2, 0.05)),
    (10.5, 11.0, _launch(10.5, 11.0, 0.0, admits=(5.0,))),  # after the window
]


def _ctx(steps, traced=TRACED):
    positions, rows = [], []
    for start, end, launch in steps:
        i = len(positions)
        if launch is not None:
            positions.append(launch)
        rows.append((start, end, i, len(positions)))
    win = Window(t0=0.0, t1=10.0, end=11.0, recs=[], steps=rows,
                 lateness=[], trace=traced)
    return Context(sizes={}, peaks=None, window=win, positions=positions,
                   prefill_bucket={})


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_admit_ms_mean_of_untraced_admissions():
    assert _read("sched.admit_ms", _ctx(STEPS)) == pytest.approx(200.0)


def test_host_ms_per_step_less_blocked_time():
    # (0.5 - 0.2) and (0.2 - 0.05) seconds over two untraced launches.
    assert _read("sched.host_ms_per_step", _ctx(STEPS)) == pytest.approx(225.0)


def test_traced_steps_left_out_only_where_they_overlap():
    # With no traced span the two steps under it count too.
    ctx = _ctx(STEPS, traced=None)
    assert _read("sched.admit_ms", ctx) == pytest.approx(1e3 * 10.4 / 4)
    assert _read("sched.host_ms_per_step", ctx) == pytest.approx(
        1e3 * (0.3 + 0.2 + 0.5 + 0.15) / 4)


@pytest.mark.parametrize("name", ["sched.admit_ms", "sched.host_ms_per_step"])
def test_nothing_to_read(name):
    assert _read(name, _ctx([])) is None
    # Launches only under the traced span.
    assert _read(name, _ctx(STEPS[3:4])) is None
    # A record of a scheduler that keeps no stamps.
    bare = [(1.0, 1.5, {"kvb": 1024, "pos": [5], "slots": [0]})]
    assert _read(name, _ctx(bare)) is None


def test_admit_ms_none_without_admissions():
    ctx = _ctx(STEPS[4:5])
    assert _read("sched.admit_ms", ctx) is None
    assert _read("sched.host_ms_per_step", ctx) == pytest.approx(150.0)
