"""The scheduler's spans and its per-launch record tell one story.

Each ``ContinuousScheduler.step()`` opens ``sched.*`` profiler spans and
adds the same stamps to its ``step_positions`` entry: ``t0``/``t1``,
seconds per span name (``phases``), the seconds the host waited on the
device (``blocked_s``) and one dict per admission (``admits``).  These
tests hold the record to its arithmetic, every request to exactly one
admission, and a CPU profiler trace to the span names, their stats and
their nesting.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.launch.scheduler import ContinuousScheduler
from repro.launch.serve import Request, VortexServer
from repro.models.registry import get_smoke_config

SPANS = (
    "sched.step", "sched.admit", "sched.prefill", "sched.first_token",
    "sched.slot_copy", "sched.grow", "sched.decode", "sched.readback",
    "sched.emit",
)
# Spans of which no two ever nest in one another.
DISJOINT = ("sched.admit", "sched.decode", "sched.readback", "sched.emit")


@pytest.fixture(scope="module")
def server():
    cfg = get_smoke_config("paper-gpt2-124m")
    return VortexServer(cfg, make_host_mesh(), max_cache=256)


def _request(rng, s, max_new):
    return Request(
        tokens=rng.integers(0, 512, (1, s)).astype(np.int32),
        max_new=max_new,
    )


def _serve(server, seed):
    """Staggered traffic on four slots: two requests, a few steps, then
    three more, one of which (121 tokens, 16 new) outgrows the first kv
    bucket mid-decode.  Returns the scheduler and {rid: prompt length}."""
    rng = np.random.default_rng(seed)
    sched = ContinuousScheduler(server, batch_rows=4)
    sent = {}

    def submit(s, max_new):
        sent[sched.submit(_request(rng, s, max_new))] = s

    submit(20, 6)
    submit(45, 4)
    for _ in range(3):
        sched.step()
    submit(121, 16)
    submit(9, 5)
    submit(60, 3)
    res = sched.drain()
    assert set(res) == set(sent)
    assert all(isinstance(v, np.ndarray) for v in res.values())
    sched.close()
    return sched, sent


@pytest.fixture(scope="module")
def served(server):
    return _serve(server, 0)


def test_record_stamps_ordered(served):
    sched, _ = served
    assert sched.step_positions
    assert len(sched.step_positions) == sched.stats["steps"]
    for rec in sched.step_positions:
        assert rec["t0"] <= rec["t1"]
        assert {"kvb", "pos", "slots"} <= set(rec)


def test_record_blocked_within_step(served):
    sched, _ = served
    for rec in sched.step_positions:
        span = rec["t1"] - rec["t0"]
        assert 0.0 <= rec["blocked_s"] <= span
        assert rec["blocked_s"] == pytest.approx(
            rec["phases"].get("sched.first_token", 0.0)
            + rec["phases"]["sched.readback"]
        )


def test_record_phases_within_step(served):
    sched, _ = served
    for rec in sched.step_positions:
        span = rec["t1"] - rec["t0"]
        phases = rec["phases"]
        assert set(phases) <= set(SPANS)
        assert {"sched.step", "sched.decode", "sched.readback",
                "sched.emit"} <= set(phases)
        assert all(0.0 <= v <= span for v in phases.values())
        assert sum(phases.get(n, 0.0) for n in DISJOINT) <= span


def test_each_request_admitted_once(served, server):
    sched, sent = served
    admits = [a for rec in sched.step_positions for a in rec["admits"]]
    assert sorted(a["rid"] for a in admits) == sorted(sent)
    for a in admits:
        assert a["prompt"] == sent[a["rid"]]
        assert a["bucket"] == server.prefill_seq_bucket(a["prompt"])
        assert 0.0 <= a["admit_s"]
        assert 0.0 <= a["queued_s"]
    for rec in sched.step_positions:
        assert sum(a["admit_s"] for a in rec["admits"]) == pytest.approx(
            rec["phases"].get("sched.admit", 0.0)
        )
    # Every submit stamp was taken back at admission.
    assert not sched._submitted


def test_submit_stamps_settle_on_expiry_and_failure(server):
    """A request that expires in the queue or fails its admission leaves
    no submit stamp behind and no admission in the record."""
    from repro.runtime import faults

    rng = np.random.default_rng(1)
    sched = ContinuousScheduler(server, batch_rows=4)
    doomed = Request(tokens=_request(rng, 12, 4).tokens, max_new=4,
                     deadline_s=0.0)
    sched.submit(doomed)
    plan = faults.FaultPlan({"pool_lease": [1]})
    with faults.installed(plan):
        failed = sched.submit(_request(rng, 30, 4))
        ok = sched.submit(_request(rng, 14, 4))
        res = sched.drain()
    assert plan.fired == [("pool_lease", 1)]
    assert isinstance(res[failed], Exception)
    assert isinstance(res[ok], np.ndarray)
    assert not sched._submitted
    admits = [a["rid"] for r in sched.step_positions for a in r["admits"]]
    assert admits == [ok]
    sched.close()


@pytest.fixture(scope="module")
def traced(server, tmp_path_factory):
    """Host events of a CPU profiler trace of ``_serve``, by thread."""
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("sched_trace")
    jax.profiler.start_trace(str(path))
    try:
        _serve(server, 2)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(sorted(files)[-1])
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("sched.")]
            if evs:
                lines.append(evs)
    return lines


def test_trace_emits_every_span(traced):
    names = {e[0] for evs in traced for e in evs}
    assert names == set(SPANS)


def test_trace_admit_carries_ids(traced):
    admits = [e for evs in traced for e in evs if e[0] == "sched.admit"]
    assert len(admits) == 5
    for _, _, _, stats in admits:
        assert {"rid", "prompt", "bucket"} <= set(stats)
    assert sorted(s["prompt"] for *_, s in admits) == [9, 20, 45, 60, 121]
    decodes = [e for evs in traced for e in evs if e[0] == "sched.decode"]
    assert decodes and all({"rows", "kvb"} <= set(e[3]) for e in decodes)


def test_trace_spans_nest_in_a_step(traced):
    assert len(traced) == 1, "the spans came from more than one thread"
    evs = traced[0]
    steps = [(a, b) for n, a, b, _ in evs if n == "sched.step"]
    for name, a, b, _ in evs:
        if name != "sched.step":
            assert any(x <= a and b <= y for x, y in steps), name
