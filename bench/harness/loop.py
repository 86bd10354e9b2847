"""The measured window: one thread drives ``ContinuousScheduler``.

Each pass of the loop submits every request whose due time has passed,
calls ``step()`` once, and stamps the tokens that step put into
``sched.rows[*].out``.  With nothing in flight it sleeps until the next due
time.  Open loop: requests are due on the mix's schedule whatever the
server does, and each is timed from its due time.  Closed loop: each client
sends its next request when its last one completes.

Traffic starts ``lead_s`` before the window opens, uncounted, so the
window begins in steady state rather than with an empty server (or, in
a closed loop, with every client's first request at once).  Every request
due inside the window counts.  Arrivals go on after the window closes
(uncounted), so the counted requests finish under the same load, until
they are all done or ``cap_s`` has passed.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np

from repro.launch.serve import Request


@dataclasses.dataclass
class Rec:
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    counted: bool
    client: int = -1
    tokens: list = dataclasses.field(default_factory=list)
    stamps: list = dataclasses.field(default_factory=list)
    failed: str | None = None

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new

    @property
    def open(self) -> bool:
        return not self.done and self.failed is None


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                      # t0 + seconds: the window's close
    end: float                     # when the loop stopped
    recs: list
    steps: list                    # (start, end, positions_before, positions_after)
    lateness: list                 # submit time - due time, per submission
    trace: tuple | None = None     # (start, end) host times of the traced steps
    slowest: dict = dataclasses.field(default_factory=dict)
    # phase -> (seconds, start - t0) of its slowest pass
    gc_pauses: list = dataclasses.field(default_factory=list)
    # (start - t0, seconds, generation) of each garbage collection


def _span(name):
    return jax.profiler.TraceAnnotation(name)


def run(sched, gen, seconds: float, *, lead_s: float = 0.0,
        cap_s: float = 60.0, trace_at: tuple | None = None,
        tracer=None) -> Window:
    """Drive ``sched`` with ``gen``'s requests for ``seconds``.

    ``trace_at`` = (a, b): seconds after the window opens at which
    ``tracer.start()`` / ``tracer.stop()`` are called, between steps.
    """
    clock = time.perf_counter
    recs: dict[int, Rec] = {}
    order: list[Rec] = []
    steps, lateness = [], []
    trace_span = None
    tracing = False
    slowest: dict[str, tuple[float, float]] = {}
    pauses: list = []
    gc_start = [0.0]

    def note(phase: str, a: float, b: float) -> None:
        if b - a > slowest.get(phase, (0.0,))[0]:
            slowest[phase] = (b - a, a - t0)

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            gc_start[0] = clock()
        else:
            pauses.append((gc_start[0] - t0, clock() - gc_start[0],
                           info["generation"]))

    def submit(due: float, counted: bool, client: int = -1) -> None:
        a = clock()
        draw = gen.request()
        with _span("bench.submit"):
            rid = sched.submit(
                Request(tokens=draw.prompt[None], max_new=draw.max_new)
            )
        now = clock()
        note("submit", a, now)
        rec = Rec(rid=rid, due=due, prompt=draw.prompt,
                  max_new=draw.max_new, counted=counted, client=client)
        recs[rid] = rec
        order.append(rec)
        lateness.append(now - due)

    start = clock()
    t0 = start + lead_s
    t1 = t0 + seconds

    def counts(due: float) -> bool:
        return t0 <= due < t1

    next_due = None
    if gen.closed:
        for c in range(gen.clients):
            submit(start, counts(start), c)
    else:
        next_due = start + gen.gap()

    gc.callbacks.append(on_gc)
    try:
        while True:
            now = clock()
            counted_open = any(r.counted and r.open for r in order)
            if now >= t1 and not counted_open:
                break
            if now >= t1 + cap_s:
                for r in order:
                    if r.counted and r.open:
                        r.failed = f"not finished {cap_s} s after the window"
                break
            if trace_at is not None and tracer is not None:
                if (not tracing and trace_span is None
                        and now >= t0 + trace_at[0]):
                    tracer.start()
                    tracing = True
                    trace_span = [clock(), None]
                elif tracing and now >= t0 + trace_at[1]:
                    trace_span[1] = clock()
                    tracer.stop()
                    tracing = False
            if next_due is not None:
                while next_due <= now:
                    submit(next_due, counts(next_due))
                    next_due += gen.gap()
            in_flight = [r for r in order if r.open]
            if not in_flight:
                if next_due is None:
                    break
                with _span("bench.wait"):
                    time.sleep(max(0.0, next_due - clock()))
                continue
            before = len(sched.step_positions)
            a = clock()
            with _span("bench.step"):
                worked = sched.step()
            b = clock()
            note("step", a, b)
            steps.append((a, b, before, len(sched.step_positions)))
            with _span("bench.bookkeeping"):
                _stamp(sched, recs, b)
                _settle(sched, in_flight, worked)
                if gen.closed:
                    for r in in_flight:
                        if not r.open:
                            submit(b, counts(b), r.client)
            note("bookkeeping", b, clock())
        if tracing:
            trace_span[1] = clock()
            tracer.stop()
    finally:
        gc.callbacks.remove(on_gc)
    return Window(
        t0=t0, t1=t1, end=clock(), recs=order, steps=steps,
        lateness=lateness, trace=tuple(trace_span) if trace_span else None,
        slowest=slowest, gc_pauses=pauses,
    )


def gc_summary(pauses: list) -> dict:
    """Count, total and longest of a window's garbage collections, and
    the longest of the oldest generation's."""
    full = [p[1] for p in pauses if p[2] == 2]
    return {
        "count": len(pauses),
        "total_s": sum(p[1] for p in pauses),
        "longest_s": max((p[1] for p in pauses), default=0.0),
        "full_count": len(full),
        "full_longest_s": max(full, default=0.0),
    }


def _stamp(sched, recs: dict, now: float) -> None:
    for row in sched.rows:
        if row is None:
            continue
        rec = recs.get(row.rid)
        if rec is None:
            continue
        new = row.out[len(rec.tokens):]
        if new:
            rec.tokens.extend(int(t) for t in new)
            rec.stamps.extend([now] * len(new))


def _settle(sched, in_flight: list, worked: bool) -> None:
    """Mark requests the scheduler resolved to an error.  After a step every
    queued request that fits a free slot has been admitted, so an open
    request with no seat is no longer the scheduler's."""
    seated = {row.rid for row in sched.rows if row is not None}
    free = any(row is None for row in sched.rows)
    for r in in_flight:
        if not r.open or r.rid in seated:
            continue
        if r.tokens or free or not worked:
            r.failed = "resolved without all its tokens"


def finish(sched, window: Window) -> None:
    """Collect the scheduler's own results: every counted request's tokens
    must equal what the loop stamped, and errors count as failed."""
    results = sched.drain()
    for r in window.recs:
        if not r.counted:
            continue
        got = results.get(r.rid)
        if got is None:
            if r.failed is None:
                r.failed = "no result from drain()"
        elif isinstance(got, Exception):
            r.failed = f"{type(got).__name__}: {got}"
        elif r.failed is None and list(np.asarray(got)[0]) != r.tokens:
            r.failed = "drain() tokens differ from the stamped stream"
    sched.close()
