"""Reduce a profiler trace of a span of the window to device numbers.

The trace (``jax.profiler`` xplane) holds the device's planes and the
host's.  On each TPU plane, the "XLA Modules" line has one event per
program execution and the "XLA Ops" line one per operation, named by its
HLO text (``%flash_attention.9 = bf16[...] custom-call(...)``); a loop op
encloses the ops of its body.  Programs are told apart by name
(``PROGRAMS``); an op is named by its HLO name without the ``%`` and the
instance number (``flash_attention``), and a kernel's time inside a kind
of program is the summed time of its ops there.  Busy time is the union
of the op intervals; an op's self time excludes the ops it encloses.  The host thread that holds the harness's own spans (``bench.*``) says
what the host was doing during each idle gap on the device (the innermost
span open there, the harness's or JAX's), and when the loop was waiting
for an arrival with nothing in flight.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

# Program (jit function) name fragment -> program kind.
PROGRAMS = {"prefill_step": "prefill", "decode_step": "decode"}
TOP = 10
_INSTANCE = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Summary:
    window_s: float                    # length of the traced span
    busy_s: float | None               # union of device op intervals
    active_s: float                    # span minus the loop's waits
    busy_active_s: float | None        # busy time outside the waits
    program_s: dict                    # kind -> summed module device time
    program_n: dict                    # kind -> module executions
    kernel_s: dict                     # (kind, op name) -> summed op time
    top_ops: list                      # [(op name, seconds)]
    idle_gaps: list                    # [(host span, seconds)]

    def kernel_time(self, kind: str, op: str) -> float:
        """Seconds of ``op`` inside programs of ``kind``."""
        return self.kernel_s.get((kind, op), 0.0)

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(intervals, a, b) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in intervals)


def _clip(intervals, a, b):
    return [(max(x, a), min(y, b)) for x, y in intervals if y > a and x < b]


def op_name(text: str) -> str:
    """``%flash_attention.9 = bf16[...] custom-call(...)`` -> ``flash_attention``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _INSTANCE.sub("", head)


def _self_times(ops):
    """{name: seconds} of time spent in each op outside the ops it encloses."""
    out: dict = {}
    stack: list = []  # [end, name, child_ns]
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            end, n, child = stack.pop()
            out[n] = out.get(n, 0.0) - child / 1e9
        if stack:
            stack[-1][2] += b - a
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
        stack.append([b, name, 0.0])
    for end, n, child in stack:
        out[n] = out.get(n, 0.0) - child / 1e9
    return out


def _kind(name: str) -> str | None:
    for frag, kind in PROGRAMS.items():
        if frag in name:
            return kind
    return None


def _timeline(spans, lo, hi):
    """[(start, end, name)] cutting [lo, hi] by the innermost host span
    open in each piece ("none" where none is); spans of one thread nest."""
    bounds = sorted({lo, hi, *(t for _, a, b in spans for t in (a, b)
                               if lo < t < hi)})
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][1] <= a:
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] <= a:
            stack.pop()
        # A span that ended under an inner one may still sit below it.
        live = [s for s in stack if s[2] > a]
        out.append((a, b, live[-1][0] if live else "none"))
    return out


def load(path) -> object:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(files[-1])


def reduce(path) -> Summary:
    return reduce_data(load(path))


def reduce_data(data) -> Summary:
    host_spans, waits = [], []
    tpu_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            tpu_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                # The loop's own thread: every host span on it, the
                # program's and JAX's too, can name what an idle gap waited on.
                if any(n.startswith("bench.") for n, _, _ in evs):
                    host_spans.extend(evs)
    waits = [(a, b) for n, a, b in host_spans if n == "bench.wait"]
    steps = [(a, b) for n, a, b in host_spans if n == "bench.step"]
    if steps:
        lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
        lo = min([lo] + [a for a, _ in waits])
        hi = max([hi] + [b for _, b in waits])
    else:
        lo = hi = 0.0
    window_ns = hi - lo
    waits_u = _union(_clip(waits, lo, hi))
    active_ns = window_ns - sum(b - a for a, b in waits_u)

    program_s: dict = {}
    program_n: dict = {}
    kernel_s: dict = {}
    op_time: dict = {}
    busy_ns = busy_active_ns = None
    idle: dict = {}
    if tpu_planes:
        busy_ns = busy_active_ns = 0.0
        timeline = _timeline(host_spans, lo, hi)
    for plane in tpu_planes:
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(e.start_ns, e.end_ns, _kind(e.name))
                           for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        modules = [m for m in modules if m[0] < hi and m[1] > lo]
        ops = [o for o in ops if o[0] < hi and o[1] > lo]
        for a, b, kind in modules:
            if kind is not None:
                program_s[kind] = program_s.get(kind, 0.0) + (b - a) / 1e9
                program_n[kind] = program_n.get(kind, 0) + 1
        ops = [(a, b, op_name(n)) for a, b, n in ops]
        for name, t in _self_times(ops).items():
            op_time[name] = op_time.get(name, 0.0) + t
        mods = sorted(m for m in modules if m[2] is not None)
        j = 0
        for a, b, name in sorted(ops):
            while j < len(mods) and mods[j][1] <= a:
                j += 1
            if j < len(mods) and mods[j][0] <= a:
                key = (mods[j][2], name)
                kernel_s[key] = kernel_s.get(key, 0.0) + (b - a) / 1e9
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        busy_active_ns += sum(b - a for a, b in busy) - sum(
            _overlap(busy, a, b) for a, b in waits_u
        )
        # Idle time on the device, put down to the innermost host span open
        # through each piece of it.
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_ = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        j = 0
        for a, b, name in timeline:
            while j < len(gaps_) and gaps_[j][1] <= a:
                j += 1
            k = j
            while k < len(gaps_) and gaps_[k][0] < b:
                cut = min(b, gaps_[k][1]) - max(a, gaps_[k][0])
                idle[name] = idle.get(name, 0.0) + max(cut, 0.0) / 1e9
                k += 1
    n = max(len(tpu_planes), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=window_ns / 1e9,
        busy_s=None if busy_ns is None else busy_ns / 1e9 / n,
        active_s=active_ns / 1e9,
        busy_active_s=None if busy_active_ns is None else busy_active_ns / 1e9 / n,
        program_s={k: v / n for k, v in program_s.items()},
        program_n=program_n,
        kernel_s={k: v / n for k, v in kernel_s.items()},
        top_ops=[(k, v / n) for k, v in top],
        idle_gaps=[(k, v / n) for k, v in gaps],
    )
