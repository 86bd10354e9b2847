"""The trace reduction on a trace whose every number is known.

The trace is written as an XSpace text proto: a host thread with the
harness's spans and one of JAX's, and a TPU plane with a prefill and a
decode program, their ops, and a Pallas custom call inside each.  Times
are in microseconds here, nanoseconds in the proto's lines.
"""
from __future__ import annotations

import pytest

from bench.harness import trace


def _plane(pid, name, lines):
    meta, out, mid = {}, [], 0
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, start_us, dur_us in events:
            if ename not in meta:
                mid += 1
                meta[ename] = mid
            evs.append(f"events {{ metadata_id: {meta[ename]} "
                       f"offset_ps: {int(start_us * 1e6)} "
                       f"duration_ps: {int(dur_us * 1e6)} }}")
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                   + " ".join(evs) + " }")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                  for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(out) + " " + md + " }"


HOST = ("python", [
    ("bench.step", 0, 100),          # a step holding a prefill and a decode
    ("np.asarray(jax.Array)", 80, 15),
    ("bench.bookkeeping", 100, 10),
    ("bench.wait", 110, 90),         # nothing in flight
    ("bench.step", 200, 50),         # a decode step
])
FA = "%flash_attention.3 = bf16[1] custom-call(bf16[1] %p)"
TPU = [
    ("XLA Modules", [("jit_prefill_step(7)", 10, 40),
                     ("jit_decode_step(9)", 55, 20),
                     ("jit_decode_step(9)", 210, 30)]),
    ("XLA Ops", [("%fusion.1 = bf16[2] fusion(bf16[2] %a)", 10, 15),
                 (FA, 25, 20),                              # in the prefill
                 ("%fusion.2 = bf16[2] fusion(bf16[2] %b)", 45, 5),
                 ("%while.7 = (s32[]) while((s32[]) %t)", 60, 15),
                 (FA, 60, 10),                              # in a decode, in the loop
                 ("%dot.4 = bf16[2] dot(bf16[2] %c)", 70, 5),  # in the loop
                 (FA, 215, 20),                             # in a decode
                 ("%copy.5 = bf16[2] copy(bf16[2] %d)", 120, 10)]),  # in the wait
]


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    text = (_plane(1, "/host:CPU", [HOST]) + " "
            + _plane(2, "/device:TPU:0", TPU))
    return trace.reduce_data(ProfileData.from_text_proto(text))


def test_window_busy_and_idle(summary):
    # The span runs from the first step's start to the last one's end.
    assert summary.window_s == pytest.approx(250e-6)
    # Ops cover 10-50, 60-75, 120-130, 215-235 us.
    assert summary.busy_s == pytest.approx(85e-6)
    # Work was held outside the 90 us wait, and 75 us of busy time fell
    # there.
    assert summary.active_s == pytest.approx(160e-6)
    assert summary.busy_active_s == pytest.approx(75e-6)


def test_programs_and_kernels(summary):
    assert summary.program_s == pytest.approx({"prefill": 40e-6,
                                               "decode": 50e-6})
    assert summary.program_n == {"prefill": 1, "decode": 2}
    assert summary.kernel_time("prefill", "flash_attention") == pytest.approx(20e-6)
    assert summary.kernel_time("decode", "flash_attention") == pytest.approx(30e-6)
    assert summary.kernel_time("decode", "dot") == pytest.approx(5e-6)
    assert summary.kernel_time("prefill", "dot") == 0.0


def test_op_names():
    assert trace.op_name(FA) == "flash_attention"
    assert trace.op_name("%constant_dynamic-slice_fusion.17 = bf16[1] "
                         "fusion()") == "constant_dynamic-slice_fusion"
    assert trace.op_name("jit_decode_step(9)") == "jit_decode_step(9)"


def test_breakdown(summary):
    b = summary.breakdown()
    ops = dict(b["device_ops"])
    # Self time: the loop's 15 us hold 10 + 5 us of enclosed ops.
    assert ops["flash_attention"] == pytest.approx(50e-6)
    assert ops["while"] == pytest.approx(0.0, abs=1e-12)
    assert ops["fusion"] == pytest.approx(20e-6)
    assert list(ops)[0] == "flash_attention"
    gaps = dict(b["idle_gaps"])
    # Idle 0-10, 50-60, 75-80, 95-100 and 235-250... in the steps; 80-95
    # under the readback; 100-110 in bookkeeping; 110-120 and 130-200 in
    # the wait; 200-215 in the last step.
    assert gaps["bench.step"] == pytest.approx(60e-6)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(15e-6)
    assert gaps["bench.bookkeeping"] == pytest.approx(10e-6)
    assert gaps["bench.wait"] == pytest.approx(80e-6)
    assert len(b["device_ops"]) <= trace.TOP


def test_recorded_chip_trace():
    """A trace recorded on one v5e of the gpt2-chat path: five scheduler
    steps at the 1024 kv bucket, 16 rows, one of them admitting a
    200-token prompt (prefill at the 256 bucket), and a 2 ms wait."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    path = Path(__file__).parent / "data" / "gpt2_chat_steps.xplane.pb.gz"
    with gzip.open(path) as f:
        s = trace.reduce_data(ProfileData.from_serialized_xspace(f.read()))
    assert s.program_n == {"prefill": 1, "decode": 5}
    assert s.window_s == pytest.approx(0.07844376)
    assert s.busy_s == pytest.approx(0.054075107)
    assert s.active_s == pytest.approx(0.075338305)
    assert s.program_s["decode"] == pytest.approx(0.050811901)
    assert s.program_s["prefill"] == pytest.approx(0.001241921)
    assert s.kernel_time("decode", "flash_attention") == pytest.approx(0.014651091)
    assert s.kernel_time("prefill", "flash_attention") == pytest.approx(0.000488914)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.wait"] == pytest.approx(0.003105455)
    # Every idle second is put down to some span, and no more than exists.
    assert sum(gaps.values()) <= s.window_s - s.busy_s + 1e-9
    assert s.busy_s < s.window_s
