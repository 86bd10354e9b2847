"""The whole harness at smoke size on the CPU, and the faults it must catch.

``--cpu-rehearsal`` runs the program's smoke preset of the cell's
configuration with the mix's ``rehearsal`` sizes; the chip check is the
only step skipped.  The fault tests break the timed path underneath the
scheduler and require ``correct`` to come out false.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.harness import spec

ROOT = Path(__file__).resolve().parents[2]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace", [("gpt2-chat", 0), ("gpt2-chat", 1),
                                            ("gpt2-batch", 0), ("gpt2-batch", 1)])
def test_rehearsal_result_line(workload, trace):
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**33 + 5), "--seconds", "2", "--trace", str(trace),
         "--cpu-rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_home")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = _last_line(proc.stdout)
    assert set(out) == KEYS | ({"breakdown"} if trace else set())
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"]
    cell = spec.load(workload)
    device_metrics = {m["name"] for m in cell.bench["end_to_end"]
                      + cell.bench["per_layer"]}
    # No number from the CPU is written under a metric's own name.
    assert not set(out["metrics"]) & device_metrics
    assert all(k.startswith("cpu.") for k in out["metrics"])
    # Each compared number is on stderr's last lines beside its limit.
    tail = proc.stderr.strip().splitlines()[-len(out["checks"]):]
    for line, (name, v) in zip(tail, out["checks"].items()):
        assert line == f"{name} {v['value']} limit {v['limit']}"


def test_no_chip_exits_nonzero_without_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _broken(kind: str):
    """A mixed-progress decode program that is wrong in one way."""
    from repro.launch.serve import VortexServer

    real = VortexServer._decode_exec_vec_for

    def exec_for(self, bp, kvb):
        exe = real(self, bp, kvb)

        def call(params, cache, tok, pos):
            if kind == "state_unchanged":
                # The program consumes the cache it is given (donated), so
                # the cache handed back is a copy taken before the call.
                before = jax.tree.map(jnp.copy, cache)
            logits, new = exe(params, cache, tok, pos)
            if kind == "token_altered":
                top = (jnp.argmax(logits, -1) + 1) % self.cfg.vocab
                rows = jnp.arange(logits.shape[0])
                logits = logits.at[rows, top].set(1e4)
            elif kind == "state_unchanged":
                new = before
            elif kind == "half_batch":
                half = logits.shape[0] // 2
                logits = logits.at[half:].set(logits[:1])
            return logits, new

        return call

    return exec_for


@pytest.mark.parametrize("kind", ["token_altered", "state_unchanged",
                                  "half_batch"])
@pytest.mark.parametrize("workload", ["gpt2-chat", "gpt2-batch"])
def test_broken_decode_is_not_correct(kind, workload, monkeypatch, capsys):
    from repro.launch.serve import VortexServer

    monkeypatch.setattr(VortexServer, "_decode_exec_vec_for", _broken(kind))
    rc = run.main(["--workload", workload, "--seed", "11", "--seconds", "2",
                   "--trace", "0", "--cpu-rehearsal"])
    assert rc == 0
    out = _last_line(capsys.readouterr().out)
    assert out["correct"] is False, out["checks"]
