"""The control: the reference computed in float8, one step below the
bfloat16 the configurations serve in, put in the program's place.  At the
smoke size of each cell the run has to come out not correct, with the
control's number above the cell's limit, where the program's, on the same
requests, lies below it."""
from __future__ import annotations

import json

import pytest

from bench import run
from bench.harness import spec


@pytest.mark.parametrize("seed", [21, 2**31 + 3, 977])
@pytest.mark.parametrize("workload", ["gpt2-chat", "gpt2-batch"])
def test_control_is_not_correct(workload, seed, capsys):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0", "--cpu-rehearsal",
                   "--control"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    diag = json.loads(lines[-2])["check"]
    result = json.loads(lines[-1])
    limit = spec.load(workload).limits["logit_gap"]["limit"]
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > limit
    assert diag["control"] is True
    assert diag["program_logit_gap"] <= limit
