"""``correct`` must fail when it should: the control (the reference in
the next precision down, in the program's place) and the timed path
broken underneath. Each runs the whole harness on the CPU at the
configurations' ``smoke`` sizes, with no chip to look for."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]


def _run(workload, *extra, seed=11):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(CHECKOUT / "bench" / "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "4",
         "--trace", "0", "--smoke", "1", *extra],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return lines[0]["window"], lines[-1]


@pytest.mark.parametrize("workload", ["flat.conv"])
def test_control_is_not_correct(workload):
    window, result = _run(workload, "--control", "1")
    assert result["correct"] is False
    failed = [k for k, c in result["checks"].items()
              if c["value"] > c["limit"]]
    assert failed, result["checks"]
    # the same requests and tokens pass with the program in its place
    program = window["program_checks"]
    assert all(program[k] <= result["checks"][k]["limit"]
               for k in program), program


def test_altered_token_is_not_correct():
    _, result = _run("flat.conv", "--fault", "token")
    assert result["correct"] is False
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_answer_is_not_correct():
    _, result = _run("flat.conv", "--fault", "answer")
    assert result["correct"] is False
    assert result["checks"]["decision_errors"]["value"] > 0


def test_unchanged_state_is_not_correct():
    _, result = _run("flat.conv", "--fault", "state")
    assert result["correct"] is False
    assert result["checks"]["decision_errors"]["value"] > 0
