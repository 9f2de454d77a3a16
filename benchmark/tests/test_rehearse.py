"""A CPU rehearsal of each kind at ``T5Config.tiny()``: the control flow of a
real run, and no line a driver could read as a result."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metrics" in doc:
            out.append(doc)
    return out


@pytest.mark.parametrize("cell, seconds", [
    ("t5base-finetune", "1"), ("t5base-finetune-dp4", "1"),
    ("t5base-batchgen", "1"), ("t5large-serve", "3")])
def test_rehearsal(cell, seconds):
    out = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--rehearse",
         "--seconds", seconds, "--trace", "1", "--seed", "5"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert _result_lines(out.stdout) == []
    assert out.stdout.strip().splitlines()[-1].startswith(
        f"rehearsal of {cell}: ok")


def test_no_chip_no_result():
    """Without ``--rehearse`` this sandbox has no TPU: non-zero, nothing a
    driver could read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "t5base-batchgen", "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode != 0
    assert _result_lines(out.stdout) == []
    assert "needs 1 attached TPU chip" in out.stderr


def test_an_unknown_cell_is_refused():
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "no-such-cell", "--rehearse"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and _result_lines(out.stdout) == []
