"""Every cell end to end on the CPU at tiny sizes: the rehearsal prints no
metric, says ``cpu``, the check passes, and the float32 control is refused.
Without ``--rehearse`` a run that finds no TPU exits non-zero with no result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = json.load(f)["workloads"]


def run_cell(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell['chips']}"
    env["BENCH_RUN"] = "ignored"
    p = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", cell["name"],
         "--seed", "2147483659", "--seconds", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return p, [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_rehearses_on_the_cpu(cell):
    p, lines = run_cell(cell, "--rehearse", "--control", "1", "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == cell["chips"]
    (control,) = [x for x in lines if "control" in x]
    assert control["control_correct"] is False


def test_without_a_tpu_nothing_is_reported():
    p, lines = run_cell(CELLS[0])
    assert p.returncode != 0
    assert not any("correct" in x for x in lines)
