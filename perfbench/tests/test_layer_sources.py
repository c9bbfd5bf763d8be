"""Every ``timer_mean_ms`` / ``stats_percentile`` metric names a timer or
a ``stats`` field that a rehearsal of each cell that lists it really
produced: a span renamed in the program fails here instead of leaving
``null`` in the ledger. The rehearsal prints no metric, so the cell is
served through ``run.serve_and_measure`` and read with the readers, the
way ``run.main`` does on a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = json.load(f)["workloads"]

KINDS = ("timer_mean_ms", "stats_percentile")

SERVE_AND_READ = """
import json, os, sys
import jax
from perfbench import readers, run, script
from spark_rapids_jni_tpu.utils import config as program_config

name = sys.argv[1]
args = run.parse_args(["--workload", name, "--seed", "2147483659",
                       "--seconds", "1", "--rehearse"])
cell, config, traffic, e2e, layer = run.load_cell(name)
program_config.set_flag("METRICS", True)
program_config.set_flag("KERNELS", "on")
os.makedirs(run.OUT_DIR, exist_ok=True)
data = script.Data(config, traffic, args.seed, True)
watched = (list(traffic["zero_counters"])
           + list(traffic.get("expect_counters", {})) + ["compile_cache.miss"])
m = run.serve_and_measure(args, traffic, data,
                          jax.devices()[:cell["chips"]], watched)
ctx = dict(m, requests=len(m["records"]), peaks=None, config=config,
           traffic=traffic, rows_in=script.rows_in(traffic, data))
print(json.dumps({"requests": ctx["requests"], "read": {
    s["name"]: readers.read(s, ctx) for s in layer
    if s["reader"] in %r}}))
""" % (KINDS,)


def listed(cell):
    """The cell's per-layer metrics of the two kinds, by name."""
    sys.path.insert(0, ROOT)
    from perfbench import run

    return sorted(s["name"] for s in run.load_cell(cell["name"])[4]
                  if s["reader"] in KINDS)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_named_timers_and_fields_are_produced(cell):
    want = listed(cell)
    assert want, "every cell lists a span- or stats-read metric"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell['chips']}"
    p = subprocess.run(
        [sys.executable, "-c", SERVE_AND_READ, cell["name"]],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert got["requests"] >= 1
    assert sorted(got["read"]) == want
    missing = [n for n, v in got["read"].items() if v is None]
    assert not missing, missing
    # a span that was open took some time
    assert all(v > 0 for n, v in got["read"].items()
               if n.endswith(("_ms", "_ms.convert", "_ms.exchange"))
               and not n.startswith("queue_wait"))
