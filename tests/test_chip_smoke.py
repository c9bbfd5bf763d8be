"""chip_smoke.py rehearsed on the CPU at tiny size.

The script is the repo's proof of life on the chip; these tests keep
its control flow honest without one: every phase line parses, the
zero-fallback gate trips on an injected kernel fault, and without
``--allow-cpu-rehearsal`` a CPU run exits non-zero and never prints an
``"ok": true`` line."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke as smoke_mod  # noqa: E402 - sizes only; nothing runs

sys.path.pop(0)


def _run(*argv, faults=None):
    """One process per run, as the chip tool starts it (the script
    places the compile cache and runs its phases on threads of its
    own; the suite's process keeps neither)."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    if faults:
        env["SPARK_RAPIDS_TPU_FAULTS"] = faults
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=600, env=env, cwd=_ROOT,
    )
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
    return proc.returncode, lines


def test_rehearsal_prints_every_phase_and_no_ok_true():
    rc, lines = _run("--tiny", "--allow-cpu-rehearsal")
    assert rc == 0
    phases = [d.get("phase") for d in lines[:-1]]
    assert phases == [
        "device", "setup", "resident_plan", "stream", "row_conversion",
        "no_hidden_downgrade",
    ]
    by = {d["phase"]: d for d in lines[:-1]}
    assert by["device"]["platform"] == "cpu"
    for name in ("resident_plan", "stream", "row_conversion"):
        d = by[name]
        assert d["cold_s"] > 0 and d["warm_s"] > 0
        assert d.get("compiles_warm", 0) == 0
    assert by["resident_plan"]["rows"] == smoke_mod.TINY["fact"]
    assert by["setup"]["compiles"] > 0
    assert by["stream"]["batches"] == smoke_mod.STREAM_BATCHES
    launches = by["row_conversion"]["kernel_launches"]
    assert launches["row_pack"] > 0 and launches["row_unpack"] > 0
    assert not any(by["no_hidden_downgrade"]["counters"].values())
    # a rehearsal is never reported as a chip run; count is the devices
    # the run used, not the four the (virtual) host has
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] is True
    assert by["device"]["count"] == 4 and lines[-1]["device"]["count"] == 1
    assert not any(d.get("ok") is True for d in lines)


def test_without_the_flag_a_cpu_run_fails_at_the_device_phase():
    rc, lines = _run("--tiny")
    assert rc != 0
    assert [d.get("phase") for d in lines] == ["device"]
    assert not any("ok" in d for d in lines)


def test_injected_kernel_fault_trips_the_zero_fallback_gate():
    # one permanent fault at the kernel site: the registry catches it,
    # replays on the XLA path (answers stay right) and counts one
    # kernel.fallbacks — exactly what the gate exists to refuse
    rc, lines = _run(
        "--tiny", "--allow-cpu-rehearsal",
        faults="seed=3,kernel:permanent:1:1",
    )
    assert rc != 0
    gate = [d for d in lines if d.get("phase") == "no_hidden_downgrade"]
    assert gate and gate[0]["counters"]["kernel.fallbacks"] == 1
    assert not any("ok" in d for d in lines)


def test_four_chip_option_runs_only_the_mesh_plan():
    rc, lines = _run(
        "--tiny", "--chips", "4", "--allow-cpu-rehearsal"
    )
    assert rc == 0
    assert [d.get("phase") for d in lines[:-1]] == [
        "device", "mesh_plan", "no_hidden_downgrade",
    ]
    m = lines[1]
    assert m["chips"] == 4 and m["byte_identical"] is True
    # read off the served stage itself: where it packed, what it
    # exchanged with, where it gathered from
    assert len(m["packed_on_devices"]) == 4
    assert m["gathered_from_devices"] == m["packed_on_devices"]
    assert m["exchange"] == "dense_compact"  # XLA:CPU has no ragged
    assert len(m["received_rows"]) == 4 and min(m["received_rows"]) > 0
    assert lines[-1]["device"]["count"] == 4
    assert m["counters"]["plan.mesh_segments"] == 1
    assert m["counters"]["mesh.degraded"] == 0
    assert lines[-1]["ok"] is False
