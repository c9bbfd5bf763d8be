#!/usr/bin/env bash
# Mesh chaos smoke gate: a mesh-backed served stream must survive a
# seeded distributed fault plan (ISSUE 15). Phase 1 establishes the
# fault-free baseline: a mesh=8 session streams bucket-edge batches
# byte-identical to the local exact run. Phase 2 arms 10% transient
# faults at the shuffle and collective sites — lineage replay re-runs
# only the failed exchanges and every batch still comes back
# byte-identical, with nonzero shuffle.retries. Phase 3 makes every
# collective launch fail: the MeshRunner ladder walks 8 -> 4 -> 2 -> 1
# (probing each rung), raises typed Degraded at the floor, and the plan
# degrades to the single-device exact path — the tenant is SERVED, not
# shed, and the answer is still byte-identical.
#
# Artifacts gate: the metrics dump carries shuffle.retries /
# mesh.degraded / mesh.exhausted / plan.mesh_fallbacks, the daemon
# leaks ZERO resident tables, and the flight dump merges into a
# Perfetto-loadable trace showing the degradation ladder instants.
#
# Runs on the CPU backend with 8 virtual devices so it gates every
# premerge node — the fault plan is how a laptop rehearses a dying
# TPU slice.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight.json"
export SPARK_RAPIDS_TPU_RETRY_BASE_MS=1
# the lock-order detector rides the whole smoke: the ladder's
# degrade-under-lock path is exactly where an inversion would show
export SPARK_RAPIDS_TPU_LOCKCHECK=on

python3 - <<'PY'
import json

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import parallel
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.column import Table
from spark_rapids_jni_tpu.utils import config, metrics

I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)

# row-local chain: eligible for the mesh path at any device count
CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
]

config.set_flag("BUCKETS", "")


def batch(n, seed):
    rng = np.random.default_rng(n + seed)
    k = rng.integers(-500, 500, n, dtype=np.int64)
    m = (k > 0).astype(np.uint8)
    return ([I64, B8], [0, 0], [k.tobytes(), m.tobytes()],
            [None, None], n)


def norm(wire):
    t, s, d, v, n = wire
    return (
        [int(x) for x in t], [int(x) for x in s],
        [None if x is None else bytes(x) for x in d],
        [None if x is None else bytes(x) for x in v], int(n),
    )


# bucket-edge sizes: padding boundaries are where a wrong gather shows
batches = [batch(n, s) for s, n in enumerate((1023, 1024, 1025))]
want = [
    norm(rb.table_plan_wire(json.dumps(CHAIN), *b)) for b in batches
]

with serving.serve() as srv:
    # -- phase 1: fault-free mesh baseline ----------------------------
    with serving.Client(srv.port, name="mesh-base", mesh=8) as c:
        got = [norm(g) for g in c.stream(CHAIN, batches)]
    assert got == want, "fault-free mesh stream diverged"
    docs = srv.stats()["mesh"]
    assert docs and docs[0]["devices"] == 8, docs

    # -- phase 2: 10% shuffle+collective faults, replay to parity -----
    config.set_flag(
        "FAULTS", "seed=1,shuffle:transient:0.1,collective:transient:0.1"
    )
    with serving.Client(srv.port, name="mesh-chaos", mesh=8) as c:
        for _ in range(4):
            got = [norm(g) for g in c.stream(CHAIN, batches)]
            assert got == want, "mesh stream diverged under faults"
    # the shuffle site lives in the exchange wrappers: drive it direct
    mesh = parallel.make_mesh(8)
    n = 2048
    rng = np.random.default_rng(2)
    t = Table.from_pydict({
        "k": rng.integers(0, 64, n, dtype=np.int64),
        "v": rng.integers(-100, 100, n, dtype=np.int64),
    })
    for _ in range(8):
        out, occ, overflow = parallel.shuffle_table(t, ["k"], mesh)
        assert int(np.asarray(overflow).max()) <= 0
        assert int(np.asarray(occ).sum()) == n, "rows lost under faults"
    c2 = metrics.snapshot()["counters"]
    assert c2.get("faults.injected", 0) > 0, c2
    assert c2.get("shuffle.retries", 0) > 0, c2

    # -- phase 3: persistent collective failure -> ladder -> exact ----
    config.set_flag("FAULTS", "collective:transient:1")
    config.set_flag("RETRY_MAX", "0")
    with serving.Client(srv.port, name="mesh-floor", mesh=8) as c:
        got = [norm(g) for g in c.stream(CHAIN, batches)]
    assert got == want, "degraded-to-exact stream diverged"
    config.set_flag("FAULTS", "")
    config.set_flag("RETRY_MAX", "")

c3 = metrics.snapshot()["counters"]
assert c3.get("mesh.degraded", 0) >= 1, c3
assert c3.get("mesh.exhausted", 0) >= 1, c3
assert c3.get("plan.mesh_fallbacks", 0) >= 1, c3
assert c3.get("plan.mesh_segments", 0) >= 1, c3

assert rb.resident_table_count() == 0, "daemon leaked resident tables"
assert rb.leak_report() == [], rb.leak_report()

from spark_rapids_jni_tpu.utils import lockcheck

lockdoc = lockcheck.assert_clean()
assert lockdoc["acquisitions"] > 0, "lockcheck saw no acquisitions"
print(lockcheck.summary_line())

print(
    f"mesh chaos driver OK: {c3['faults.injected']} faults injected, "
    f"{c3['shuffle.retries']} exchange retries, mesh degraded "
    f"{c3['mesh.degraded']}x to the floor, "
    f"{c3['plan.mesh_fallbacks']} exact-path fallbacks, 0 leaked tables"
)
PY

# the analysis tools below import the package too — drop the dump envs
# so THEIR atexit hooks can't clobber the artifacts under test
unset SPARK_RAPIDS_TPU_FLIGHT_DUMP SPARK_RAPIDS_TPU_METRICS_DUMP \
  SPARK_RAPIDS_TPU_LOCKCHECK

test -s "$out/metrics.json"
test -s "$out/flight.json"
python3 - "$out/metrics.json" <<'PY'
import json
import sys

c = json.load(open(sys.argv[1])).get("counters", {})
assert c.get("shuffle.retries", 0) > 0, c
assert c.get("mesh.degraded", 0) >= 1, c
assert c.get("mesh.exhausted", 0) >= 1, c
assert c.get("plan.mesh_fallbacks", 0) >= 1, c
mesh_counters = {
    k: v for k, v in sorted(c.items())
    if k.split(".")[0] in ("shuffle", "mesh", "plan", "faults")
}
print("mesh chaos metrics dump OK:", mesh_counters)
PY

# the flight dump merges into a Perfetto trace that SHOWS the ladder:
# replay instants per rung, mesh.degraded per halving, mesh.exhausted
# at the floor, and the plan falling back to the exact path
python3 tools/explain.py --merge "$out/flight.json" \
  -o "$out/merged.trace.json" > "$out/merged.txt"
python3 - "$out/merged.trace.json" <<'PY'
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
assert events, "empty merged trace"
instants = [e for e in events if e.get("ph") == "i"]
names = {e["name"].split("/")[-1] for e in instants}
assert "mesh.degraded" in names, sorted(names)
assert "mesh.exhausted" in names, sorted(names)
assert "mesh.replay" in names, sorted(names)
assert "plan.mesh_fallback" in names, sorted(names)
print(
    f"mesh chaos trace OK: {len(events)} events, degradation ladder + "
    f"{sum(1 for e in instants if e['name'].endswith('mesh.degraded'))} "
    "degrade instants in the merged Perfetto timeline"
)
PY
