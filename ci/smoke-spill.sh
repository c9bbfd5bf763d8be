#!/usr/bin/env bash
# Spill smoke gate: a served query stream whose device working set is
# ~2x the (shrunk) HBM budget must DEGRADE, not die (ISSUE 11). Cold
# resident tables are demoted host->disk under pressure while the
# stream keeps answering BYTE-IDENTICAL batches — zero OverBudget /
# Busy sheds — then every spilled table re-promotes on re-access and
# round-trips exactly.
#
# Artifacts gate: nonzero spill.bytes_out AND spill.bytes_in (the
# stream really evicted and really repaged), disk-tier .npz files
# exist while cold and are GONE afterwards, the daemon leaks zero
# resident tables, and the flight dump merges into a Perfetto trace
# carrying the eviction/repage instants.
#
# Runs on the CPU backend so it gates every premerge node — the shrunk
# budget is how a laptop rehearses HBM pressure.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight.json"
export SPARK_RAPIDS_TPU_SPILL=on
export SPARK_RAPIDS_TPU_SPILL_DIR="$out/spill"

python3 - "$out/spill" <<'PY'
import glob
import json
import sys

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import pipeline
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import config, hbm, metrics, spill

spill_dir = sys.argv[1]
I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)

CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
    {"op": "sort_by", "keys": [{"column": 0}]},
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


batches = [batch(4096, s) for s in range(6)]
want = [
    norm(rb.table_plan_wire(json.dumps(CHAIN), *b)) for b in batches
]

# size the COLD set from one probe table, then shrink the budget to
# HALF the working set BEFORE uploading it: each upload past the line
# evicts the coldest predecessor (note_put is the pressure point).
# Host tier takes one table's worth, the rest demotes to disk — all
# three tiers exercised.
probe = rb.table_upload_wire(*batch(1 << 15, 99))
one_table = hbm.table_bytes(rb._RESIDENT[probe])
rb.table_free(probe)
working_set = 12 * one_table
gib = float(1 << 30)
shrunk_gb = (working_set / 2) / (1.0 - hbm.RESERVE_FRACTION) / gib
config.set_flag("HBM_BUDGET_GB", shrunk_gb)
config.set_flag("HOST_SPILL_BUDGET_GB", one_table / gib)

cold_wires = [batch(1 << 15, 100 + s) for s in range(12)]
cold_ids = [rb.table_upload_wire(*w) for w in cold_wires]

# -- phase 1: served stream under pressure — degrade, don't die -------
with serving.serve() as srv:
    with serving.Client(srv.port, name="pressure") as c:
        got = [norm(g) for g in c.stream(CHAIN, batches)]
    assert got == want, "served results diverged under HBM pressure"
    doc = srv.stats()
    assert doc["spill"]["enabled"], doc["spill"]
stats = spill.stats_doc()
assert stats["host_bytes"] + stats["disk_bytes"] > 0, stats
assert stats["disk_bytes"] > 0, stats
pipeline.drain_io()  # demotion writes ride the async IO lane
assert glob.glob(spill_dir + "/*.npz"), "disk tier left no files"

c = metrics.snapshot()
assert c["counters"].get("spill.evictions", 0) > 0, c["counters"]
assert c["counters"].get("spill.demotions", 0) > 0, c["counters"]
assert c["bytes"].get("spill.bytes_out", 0) > 0, c["bytes"]
# graceful degradation means ZERO sheds for a host+disk-fitting load
assert c["counters"].get("serving.over_budget", 0) == 0, c["counters"]
assert c["counters"].get("serving.shed", 0) == 0, c["counters"]

# -- phase 2: re-access re-promotes every cold table byte-identical ---
for w, tid in zip(cold_wires, cold_ids):
    assert norm(rb.table_download_wire(tid)) == norm(w), (
        "spilled table diverged after repage"
    )
c = metrics.snapshot()
assert c["counters"].get("spill.repages", 0) > 0, c["counters"]
assert c["bytes"].get("spill.bytes_in", 0) > 0, c["bytes"]

for tid in cold_ids:
    rb.table_free(tid)
assert rb.resident_table_count() == 0, "daemon leaked resident tables"
assert rb.leak_report() == [], rb.leak_report()
assert spill.spill_file_count() == 0, "spill backing leaked"
assert glob.glob(spill_dir + "/*.npz") == [], "leftover spill files"

c = metrics.snapshot()["counters"]
print(
    f"spill driver OK: working set {working_set} B over a "
    f"{int(shrunk_gb * gib)} B budget, {c['spill.evictions']} "
    f"evictions / {c['spill.demotions']} demotions / "
    f"{c['spill.repages']} repages, byte-identical stream, 0 sheds, "
    "0 leaked tables, 0 leftover files"
)
PY

# the analysis tools below import the package too — drop the dump envs
# so THEIR atexit hooks can't clobber the artifacts under test
unset SPARK_RAPIDS_TPU_FLIGHT_DUMP SPARK_RAPIDS_TPU_METRICS_DUMP \
  SPARK_RAPIDS_TPU_SPILL SPARK_RAPIDS_TPU_SPILL_DIR

# both artifacts exist, parse, and the metrics dump carries the spill
# counters the driver asserted in-process
test -s "$out/metrics.json"
test -s "$out/flight.json"
python3 - "$out/metrics.json" <<'PY'
import json
import sys

m = json.load(open(sys.argv[1]))
c, b = m.get("counters", {}), m.get("bytes", {})
assert c.get("spill.evictions", 0) > 0, c
assert c.get("spill.repages", 0) > 0, c
assert b.get("spill.bytes_out", 0) > 0, b
assert b.get("spill.bytes_in", 0) > 0, b
spill_counters = {
    k: v for k, v in sorted({**c, **b}.items())
    if k.startswith("spill.")
}
print("spill metrics dump OK:", spill_counters)
PY

# the flight dump merges into a Perfetto trace showing the eviction /
# repage instants — the postmortem view of a memory-pressured daemon
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
assert "spill.out" in names, sorted(names)
assert "spill.in" in names, sorted(names)
print(
    f"spill trace OK: {len(events)} events, "
    f"{sum(1 for e in instants if e['name'].endswith('spill.out'))} "
    "eviction instants in the merged Perfetto timeline"
)
PY
