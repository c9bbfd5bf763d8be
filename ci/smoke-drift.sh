#!/usr/bin/env bash
# Plan-statistics + drift smoke gate (ISSUE 16): every run_plan
# execution under a configured stats dir must append one CRC-framed
# record carrying per-segment observations (rows in/out, bytes, wall
# time, HBM proxy) next to the embedded plan-time prediction; a
# seeded cardinality skew against the accumulated history must raise
# a typed drift finding at append time; and `explain --drift` must
# render the store as per-segment predicted-vs-observed percentiles
# in both human and --json form.
#
# Runs on the CPU backend by default so it gates every premerge node;
# set SPARK_RAPIDS_TPU_TEST_PLATFORM/JAX_PLATFORMS for an on-chip run.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_PLANSTATS_DIR="$out/planstats"

# Phase 1: the same wire plan twice (distinct data seeds). The stats
# hook rides profiler._SessionScope, so the PLANSTATS_DIR flag alone —
# no PROFILE — must be enough to land records.
python3 - <<'PY'
import json

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)
F64 = int(dt.TypeId.FLOAT64)
PLAN = json.dumps([
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
])
N = 600

for seed in (0, 1):
    rng = np.random.default_rng(seed)
    k = rng.integers(-50, 50, N, dtype=np.int64)
    mask = (k > 0).astype(np.uint8)
    rb.table_plan_wire(
        PLAN, [I64, B8], [0, 0], [k.tobytes(), mask.tobytes()],
        [None, None], N,
    )
PY

# one record per execution, each with per-segment observations and the
# embedded static prediction
python3 - "$out/planstats" <<'PY'
import sys

from spark_rapids_jni_tpu.utils import planstats

records = planstats.load(sys.argv[1])
assert len(records) == 2, f"expected 2 records, got {len(records)}"
for r in records:
    assert r["segments"], r
    for s in r["segments"]:
        assert s["calls"] > 0, s
        assert s["rows_in"] > 0, s
        assert s["rows_out"] > 0, s
        assert s["out_bytes"] > 0, s
        assert s["wall_s"] >= 0.0, s
    assert r["pred"]["segments"], r
    assert r["schema"] == "INT64,BOOL8", r
    assert r["bucket"] is not None, r
print(f"planstats store OK: {len(records)} records, "
      f"{len(records[0]['segments'])} segment(s) each")
PY

# Phase 2: seeded cardinality skew. History now holds two runs with
# ~half the rows surviving the filter; an all-pass mask doubles the
# observed rows_out, which must clear the (lowered) drift factor and
# land a typed finding on the record itself.
SPARK_RAPIDS_TPU_DRIFT_ROWS_FACTOR=1.5 python3 - <<'PY'
import json

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)
F64 = int(dt.TypeId.FLOAT64)
PLAN = json.dumps([
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
])
N = 600

rng = np.random.default_rng(7)
k = rng.integers(1, 50, N, dtype=np.int64)  # all positive: mask all-true
mask = (k > 0).astype(np.uint8)
rb.table_plan_wire(
    PLAN, [I64, B8], [0, 0], [k.tobytes(), mask.tobytes()],
    [None, None], N,
)
PY

python3 - "$out/planstats" <<'PY'
import sys

from spark_rapids_jni_tpu.utils import planstats

records = planstats.load(sys.argv[1])
assert len(records) == 3, f"expected 3 records, got {len(records)}"
finds = records[-1].get("drift") or []
kinds = {f["type"] for f in finds}
assert "cardinality" in kinds, (kinds, finds)
card = [f for f in finds if f["type"] == "cardinality"][0]
assert card["segment"] is not None, card
print(f"drift finding OK: {sorted(kinds)} on segment {card['segment']}")
PY

# Phase 3: explain --drift renders the store — per-segment predicted
# bound next to observed p50/p95/max, plus the typed finding — and the
# --json form carries the full report
python3 tools/explain.py --drift "$out/planstats" > "$out/drift.txt"
grep -q "PLAN DRIFT" "$out/drift.txt"
grep -q "rows_out p50/p95/max" "$out/drift.txt"
grep -q "hbm p50/p95/max" "$out/drift.txt"
grep -q "wall p50/p95/max" "$out/drift.txt"
grep -q "pred bound" "$out/drift.txt"
grep -q "DRIFT\[cardinality\]" "$out/drift.txt"

python3 tools/explain.py --drift --json "$out/planstats" > "$out/drift.json"
python3 - "$out/drift.json" <<'PY'
import json
import sys

report = json.load(open(sys.argv[1]))
assert report["records"] == 3, report["records"]
groups = report["groups"]
assert len(groups) == 1, [g["fp"] for g in groups]
g = groups[0]
assert g["runs"] == 3, g["runs"]
assert g["schema"] == "INT64,BOOL8", g
for s in g["segments"]:
    assert s["rows_out"]["n"] == 3, s
    assert s["wall_s"]["n"] == 3, s
    assert s["pred"] is not None, s
kinds = {f["type"] for f in g["findings"]}
assert "cardinality" in kinds, kinds
print(
    f"explain --drift OK: {g['runs']} runs, "
    f"{len(g['segments'])} segment(s), findings={sorted(kinds)}"
)
PY

echo "smoke-drift OK"
