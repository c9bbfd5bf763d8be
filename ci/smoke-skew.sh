#!/usr/bin/env bash
# Shuffle-as-a-plan-op + adaptive skew smoke gate (ISSUE 17): a seeded
# zipf stream through a plan carrying a `partition` op must run on the
# 8-device CPU mesh byte-identical to the single-device exact path;
# the adaptive skew splitter must fire on the zipf groupby (nonzero
# `shuffle.skew_splits`) and bring the planned max/mean destination
# recv ratio under SKEW_SPLIT_FACTOR; the run must leak zero resident
# tables; and `explain --drift` over the planstats store must render
# the split decision as a typed DRIFT[skew] finding.
#
# Runs on the CPU backend (forced 8-way host platform) so it gates
# every premerge node.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
export SPARK_RAPIDS_TPU_PLANSTATS_DIR="$out/planstats"
export SPARK_RAPIDS_TPU_METRICS=1

# Phase 1: partition as a plan op — mesh vs exact byte parity at the
# shard boundary sizes, with row-local chains fused on BOTH sides of
# the exchange. Phase 2: the adaptive splitter on the skewed groupby.
# Both phases run in one process so the leak check at the end covers
# the whole plane.
python3 - <<'PY'
import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Table
from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
from spark_rapids_jni_tpu.parallel import distributed_groupby, make_mesh
from spark_rapids_jni_tpu.parallel.tolerant import MeshRunner
from spark_rapids_jni_tpu.utils import config, metrics, profiler

F64 = int(dt.TypeId.FLOAT64)
PLAN = [
    {"op": "filter", "mask": 2},
    {"op": "partition", "kind": "hash", "keys": [0], "num": 16},
    {"op": "cast", "column": 0, "type_id": F64},
]


def _view(t):
    n = int(t.logical_row_count)
    cols = []
    for c in t.columns:
        data = np.asarray(c.data)
        cols.append((
            str(data.dtype), data[:n].tolist(),
            None if c.validity is None
            else np.asarray(c.validity)[:n].tolist(),
        ))
    return (n, cols)


runner = MeshRunner(8)
for n in (1023, 1024, 1025):
    rng = np.random.default_rng(n)
    k = np.minimum(rng.zipf(1.3, n), 100_000).astype(np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    m = rng.integers(0, 3, n, dtype=np.int64) > 0
    t = Table.from_pydict({"k": k, "v": v, "m": m})
    schema = "int64,int64,bool8"
    with profiler.profile_session(PLAN, label="smoke-skew", schema=schema):
        got = plan_mod.run_plan(PLAN, t, mesh_runner=runner)
    want = plan_mod.run_plan(PLAN, t)
    assert _view(got) == _view(want), f"mesh/exact divergence at n={n}"
print("partition plan parity OK at 1023/1024/1025")

# Phase 2: zipf(1.3) groupby at 200k rows — hot key concentration must
# trip the splitter, and the planned post-split recv max/mean must be
# under the factor.
config.set_flag("SKEW_SPLIT", "1")
n = 200_000
rng = np.random.default_rng(7)
k = np.minimum(rng.zipf(1.3, n), 100_000).astype(np.int64)
v = rng.integers(-100, 100, n, dtype=np.int64)
t = Table.from_pydict({"k": k, "v": v})
mesh = make_mesh(8)
aggs = [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")]
GROUPBY_PLAN = [{
    "op": "groupby", "by": [0],
    "aggs": [{"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"}],
}]
with profiler.profile_session(
    GROUPBY_PLAN, label="smoke-skew-groupby", schema="int64,int64",
):
    agg, ngroups, overflow = distributed_groupby(t, ["k"], aggs, mesh)
assert int(np.asarray(overflow).max()) <= 0
total_groups = int(np.asarray(ngroups).sum())
assert total_groups == len(np.unique(k)), total_groups

snap = metrics.snapshot()
splits = int(snap["counters"].get("shuffle.skew_splits", 0))
assert splits > 0, f"adaptive splitter never fired: {snap['counters']}"
factor = float(config.get_flag("SKEW_SPLIT_FACTOR"))
ratio_g = snap["gauges"].get("shuffle.skew_post_ratio_x100")
assert ratio_g is not None, snap["gauges"]
post_ratio = float(ratio_g["value"]) / 100.0
assert post_ratio < factor, (
    f"post-split recv ratio {post_ratio:.2f}x >= factor {factor}"
)
print(f"skew split OK: splits={splits}, post max/mean={post_ratio:.2f}x "
      f"(factor {factor})")

# zero leaked resident tables across both phases
leaked = rb.resident_table_count()
assert leaked == 0, f"{leaked} resident table(s) leaked"
print("leak check OK: 0 resident tables")
PY

# Phase 3: the split decision must surface as a typed skew finding in
# the drift report, and the exchange counters must render.
python3 tools/explain.py --drift "$out/planstats" > "$out/drift.txt"
grep -q "DRIFT\[skew\]" "$out/drift.txt"
grep -q "shuffle.skew_splits" "$out/drift.txt"

python3 - "$out/planstats" <<'PY'
import sys

from spark_rapids_jni_tpu.utils import planstats

records = planstats.load(sys.argv[1])
finds = [f for r in records for f in (r.get("drift") or [])]
kinds = {f["type"] for f in finds}
assert "skew" in kinds, (kinds, finds)
skew = [f for f in finds if f["type"] == "skew"]
assert any("split" in (f.get("detail") or "") for f in skew), skew
print(f"drift findings OK: {sorted(kinds)}, {len(skew)} skew finding(s)")
PY

echo "smoke-skew OK"
