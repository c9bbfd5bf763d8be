#!/usr/bin/env bash
# Kernel tier smoke gate (ISSUE 20): the Pallas kernel tier
# (kernels/registry.py) must hold its whole contract end to end —
#
#   1. the plan-time static report tags exactly the kernel-eligible
#      ops (the transposes of a sort/groupby/transpose plan carry a
#      kernel tag, rendered as ~kernel:<name> markers and listed in
#      report["kernel_ops"]; sort_by/groupby/join have no registered
#      kernel and stay untagged);
#   2. a dispatch stream with SPARK_RAPIDS_TPU_KERNELS=on launches
#      kernels (nonzero kernel.launches) and stays byte-identical to
#      the same stream with KERNELS=off;
#   3. a seeded `kernel` chaos fault falls back to the exact path with
#      identical bytes, one metered kernel.fallbacks, and zero leaked
#      resident tables;
#   4. the kernel.<name> spans land on the flight ring and survive the
#      merge into a Perfetto-loadable Chrome trace.
#
# Runs on the CPU backend (interpret=True Pallas) by default so it
# gates every premerge node; set SPARK_RAPIDS_TPU_TEST_PLATFORM /
# JAX_PLATFORMS for an on-chip Mosaic run.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# Phase 1: static kernel tagging — the analyzer must tag the two
# transposes of the plan, nothing else, and render the markers
python3 - <<'PY'
from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plancheck as pc

I64 = dt.TypeId.INT64
PLAN = [
    {"op": "sort_by", "keys": [{"column": 0}]},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
    {"op": "to_rows"},
    {"op": "from_rows", "type_ids": [int(I64), int(I64)], "scales": [0, 0]},
]
rep = pc.analyze(
    PLAN, schema=[pc.ColType(I64), pc.ColType(I64)], rows=4096,
)
assert rep["ok"], rep
assert rep["kernel_ops"] == [2, 3], rep["kernel_ops"]
tags = [e["kernel"] for e in rep["ops"]]
assert tags == [None, None, "row_pack", "row_unpack"], tags
txt = pc.render_report(rep)
assert "~kernel:row_pack" in txt, txt
assert "~kernel:row_unpack" in txt, txt
print(f"static kernel tagging OK: ops {rep['kernel_ops']} -> {tags}")
PY

# Phases 2-4: dispatch parity + counters, seeded-fault fallback, and
# the flight-ring spans (dumped for the trace merge below)
python3 - "$out/flight.json" <<'PY'
import json
import sys

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, flight, metrics

config.set_flag("METRICS", "1")
config.set_flag("FLIGHT", "1")

I64 = int(dt.TypeId.INT64)
OP_PACK = json.dumps({"op": "to_rows"})
OP_UNPACK = json.dumps(
    {"op": "from_rows", "type_ids": [I64, I64], "scales": [0, 0]}
)
N = 4096

rng = np.random.default_rng(17)
k = rng.integers(-500, 500, N, dtype=np.int64)
v = rng.integers(-100, 100, N, dtype=np.int64)
wire_in = ([I64, I64], [0, 0], [k.tobytes(), v.tobytes()],
           [None, None], N)


def stream():
    t1 = rb.table_op_wire(OP_PACK, *wire_in)
    t2 = rb.table_op_wire(OP_UNPACK, *t1)
    return t1, t2


# Phase 2: ON vs OFF byte parity with nonzero launches on the ON arm
config.set_flag("KERNELS", "off")
want = stream()
metrics.reset()
config.set_flag("KERNELS", "on")
got = stream()
ctr = metrics.snapshot()["counters"]
assert got == want, "kernel tier changed bytes"
launches = int(ctr.get("kernel.launches", 0))
assert launches >= 2, ctr
assert int(ctr.get("kernel.fallbacks", 0)) == 0, ctr
print(f"kernel parity OK: {launches} launches, 0 fallbacks")

# Phase 3: a seeded kernel fault must fall back byte-identical with
# one metered fallback and zero leaked resident tables
live_before = len(rb._RESIDENT)
config.set_flag("FAULTS", "seed=7,kernel:permanent:1:1")
metrics.reset()
got_faulted = stream()
config.clear_flag("FAULTS")
ctr = metrics.snapshot()["counters"]
assert got_faulted == want, "faulted kernel run changed bytes"
assert int(ctr.get("kernel.fallbacks", 0)) == 1, ctr
assert len(rb._RESIDENT) == live_before, "leaked resident tables"
print("kernel fault fallback OK: byte-identical, 1 fallback, 0 leaks")

path = flight.dump(sys.argv[1])
assert path, "flight dump not written"
PY

# Phase 4: the kernel spans survive the merge into a Chrome trace
test -s "$out/flight.json"
python3 tools/trace2chrome.py "$out/flight.json" -o "$out/trace.json"
python3 - "$out/trace.json" <<'PY'
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
spans = [e for e in events if e["ph"] == "X"]
kernel_spans = sorted(
    {e["name"].split("/")[-1] for e in spans
     if e["name"].split("/")[-1].startswith("kernel.")}
)
assert "kernel.row_pack" in kernel_spans, kernel_spans
assert "kernel.row_unpack" in kernel_spans, kernel_spans
assert "kernel" in {e["cat"] for e in spans}, "no kernel category"
print(f"kernel trace spans OK: {kernel_spans}")
PY

echo "smoke-kernels: all gates passed"
