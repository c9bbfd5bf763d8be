#!/usr/bin/env bash
# Trace-context smoke gate: one serving request over the 2-device mesh
# with tracing on must leave per-process flight dumps that
# tools/tracequery.py merges into a SINGLE trace — client.rpc from the
# client process; admission, queue-wait, compile, per-segment execute
# and mesh exchange spans from the daemon process — all sharing the
# request's W3C-style trace id (ISSUE 18).
#
# Chaos half: a second client is kill -9'd mid-stream. Its flight dump
# never lands (SIGKILL skips atexit — that dump is the casualty), the
# daemon must keep serving, and tracequery must merge the SURVIVING
# dumps into the complete server -> session -> mesh trace.
#
# Live plane: the `trace` serving command must return the tail-sampled
# slow-request log (entries carrying the trace id + span detail) and a
# non-empty Prometheus text exposition of the metrics snapshot.
#
# Runs on the CPU backend with 2 virtual devices so it gates every
# premerge node.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=2}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_METRICS=1

# -- daemon process: its own flight dump ------------------------------
SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/daemon-flight.json" \
python3 - "$out/port" "$out/stop" <<'PY' &
import os
import sys
import time

from spark_rapids_jni_tpu import serving

port_path, stop_path = sys.argv[1], sys.argv[2]
srv = serving.Server(workers=2)
srv.start()
with open(port_path + ".tmp", "w") as f:
    f.write(str(srv.port))
os.rename(port_path + ".tmp", port_path)  # atomic: readers never race
for _ in range(1200):
    if os.path.exists(stop_path):
        break
    time.sleep(0.1)
srv.stop()
PY
daemon=$!

for _ in $(seq 300); do
  [ -f "$out/port" ] && break
  sleep 0.1
done
test -f "$out/port"
port="$(cat "$out/port")"

# -- victim client: killed -9 mid-stream over the mesh ----------------
SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/victim-flight.json" \
python3 - "$port" "$out/victim-ready" <<'PY' &
import sys
import time

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import serving

port, ready_path = int(sys.argv[1]), sys.argv[2]
I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
]


def batch(n, seed):
    rng = np.random.default_rng(n + seed)
    k = rng.integers(-500, 500, n, dtype=np.int64)
    m = (k > 0).astype(np.uint8)
    return ([I64, int(dt.TypeId.BOOL8)], [0, 0],
            [k.tobytes(), m.tobytes()], [None, None], n)


c = serving.Client(port, name="victim", mesh=2).connect()
batches = [batch(4096, s) for s in range(4)]
c.stream(CHAIN, batches)
open(ready_path, "w").close()
while True:  # the shell kill -9s us mid-stream
    c.stream(CHAIN, batches)
    time.sleep(0.01)
PY
victim=$!

for _ in $(seq 300); do
  [ -f "$out/victim-ready" ] && break
  sleep 0.1
done
test -f "$out/victim-ready"
kill -9 "$victim"
wait "$victim" || true

# SIGKILL skips atexit: the victim's dump is the one that does NOT
# survive — tracequery must work from the remaining two
test ! -s "$out/victim-flight.json"

# -- clean client: ONE traced request over the mesh + the live plane --
SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/client-flight.json" \
python3 - "$port" "$out/trace_id" <<'PY'
import json
import sys

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import tracing

port, tid_path = int(sys.argv[1]), sys.argv[2]
I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)
# two plans under ONE trace: the row-local chain runs sharded over the
# mesh (mesh.stage / plan.mesh exchange spans); the sort chain declines
# the mesh and runs exact, paying a fresh cached_jit compile
# (compile.jit) with per-segment execute spans (plan.segment.<sig>)
MESH_CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
]
SORT_CHAIN = MESH_CHAIN + [{"op": "sort_by", "keys": [{"column": 0}]}]


def batch(n, seed):
    rng = np.random.default_rng(n + seed)
    k = rng.integers(-500, 500, n, dtype=np.int64)
    m = (k > 0).astype(np.uint8)
    return ([I64, B8], [0, 0], [k.tobytes(), m.tobytes()],
            [None, None], n)


c = serving.Client(port, name="traced", mesh=2).connect()
# the daemon survived the victim's SIGKILL and still serves
ctx = tracing.new_context()  # the one mint — this id spans the fleet
with tracing.activate(ctx):
    got = c.stream(MESH_CHAIN, [batch(2048, 7), batch(2049, 8)])
    got2 = c.stream(SORT_CHAIN, [batch(1536, 9)])
assert len(got) == 2 and len(got2) == 1, (len(got), len(got2))

# live introspection plane: slow-request log + Prometheus exposition
doc = c.trace()
assert set(doc) >= {"slow_requests", "prometheus", "slo_ms", "topk"}, doc
labels = {r["label"] for r in doc["slow_requests"]}
assert any("stream" in lbl for lbl in labels), labels
traced = [r for r in doc["slow_requests"]
          if r.get("trace_id") == ctx.trace_id]
assert traced, (ctx.trace_id, doc["slow_requests"])
prom = doc["prometheus"]
assert "# TYPE" in prom and "srt_" in prom, prom[:200]
c.close()

with open(tid_path, "w") as f:
    f.write(ctx.trace_id)
print("traced request OK:", ctx.trace_id)
PY

tid="$(cat "$out/trace_id")"

# -- stop the daemon: its atexit flight dump lands --------------------
touch "$out/stop"
wait "$daemon"
test -s "$out/daemon-flight.json"
test -s "$out/client-flight.json"

# the analysis tool below imports the package too — drop the dump envs
# so ITS atexit hooks can't clobber the artifacts under test
unset SPARK_RAPIDS_TPU_FLIGHT_DUMP

# -- merge the surviving dumps: ONE trace, two processes --------------
python3 tools/tracequery.py --list \
  "$out/daemon-flight.json" "$out/client-flight.json"
python3 tools/tracequery.py --trace "$tid" \
  "$out/daemon-flight.json" "$out/client-flight.json"
python3 tools/tracequery.py --trace "$tid" --json \
  "$out/daemon-flight.json" "$out/client-flight.json" \
  > "$out/spans.jsonl"
python3 tools/tracequery.py --trace "$tid" --chrome "$out/req.json" \
  "$out/daemon-flight.json" "$out/client-flight.json"

python3 - "$out/spans.jsonl" "$tid" "$out/req.json" <<'PY'
import json
import sys

recs = [json.loads(line) for line in open(sys.argv[1])]
tid = sys.argv[2]
assert recs, "tracequery merged zero spans for the traced request"
procs = {r["proc"] for r in recs}
assert len(procs) >= 2, f"trace spans only {procs} — expected >= 2 processes"
names = {r["name"].split("/")[-1] for r in recs}
# server -> session -> mesh, across the process boundary:
for want in ("client.rpc", "serving.admission", "serving.queue_wait",
             "serving.stream", "mesh.stage", "plan.mesh"):
    assert want in names, f"{want!r} missing from merged trace: {sorted(names)}"
# compile + per-segment execute spans ride the same trace
assert any(n.startswith("compile.") for n in names), sorted(names)
assert any(n.startswith("plan.segment.") for n in names) or "plan" in names, \
    sorted(names)

chrome = json.load(open(sys.argv[3]))
spans = [e for e in chrome["traceEvents"] if e.get("ph") == "X"]
pids = {e["pid"] for e in spans}
assert spans and len(pids) >= 2, (len(spans), pids)
print(
    f"trace smoke OK: trace {tid[:12]} merged {len(recs)} spans from "
    f"{len(procs)} processes ({len(spans)} Chrome spans, "
    f"{len(pids)} process tracks)"
)
PY
