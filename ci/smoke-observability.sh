#!/usr/bin/env bash
# Observability smoke gate: a tiny bench config with tracing + the
# flight recorder enabled must leave BOTH telemetry artifacts behind
# (metrics snapshot + flight dump), and the flight dump must convert
# into a Perfetto-loadable Chrome trace with spans from the dispatch,
# wire-serde and bucketed subsystems plus at least one counter track.
#
# This is the crash-postmortem contract of ISSUE 3: if this gate
# passes, a SIGTERM'd production run leaves a timeline you can open at
# https://ui.perfetto.dev instead of a bare "device unreachable".
#
# Runs on the CPU backend by default so it gates every premerge node;
# set SPARK_RAPIDS_TPU_TEST_PLATFORM/JAX_PLATFORMS for an on-chip run.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight.json"
# shrink the resident-chain config (filter -> sort -> groupby through
# wire AND resident handles: dispatch + serde + bucketed spans,
# resident.live counter samples) to smoke scale
export SRT_BENCH_RESIDENT_ROWS=200000

python3 bench.py --one resident

# both artifacts exist and parse as JSON
test -s "$out/metrics.json"
test -s "$out/flight.json"
python3 -m json.tool "$out/metrics.json" > /dev/null
python3 -m json.tool "$out/flight.json" > /dev/null

# the flight dump converts into a schema-valid Chrome trace covering
# >= 3 subsystems + >= 1 counter track
python3 tools/trace2chrome.py "$out/flight.json" -o "$out/trace.json"
python3 - "$out/trace.json" <<'PY'
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
assert events, "empty trace"
for e in events:
    assert "ph" in e and "pid" in e and "tid" in e, e
spans = [e for e in events if e["ph"] == "X"]
cats = {e["cat"] for e in spans}
assert "dispatch" in cats, cats
assert "wire" in cats, cats
assert "bucketed" in cats, cats
counters = {e["name"] for e in events if e["ph"] == "C"}
assert counters, "no counter tracks"
print(
    f"observability smoke OK: {len(spans)} spans, "
    f"subsystems={sorted(cats)}, counters={sorted(counters)}"
)
PY

# plan-fusion observability (ISSUE 4): a fused plan run under
# METRICS+FLIGHT must land the plan.* counters in the metrics dump and
# its per-segment spans must convert into the Chrome trace
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics_plan.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight_plan.json"
export SRT_BENCH_PLAN_ROWS=4000

python3 bench.py --one fused_plan

test -s "$out/metrics_plan.json"
test -s "$out/flight_plan.json"
python3 -m json.tool "$out/metrics_plan.json" > /dev/null
python3 tools/trace2chrome.py "$out/flight_plan.json" -o "$out/trace_plan.json"
python3 - "$out/metrics_plan.json" "$out/trace_plan.json" <<'PY'
import json
import sys

m = json.load(open(sys.argv[1]))
c = m.get("counters", {})
assert c.get("plan.segments", 0) > 0, c
assert c.get("plan.fused_ops", 0) > 0, c
trace = json.load(open(sys.argv[2]))
events = trace["traceEvents"]
assert events, "empty plan trace"
spans = [e for e in events if e["ph"] == "X"]
seg = [e for e in spans
       if e["name"].split("/")[-1].startswith("plan.segment.")]
assert seg, sorted({e["name"] for e in spans})
assert "plan" in {e["cat"] for e in spans}
# the completion clock's lane: one device interval a launch
assert [e for e in spans if e["name"].startswith("device.srt_")], sorted(
    {e["name"] for e in spans})
print(
    "plan fusion smoke OK:",
    {k: v for k, v in sorted(c.items()) if k.startswith("plan.")},
    f"+ {len(seg)} plan.segment.<sig> spans in trace",
)
PY

# pipelined dispatch observability (ISSUE 5): a pipelined stream run
# under METRICS+FLIGHT must land the pipeline.* counters in the metrics
# dump, and the converted Chrome trace must show the decode/encode
# STAGE spans on WORKER thread ids distinct from the compute thread —
# the visual proof of host/device overlap the tentpole promises
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics_pipe.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight_pipe.json"
export SRT_BENCH_STREAM_ROWS=20000
export SRT_BENCH_PIPELINE_DEPTH=2

python3 bench.py --one pipelined_stream

test -s "$out/metrics_pipe.json"
test -s "$out/flight_pipe.json"
python3 -m json.tool "$out/metrics_pipe.json" > /dev/null
python3 tools/trace2chrome.py "$out/flight_pipe.json" -o "$out/trace_pipe.json"
python3 - "$out/metrics_pipe.json" "$out/trace_pipe.json" <<'PY'
import json
import sys

m = json.load(open(sys.argv[1]))
c = m.get("counters", {})
assert c.get("pipeline.enqueued", 0) > 0, c
assert c.get("pipeline.completed", 0) > 0, c
assert "pipeline.overlap_ms" in m.get("histograms", {}), sorted(
    m.get("histograms", {})
)
assert m.get("bytes", {}).get("hbm.donated_bytes", 0) > 0, m.get("bytes")
trace = json.load(open(sys.argv[2]))
events = trace["traceEvents"]
assert events, "empty pipeline trace"
spans = [e for e in events if e["ph"] == "X"]
stage = [
    e for e in spans
    if e["name"].split("/")[-1] in ("pipeline.decode", "pipeline.encode")
]
assert stage, sorted({e["name"] for e in spans})
stage_tids = {e["tid"] for e in stage}
compute_tids = {
    e["tid"] for e in spans
    if e["name"].split("/")[-1].startswith("plan.segment.")
}
worker_tids = stage_tids - compute_tids
assert worker_tids, (
    f"stage spans only on compute tids {compute_tids} — no worker-side "
    "stage execution in the trace"
)
print(
    "pipelined dispatch smoke OK:",
    {k: v for k, v in sorted(c.items()) if k.startswith("pipeline.")},
    f"+ {len(stage)} stage spans on {len(worker_tids)} worker tid(s)",
)
PY

# query profiler + EXPLAIN ANALYZE (ISSUE 8): two fused-plan runs under
# PROFILE=on (distinct processes -> distinct pids) must each leave a
# flight dump carrying profile sessions; explain.py must render a
# per-op report naming EVERY plan op with a nonzero fused count and a
# valid --json form, and --merge must combine both dumps into one
# report + one Perfetto trace with two process tracks
export SPARK_RAPIDS_TPU_PROFILE=on
export SRT_BENCH_PLAN_ROWS=4000

export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics_prof0.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight_prof0.json"
export SPARK_RAPIDS_TPU_PROFILE_DUMP="$out/profile0.json"
python3 bench.py --one fused_plan > "$out/bench_prof0.json"
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics_prof1.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight_prof1.json"
export SPARK_RAPIDS_TPU_PROFILE_DUMP="$out/profile1.json"
python3 bench.py --one fused_plan > "$out/bench_prof1.json"
# the analysis tools below import the package too — drop the dump envs
# so THEIR atexit hooks can't clobber the artifacts under test
unset SPARK_RAPIDS_TPU_PROFILE SPARK_RAPIDS_TPU_PROFILE_DUMP \
  SPARK_RAPIDS_TPU_FLIGHT_DUMP SPARK_RAPIDS_TPU_METRICS_DUMP

test -s "$out/profile0.json"
test -s "$out/profile1.json"
python3 -m json.tool "$out/profile0.json" > /dev/null

# the report names every plan op, shows fused segments, and the
# machine form is valid JSON with the split-sums invariant
python3 tools/explain.py "$out/profile0.json" > "$out/explain.txt"
grep -q "EXPLAIN ANALYZE" "$out/explain.txt"
for op in filter cast sort_by groupby; do
  grep -q "$op" "$out/explain.txt"
done
grep -q "fused)" "$out/explain.txt"
python3 tools/explain.py --json "$out/profile0.json" > "$out/explain.json"
python3 - "$out/explain.json" <<'PY'
import json
import sys

sessions = json.load(open(sys.argv[1]))
assert sessions, "no sessions in --json output"
fused = 0
for s in sessions:
    for seg in s["segments"]:
        fused += seg["kind"] == "fused"
        total = (
            seg["compile_s"] + seg["execute_s"] + seg["serde_s"]
            + seg["stall_s"]
        )
        assert abs(total - seg["wall_s"]) < 1e-6, seg
assert fused > 0, "no fused segments profiled"
print(f"explain smoke OK: {len(sessions)} sessions, {fused} fused segments")
PY

# multi-process merge: both flight dumps (which carry the sessions and
# the pid/host/session_id stamps) -> one report + one Perfetto trace
# with two distinct process tracks
python3 tools/explain.py --merge \
  "$out/flight_prof0.json" "$out/flight_prof1.json" \
  -o "$out/merged.trace.json" > "$out/merged.txt"
grep -q "MERGED PROFILE  2 process(es)" "$out/merged.txt"
python3 - "$out/merged.trace.json" <<'PY'
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
assert events, "empty merged trace"
pids = {e["pid"] for e in events}
assert len(pids) >= 2, f"merge kept only {pids}"
names = [e for e in events if e["name"] == "process_name"]
assert len({e["pid"] for e in names}) >= 2, names
print(
    f"profile merge smoke OK: {len(events)} events across "
    f"{len(pids)} process tracks"
)
PY

# multi-tenant serving daemon (ISSUE 9): the serving bench starts a
# daemon and streams TPC-DS-shaped plan mixes through concurrent tenant
# sessions. Gates: (i) the session-stamped profile dump merges into an
# EXPLAIN report naming >= 2 served sessions (serve:<name> labels),
# (ii) the served phase warm-hits the cross-session compile cache
# (nonzero compile_cache.hit with ~0 misses), (iii) the daemon shuts
# down clean with ZERO leaked resident tables
export SPARK_RAPIDS_TPU_PROFILE=on
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics_serve.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight_serve.json"
export SPARK_RAPIDS_TPU_PROFILE_DUMP="$out/profile_serve.json"
export SRT_BENCH_SERVE_ROWS=8000

python3 bench.py --one serving_multiquery > "$out/bench_serve.json"
unset SPARK_RAPIDS_TPU_PROFILE SPARK_RAPIDS_TPU_PROFILE_DUMP \
  SPARK_RAPIDS_TPU_FLIGHT_DUMP SPARK_RAPIDS_TPU_METRICS_DUMP

test -s "$out/profile_serve.json"
python3 -m json.tool "$out/profile_serve.json" > /dev/null

# gate (ii) + (iii): the structured "serving" block from the bench
# entry — cross-session hits nonzero, misses ~0, zero leaked tables —
# and analyze_bench.py renders the block from the raw entry line
python3 - "$out/bench_serve.json" <<'PY'
import json
import sys

entries = []
for line in open(sys.argv[1]):
    line = line.strip()
    if line.startswith("BENCH_ENTRY "):
        entries.append(json.loads(line[len("BENCH_ENTRY "):]))
blocks = [e["serving"] for e in entries if isinstance(e.get("serving"), dict)]
assert blocks, f"no serving block in {len(entries)} entries"
s = blocks[0]
assert s["sessions"] >= 2, s
assert s["cross_session_hits"] > 0, s
assert s["cross_session_misses"] == 0, s
assert s["leaked_tables"] == 0, s
assert s["requests"] > 0, s
print(
    f"serving bench smoke OK: {s['sessions']} sessions, "
    f"{s['cross_session_hits']} cross-session cache hits, "
    f"shed={s['shed']}, wait p95 {s['queue_wait_ms_p95']} ms, "
    f"0 leaked tables"
)
PY

# gate (i): the profile dump is session-stamped — the EXPLAIN report
# and the flight-dump merge both name >= 2 distinct served sessions
python3 tools/explain.py "$out/profile_serve.json" > "$out/explain_serve.txt"
python3 tools/explain.py --merge "$out/flight_serve.json" \
  -o "$out/merged_serve.trace.json" > "$out/merged_serve.txt"
python3 - "$out/explain_serve.txt" "$out/merged_serve.txt" <<'PY'
import re
import sys

for path in sys.argv[1:3]:
    text = open(path).read()
    served = set(re.findall(r"serve:[\w.-]+", text))
    assert len(served) >= 2, (path, sorted(served))
print(f"serving session stamps OK: {sorted(served)}")
PY
