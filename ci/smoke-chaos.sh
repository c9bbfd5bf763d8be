#!/usr/bin/env bash
# Chaos smoke gate: a served query stream must survive a seeded fault
# plan (ISSUE 10). Phase 1 streams batches through the serving daemon
# with 10% transient faults injected at the dispatch and serde sites —
# every batch must come back BYTE-IDENTICAL to the local run and the
# retry counters must be nonzero. Phase 2 trips the serving circuit
# breaker (consecutive serve_accept transients), watches the typed
# Degraded shed, then clears the fault plan and waits for the
# BACKGROUND probe to close the breaker with no client traffic.
#
# Artifacts gate: the metrics dump carries retry.attempts /
# faults.injected / breaker.opened / breaker.closed, the daemon leaks
# ZERO resident tables, and the flight dump merges into a
# Perfetto-loadable trace showing the breaker state transitions.
#
# Runs on the CPU backend by default so it gates every premerge node —
# the fault plan is how a laptop rehearses a dying TPU.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_PROFILE=on
export SPARK_RAPIDS_TPU_METRICS_DUMP="$out/metrics.json"
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight.json"
# the seeded chaos plan under test: 10% transient faults at the device
# dispatch and wire-serde boundaries (deterministic per seed, so this
# gate never flakes), fast backoff, a hair-trigger breaker
export SPARK_RAPIDS_TPU_FAULTS="seed=1,dispatch:transient:0.1,serde:transient:0.1"
export SPARK_RAPIDS_TPU_RETRY_BASE_MS=1
export SPARK_RAPIDS_TPU_BREAKER_THRESHOLD=2
export SPARK_RAPIDS_TPU_BREAKER_PROBE_S=0.2
# dynamic lock-order detector rides the whole smoke (the racecheck
# half of the srt-check CI discipline): every tracked lock records the
# acquisition-order graph; the driver fails on any cycle or inversion
# of the sanctioned registry->session->scheduler->spill order
export SPARK_RAPIDS_TPU_LOCKCHECK=on

python3 - <<'PY'
import json
import time

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import config, faults, metrics

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
# the local runs recover from the same armed fault plan, so parity
# below proves recovery on BOTH sides of the wire
want = [
    norm(rb.table_plan_wire(json.dumps(CHAIN), *b)) for b in batches
]

# -- phase 1: served stream under 10% transient faults ----------------
with serving.serve() as srv:
    with serving.Client(srv.port, name="chaos") as c:
        got = [norm(g) for g in c.stream(CHAIN, batches)]
    assert got == want, "served results diverged under injected faults"

    # -- phase 2: trip the breaker, shed typed, recover via probe -----
    config.set_flag("FAULTS", "serve_accept:transient:1")
    with serving.Client(srv.port, name="tripper") as c:
        for _ in range(2):
            try:
                c.stream(CHAIN, batches[:1])
                raise AssertionError("injected fault did not surface")
            except serving.ServingTransientError:
                pass
        try:
            c.stream(CHAIN, batches[:1])
            raise AssertionError("open breaker did not shed")
        except serving.ServingDegraded:
            pass
        assert srv.stats()["breaker"]["state"] == faults.OPEN
        # device "recovers": only the background probe closes it
        config.set_flag("FAULTS", "")
        deadline = time.perf_counter() + 30
        while srv.breaker.state != faults.CLOSED:
            assert time.perf_counter() < deadline, "breaker stuck open"
            time.sleep(0.05)
        got = [norm(g) for g in c.stream(CHAIN, batches[:1])]
        assert got == want[:1], "post-recovery stream diverged"

assert rb.resident_table_count() == 0, "daemon leaked resident tables"
assert rb.leak_report() == [], rb.leak_report()

# lock-order gate: the retrying, breaker-tripping, multi-threaded run
# above is exactly the interleaving soup where an inversion would show
from spark_rapids_jni_tpu.utils import lockcheck

lockdoc = lockcheck.assert_clean()
assert lockdoc["acquisitions"] > 0, "lockcheck saw no acquisitions"
print(lockcheck.summary_line())

c = metrics.snapshot()["counters"]
assert c.get("retry.attempts", 0) > 0, c
assert c.get("faults.injected", 0) > 0, c
assert c.get("breaker.opened", 0) >= 1, c
assert c.get("breaker.closed", 0) >= 1, c
print(
    f"chaos driver OK: {c['faults.injected']} faults injected, "
    f"{c['retry.attempts']} retries, breaker opened "
    f"{c['breaker.opened']}x / closed {c['breaker.closed']}x, "
    "0 leaked tables"
)
PY

# the analysis tools below import the package too — drop the dump envs
# so THEIR atexit hooks can't clobber the artifacts under test
unset SPARK_RAPIDS_TPU_PROFILE SPARK_RAPIDS_TPU_FLIGHT_DUMP \
  SPARK_RAPIDS_TPU_METRICS_DUMP SPARK_RAPIDS_TPU_FAULTS \
  SPARK_RAPIDS_TPU_LOCKCHECK

# both artifacts exist, parse, and the metrics dump carries the
# fault-plane counters the driver asserted in-process
test -s "$out/metrics.json"
test -s "$out/flight.json"
# the flight dump's lockcheck exit section is the crash postmortem a
# hang-to-SIGKILL run would leave behind — it must carry the graph
python3 - "$out/flight.json" <<'PY'
import json
import sys

sec = json.load(open(sys.argv[1]))["sections"]["lockcheck"]
assert sec["enabled"] is True, sec
assert sec["acquisitions"] > 0, sec
assert sec["cycles"] == [], sec
assert sec["order_violations"] == [], sec
print(
    f"lockcheck flight section OK: {sec['acquisitions']} acquisitions, "
    f"{len(sec['edges'])} edges, 0 cycles, 0 order violations"
)
PY
python3 - "$out/metrics.json" <<'PY'
import json
import sys

c = json.load(open(sys.argv[1])).get("counters", {})
assert c.get("retry.attempts", 0) > 0, c
assert c.get("faults.injected", 0) > 0, c
assert c.get("breaker.opened", 0) >= 1, c
assert c.get("breaker.closed", 0) >= 1, c
fault_counters = {
    k: v for k, v in sorted(c.items())
    if k.split(".")[0] in ("faults", "retry", "breaker")
}
print("chaos metrics dump OK:", fault_counters)
PY

# the flight dump merges into a Perfetto trace that SHOWS the breaker
# walking open -> (half-open) -> closed, plus the injection/retry
# instants — the postmortem view of a degraded daemon
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
assert "breaker.opened" in names, sorted(names)
assert "breaker.closed" in names, sorted(names)
assert "fault.injected" in names, sorted(names)
assert "retry" in names, sorted(names)
print(
    f"chaos trace OK: {len(events)} events, breaker transitions + "
    f"{sum(1 for e in instants if e['name'].endswith('fault.injected'))} "
    "injection instants in the merged Perfetto timeline"
)
PY
