#!/usr/bin/env bash
# Restart smoke gate: a durable daemon serving multiple sessions is
# SIGKILLed mid-stream (ISSUE 14). Its successor must replay the
# per-session journals BEFORE accepting traffic: clients reconnect
# with their resume tokens and download BYTE-IDENTICAL tables, replay
# their mutating request ids without re-application, and land their
# plans on a manifest-warmed compile cache — nonzero cache hits, ZERO
# misses across the replayed plans.
#
# Artifacts gate: journal + payload files exist after the kill, the
# restore doc reports every session recovered with zero quarantines
# and zero warm-start failures, clean byes erase the durable state,
# the daemon leaks zero resident tables, and the flight dump merges
# into a Perfetto trace carrying the restore/checkpoint instants.
#
# Runs on the CPU backend so it gates every premerge node — kill -9
# against a laptop process is exactly the crash it rehearses.
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

out="$(mktemp -d)"
trap 'rm -rf "$out"' EXIT

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export SPARK_RAPIDS_TPU_DURABLE=on
export SPARK_RAPIDS_TPU_CHECKPOINT_DIR="$out/ckpt"
export SPARK_RAPIDS_TPU_METRICS=on

# -- life 1: serve multi-session state, then die by SIGKILL -----------
python3 - "$out/state.json" "$out/ready" <<'PY' &
import json
import sys
import threading
import time

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import serving

state_path, ready_path = sys.argv[1], sys.argv[2]
I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)

CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
    {"op": "sort_by", "keys": [{"column": 0}]},
]


def batch(n, seed):
    rng = np.random.default_rng(n + seed)
    k = rng.integers(-500, 500, n, dtype=np.int64)
    m = (k > 0).astype(np.uint8)
    return ([I64, B8], [0, 0], [k.tobytes(), m.tobytes()],
            [None, None], n)


def canon(wire):
    t, s, d, v, n = wire
    return [
        [int(x) for x in t], [int(x) for x in s],
        [None if x is None else bytes(x).hex() for x in d],
        [None if x is None else bytes(x).hex() for x in v], int(n),
    ]


srv = serving.Server(workers=2)
srv.start()
state = {"sessions": []}
clients = []
for i in range(3):
    c = serving.Client(srv.port, name=f"tenant-{i}").connect()
    clients.append(c)
    assert c.resume_token, "durable daemon handed out no resume token"
    doc = {"session": c.session, "token": c.resume_token, "tables": {}}
    up = batch(2048 + 128 * i, seed=i)
    t1 = c.upload(up, req=f"up-{i}")
    doc["tables"][t1] = canon(c.download(t1))
    t2 = c.plan(CHAIN, [t1], req=f"plan-{i}")
    doc["tables"][t2] = canon(c.download(t2))
    doc["replay"] = {"up": [f"up-{i}", t1], "plan": [f"plan-{i}", t2]}
    state["sessions"].append(doc)

# keep a stream in flight so the SIGKILL lands on a HOT daemon — the
# crash the journal exists for, not a quiesced shutdown
streamer = serving.Client(srv.port, name="streamer").connect()
state["streamer"] = {
    "session": streamer.session, "token": streamer.resume_token,
}
with open(state_path, "w") as f:
    json.dump(state, f)


def pound():
    while True:
        streamer.stream(CHAIN, [batch(4096, s) for s in range(4)])


threading.Thread(target=pound, daemon=True).start()
time.sleep(0.2)
open(ready_path, "w").close()
time.sleep(600)  # the shell kill -9s us long before this
PY
life1=$!

for _ in $(seq 300); do
  [ -f "$out/ready" ] && break
  sleep 0.1
done
test -f "$out/ready"
kill -9 "$life1"
wait "$life1" || true

# the crash left durable state behind: journals + table payloads
test -n "$(ls "$out/ckpt"/*.wal)"
test -n "$(ls "$out/ckpt"/*.npz)"

# -- life 2: restore, reconnect, verify ------------------------------
export SPARK_RAPIDS_TPU_TRACE=1
export SPARK_RAPIDS_TPU_FLIGHT_DUMP="$out/flight.json"
export SPARK_RAPIDS_TPU_PROFILE=on
python3 - "$out/state.json" <<'PY'
import json
import sys

from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu.utils import metrics

state = json.load(open(sys.argv[1]))
F64 = int(dt.TypeId.FLOAT64)
CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
    {"op": "sort_by", "keys": [{"column": 0}]},
]


def canon(wire):
    t, s, d, v, n = wire
    return [
        [int(x) for x in t], [int(x) for x in s],
        [None if x is None else bytes(x).hex() for x in d],
        [None if x is None else bytes(x).hex() for x in v], int(n),
    ]


srv = serving.Server(workers=2)
srv.start()
doc = srv.stats()["durability"]
restore = doc["restore"]
# the streamer session held no tables at the kill; it restores too
assert restore["sessions"] >= len(state["sessions"]), restore
assert restore["quarantined"] == {}, restore
assert restore["warm_compiles"] >= 1, restore
assert restore["warm_failures"] == 0, restore

snap = metrics.snapshot()["counters"]
miss0 = snap.get("compile_cache.miss", 0)
hit0 = snap.get("compile_cache.hit", 0)

for sess in state["sessions"]:
    c = serving.Client(
        srv.port, session=sess["session"], resume=sess["token"]
    ).connect()
    # every journaled table survives the crash byte-identical
    for local, want in sess["tables"].items():
        assert canon(c.download(int(local))) == want, (
            f"session {sess['session']} table {local} diverged "
            "across the restart"
        )
    # a replayed mutating request id applies NOTHING new: the daemon
    # answers from the restored idempotency window
    req, t_up = sess["replay"]["up"]
    before = len(sess["tables"])
    assert c.upload(([], [], [], [], 0), req=req) == t_up
    req, t_plan = sess["replay"]["plan"]
    assert c.plan(CHAIN, [t_up], req=req) == t_plan
    stats = next(s for s in srv.stats()["sessions"]
                 if s["session"] == sess["session"])
    assert stats["tables"] == before, (stats, before)
    # a FRESH plan of the same shape lands on the warmed cache
    t_new = c.plan(CHAIN, [t_up], req=req + "-new")
    c.download(t_new)
    c.close()  # clean bye: erases this session's durable state

snap = metrics.snapshot()["counters"]
miss = snap.get("compile_cache.miss", 0) - miss0
hit = snap.get("compile_cache.hit", 0) - hit0
assert miss == 0, f"replayed plans recompiled ({miss} misses)"
assert hit > 0, "replayed plans never touched the warmed cache"
replays = snap.get("serving.idempotent_replays", 0)
assert replays >= 2 * len(state["sessions"]), replays

# the streamer held no tables at the kill; its session restored too —
# a clean bye retires its journal
serving.Client(
    srv.port, session=state["streamer"]["session"],
    resume=state["streamer"]["token"],
).connect().close()

srv.stop()
assert rb.resident_table_count() == 0, "restart leaked resident tables"
assert rb.leak_report() == [], rb.leak_report()
print(
    f"restart driver OK: {restore['sessions']} sessions restored in "
    f"{restore['took_ms']}ms, {restore['warm_compiles']} plans "
    f"warm-compiled, {replays} idempotent replays, {hit} cache hits / "
    "0 misses across replayed plans, byte-identical downloads, "
    "0 leaked tables"
)
PY

# clean byes erased every session's durable state; only the warm-start
# manifest remains for the next restart
leftover="$(ls "$out/ckpt" | grep -v '^manifest\.wal$' || true)"
test -z "$leftover"

# the flight dump merges into a Perfetto trace showing the restore —
# the postmortem view of a crash-recovered daemon
unset SPARK_RAPIDS_TPU_FLIGHT_DUMP SPARK_RAPIDS_TPU_DURABLE \
  SPARK_RAPIDS_TPU_CHECKPOINT_DIR SPARK_RAPIDS_TPU_METRICS \
  SPARK_RAPIDS_TPU_PROFILE
python3 tools/explain.py --merge "$out/flight.json" \
  -o "$out/merged.trace.json" > "$out/merged.txt"
python3 - "$out/merged.trace.json" <<'PY'
import json
import sys

trace = json.load(open(sys.argv[1]))
events = trace["traceEvents"]
assert events, "empty merged trace"
names = {e["name"].split("/")[-1] for e in events}
assert "restore.done" in names, sorted(names)
assert "restore.session" in names, sorted(names)
print(
    f"restart trace OK: {len(events)} events, restore instants in "
    "the merged Perfetto timeline"
)
PY
