"""One served request, fully accounted: the spans of the served path.

Four requests — a plan over resident tables, a ``stream`` call, an
upload with its download, and a ``mesh=4`` ``stream`` on the virtual CPU
devices — each run once against a live daemon with the metrics registry
and the flight ring on. The cases below read what those runs left
behind: every layer-boundary span (CONTRIBUTING.md, "Observability")
appears once per command as a ``metrics.span`` with a timer, carries the
request's trace id, hangs under the span that caused it (the scheduler's
thread hop included), fits inside it, and ``serving.request`` spends
next to none of its time outside its children.

One span cannot carry the trace id: ``serving.recv`` reads the frame
whose header names the trace, so it is recorded untagged, on the
connection's thread, right before its ``serving.request``.

Needs neither the native library nor a chip.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import config, flight, metrics, tracing

I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)

FACT_ROWS = 60_000
DIM_ROWS = 200
# the upload + download request does no device work to speak of: enough
# bytes that the frames, not the threads' hand-overs, are what it takes
UPDOWN_ROWS = 600_000

RESIDENT_PLAN = [
    {"op": "filter", "mask": 3},
    {"op": "join", "on": [0]},
    {"op": "groupby", "by": [0], "aggs": [
        {"column": 1, "agg": "sum"}, {"column": 2, "agg": "sum"}]},
    {"op": "sort_by", "keys": [
        {"column": 1, "ascending": False}, {"column": 0}]},
]
STREAM_PLAN = [
    {"op": "filter", "mask": 3},
    {"op": "groupby", "by": [0], "aggs": [
        {"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"}]},
]
MESH_PLAN = [
    {"op": "filter", "mask": 3},
    {"op": "partition", "kind": "hash", "keys": [0], "num": 4},
]

FLAGS = ("METRICS", "FLIGHT", "TRACE", "PIPELINE")


def _fact(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    key = rng.integers(0, DIM_ROWS, rows, dtype=np.int64)
    qty = rng.integers(1, 100, rows, dtype=np.int64)
    price = rng.random(rows) * 100.0
    mask = (rng.random(rows) < 0.8).astype(np.uint8)
    batch = ([I64, I64, F64, B8], [0, 0, 0, 0],
             [key.tobytes(), qty.tobytes(), price.tobytes(), mask.tobytes()],
             [None, None, None, None], rows)
    return batch, int(mask.sum())


def _dim():
    key = np.arange(DIM_ROWS, dtype=np.int64)
    attr = (key * 7) % 13
    return ([I64, I64], [0, 0], [key.tobytes(), attr.tobytes()],
            [None, None], DIM_ROWS)


# ---------------------------------------------------------------------------
# the four requests
# ---------------------------------------------------------------------------


def _resident_setup(c):
    fact, _ = _fact(FACT_ROWS, 1)
    ids = {"fact": c.upload(fact), "dim": c.upload(_dim())}
    c.free(c.plan(RESIDENT_PLAN, [ids["fact"], ids["dim"]]))  # compiles
    return ids


def _resident_request(c, ids):
    out = c.plan(RESIDENT_PLAN, [ids["fact"], ids["dim"]])
    rows = c.download(out)[4]
    assert 0 < rows <= DIM_ROWS
    ids["out"] = out
    return {"commands": 2}


def _stream_setup(c):
    c.stream(STREAM_PLAN, [_fact(FACT_ROWS, 2)[0]])
    return {}


def _stream_request(c, state):
    got = c.stream(STREAM_PLAN, [_fact(FACT_ROWS, 3)[0]])
    assert len(got) == 1 and 0 < got[0][4] <= DIM_ROWS
    return {"commands": 1}


def _updown_setup(c):
    c.free(c.upload(_fact(UPDOWN_ROWS, 4)[0]))
    return {}


def _updown_request(c, state):
    batch, _ = _fact(UPDOWN_ROWS, 5)
    tid = c.upload(batch)
    back = c.download(tid)
    assert back[4] == UPDOWN_ROWS and back[2][0] == batch[2][0]
    state["table"] = tid
    return {"commands": 2}


def _mesh_setup(c):
    # the same batch as the request: the exchange's capacity, and with
    # it the compiled program, follows the rows the filter keeps
    c.stream(MESH_PLAN, [_fact(FACT_ROWS, 7)[0]])
    return {}


def _mesh_request(c, state):
    batch, kept = _fact(FACT_ROWS, 7)
    got = c.stream(MESH_PLAN, [batch])
    assert got[0][4] == kept
    return {"commands": 1, "kept": kept}


REQUESTS = {
    "resident": (_resident_setup, _resident_request, {}),
    "stream": (_stream_setup, _stream_request, {}),
    "updown": (_updown_setup, _updown_request, {}),
    "mesh": (_mesh_setup, _mesh_request, {"mesh": 4}),
}

# leaf span name -> times it appears, per request
PER_COMMAND = ("client.rpc", "client.send", "client.recv", "serving.recv",
               "serving.request", "serving.send")
EXPECTED = {
    "resident": {
        "plan.check": 1, "serving.admission": 1, "serving.plan": 1,
        # the dimension's key is unique and dense: the filter and the
        # join ride the groupby's segment as masks (PR 38)
        "serving.download": 1, "plan": 1,
        "plan.segment.filter__join__groupby": 1, "plan.segment.sort_by": 1,
        "groupby.reduce": 1,
        "wire.serialize": 1, "wire.serialize.wait": 1,
        "wire.serialize.copy": 1,
    },
    "stream": {
        "serving.request_split": 1,
        "plan.check": 1, "serving.admission": 1, "serving.stream": 1,
        "wire.deserialize": 1, "plan": 1,
        "plan.segment.filter__groupby": 1, "groupby.reduce": 1,
        "wire.serialize": 1,
        "wire.serialize.wait": 1, "wire.serialize.copy": 1,
        "serving.reply_serialize": 1,
    },
    "updown": {
        "serving.request_split": 1,
        "serving.admission": 1, "serving.upload": 1, "wire.deserialize": 1,
        "serving.download": 1, "wire.serialize": 1,
        "wire.serialize.wait": 1, "wire.serialize.copy": 1,
    },
    "mesh": {
        "serving.request_split": 1,
        "plan.check": 1, "serving.admission": 1, "serving.stream": 1,
        "wire.deserialize": 1, "plan": 1,
        "plan.segment.mesh": 1, "mesh.stage": 1, "mesh.pack": 1,
        "mesh.counts": 1, "mesh.exchange": 1, "mesh.gather": 1,
        "plan.partition_counts": 1, "plan.partition_exchange": 1,
        "wire.serialize": 1, "wire.serialize.wait": 1,
        "wire.serialize.copy": 1, "serving.reply_serialize": 1,
    },
}


def _spans(events):
    """Flight B/E pairs -> [{name (qualified), leaf, tid, t0, t1, tp}]."""
    out, open_ = [], {}
    for e in sorted(events, key=lambda e: e["seq"]):
        if e["ph"] == "B":
            open_.setdefault(e["tid"], []).append(e)
        elif e["ph"] == "E":
            stack = open_.get(e["tid"], [])
            for i in range(len(stack) - 1, -1, -1):
                if stack[i]["name"] == e["name"]:
                    b = stack.pop(i)
                    out.append({
                        "name": b["name"], "leaf": b["name"].rsplit("/", 1)[-1],
                        "tid": b["tid"], "t0": b["t_ns"], "t1": e["t_ns"],
                        "tp": b.get("arg"),
                    })
                    break
    # the scheduler backdates the queue wait onto the ring as a B/E pair
    # of its own; it is not a metrics.span
    return [s for s in out if s["leaf"] != "serving.queue_wait"]


def _wait_for_requests(n: int) -> None:
    """The client has its reply a moment before the connection thread
    closes ``serving.request``: wait until ``n`` of them are recorded."""
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        done = metrics.snapshot()["histograms"].get(
            "span_ms.serving.request", {}).get("count", 0)
        if done >= n:
            return
        time.sleep(0.005)
    raise AssertionError(f"{n} serving.request spans never closed")


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps the names."""

    names: list = []

    def __init__(self, name):
        _Recorder.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def anatomy():
    """Each request once, warm, under one trace id -> what it left in
    the flight ring, the registry and the session's stats."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU devices")
    config.set_flag("METRICS", True)
    config.set_flag("FLIGHT", True)
    config.set_flag("PIPELINE", "0")
    real = metrics._ANNOTATION
    seen = {}
    try:
        with serving.serve(workers=2) as srv:
            for name, (setup, request, kw) in REQUESTS.items():
                with serving.Client(srv.port, name=name, timeout=300.0,
                                    **kw) as c:
                    state = setup(c)
                    time.sleep(0.3)  # a client thinking between commands
                    metrics.reset()
                    flight.reset()
                    _Recorder.names = []
                    metrics._ANNOTATION = _Recorder
                    ctx = tracing.new_context()
                    try:
                        with tracing.activate(ctx):
                            facts = request(c, state)
                        _wait_for_requests(facts["commands"])
                    finally:
                        metrics._ANNOTATION = real
                    snap = metrics.snapshot()  # drains the clock
                    seen[name] = dict(
                        facts, trace_id=ctx.trace_id,
                        spans=_spans(flight.tail_records()),
                        device=[e for e in flight.tail_records()
                                if e["ph"] == "X"],
                        stats_device=c.stats()["device"],
                        snap=snap,
                        annotations=list(_Recorder.names),
                        session=next(s for s in c.stats()["sessions"]
                                     if s["name"] == name),
                    )
    finally:
        metrics._ANNOTATION = real
        for f in FLAGS:
            config.clear_flag(f)
        flight.reset()
        metrics.reset()
        tracing.reset_requests()
    return seen


ALL = sorted(REQUESTS)


@pytest.mark.parametrize("req", ALL)
def test_every_span_appears_once_per_command(anatomy, req):
    run = anatomy[req]
    count = {}
    for s in run["spans"]:
        count[s["leaf"]] = count.get(s["leaf"], 0) + 1
    want = dict(EXPECTED[req])
    want.update({n: run["commands"] for n in PER_COMMAND})
    assert {n: count.get(n, 0) for n in want} == want


@pytest.mark.parametrize("req", ALL)
def test_every_span_is_a_timer(anatomy, req):
    """One way to open a span: each has a timer and a self time."""
    run = anatomy[req]
    timers, selfs = run["snap"]["timers"], run["snap"]["span_self"]
    for leaf in {s["leaf"] for s in run["spans"]}:
        assert timers[leaf]["count"] >= 1, leaf
        assert leaf in selfs, leaf
    # the queue wait is no span: the scheduler observes it at dequeue,
    # into the histogram the session's percentiles are the other view of
    waits = run["snap"]["histograms"]["serving.queue_wait_ms"]
    assert waits["count"] >= run["commands"]
    assert "serving.queue_wait" not in timers


@pytest.mark.parametrize("req", ALL)
def test_spans_share_the_requests_trace_id(anatomy, req):
    run = anatomy[req]
    for s in run["spans"]:
        if s["leaf"] == "serving.recv":
            assert s["tp"] is None  # the header it reads names the trace
            continue
        ctx = tracing.parse_traceparent(s["tp"])
        assert ctx is not None and ctx.trace_id == run["trace_id"], s


@pytest.mark.parametrize("req", ALL)
def test_recv_comes_right_before_its_request(anatomy, req):
    spans = anatomy[req]["spans"]
    for r in (s for s in spans if s["leaf"] == "serving.recv"):
        after = [s for s in spans if s["tid"] == r["tid"]
                 and s["name"] == "serving.request" and s["t0"] >= r["t1"]]
        assert after, r
        nxt = min(after, key=lambda s: s["t0"])
        between = [s for s in spans if s["tid"] == r["tid"]
                   and r["t1"] <= s["t0"] < nxt["t0"] and s is not r]
        assert not between, between


@pytest.mark.parametrize("req", ALL)
def test_parent_is_the_span_that_caused_it(anatomy, req):
    """The qualified name is the ancestry; the parent it names is open
    around the child — on another thread where the scheduler hands the
    work over — and every server-side span descends from
    ``serving.request``, every client-side one from ``client.rpc``."""
    spans = anatomy[req]["spans"]
    hops = 0
    for s in spans:
        path = s["name"].split("/")
        if len(path) == 1:
            assert s["leaf"] in ("client.rpc", "serving.recv",
                                 "serving.request"), s
            continue
        assert path[0] in ("client.rpc", "serving.request"), s
        parent = "/".join(path[:-1])
        holders = [p for p in spans if p["name"] == parent
                   and p["t0"] <= s["t0"] and s["t1"] <= p["t1"]]
        assert holders, (s, parent)
        hops += all(p["tid"] != s["tid"] for p in holders)
    # the executor's root span hangs under the connection thread's
    assert hops >= anatomy[req]["commands"]


@pytest.mark.parametrize("req", ALL)
def test_children_fit_inside_their_parent(anatomy, req):
    spans = anatomy[req]["spans"]
    for p in spans:
        kids = [s for s in spans if s["tid"] == p["tid"]
                and s["name"] == p["name"] + "/" + s["leaf"]
                and p["t0"] <= s["t0"] and s["t1"] <= p["t1"]]
        assert sum(k["t1"] - k["t0"] for k in kids) <= p["t1"] - p["t0"], p


@pytest.mark.parametrize("req", ALL)
def test_request_self_time_is_small(anatomy, req):
    """What the connection thread hands to the executor — the queue
    wait and the work — is credited to ``serving.request`` as child
    time, so its self time is the daemon's own bookkeeping."""
    snap = anatomy[req]["snap"]
    total = snap["timers"]["serving.request"]["total_s"]
    self_s = snap["span_self"]["serving.request"]["self_s"]
    assert 0.0 <= self_s < 0.10 * total, (self_s, total)


# program -> launches, and segment -> launches, of one request
LAUNCHES = {
    "resident": (
        {"srt_bucketed_join_span": 1, "srt_fused_plan": 1,
         "srt_groupby_reduce": 1, "srt_bucketed_sort": 1},
        # the span read comes before the plan is segmented
        {"plan.segment.filter__join__groupby": 2, "plan.segment.sort_by": 1},
    ),
    "stream": (
        {"srt_fused_plan": 1, "srt_groupby_reduce": 1},
        {"plan.segment.filter__groupby": 2},
    ),
    # the stage's two cached shard_map programs (PR 43), both inside
    # the whole-plan mesh segment
    "mesh": (
        {"srt_mesh_counts": 1, "srt_mesh_exchange": 1},
        {"plan.segment.mesh": 2},
    ),
    "updown": ({}, {}),
}


def _device_timers(run):
    timers = run["snap"]["timers"]
    busy = timers.get("device.busy", {"count": 0, "total_s": 0.0})
    idle = timers.get("device.idle", {"count": 0, "total_s": 0.0})
    segs = {k[len("device."):]: t for k, t in timers.items()
            if k.startswith("device.plan.segment.")}
    progs = {k[len("device."):]: t for k, t in timers.items()
             if k.startswith("device.") and k not in ("device.busy",)
             and not k.startswith(("device.idle", "device.plan.segment."))}
    return busy, idle, segs, progs


@pytest.mark.parametrize("req", ALL)
def test_every_launch_leaves_one_interval(anatomy, req):
    """Under its program's name and under its segment's, the reduce half
    and the span read included; the drain put them in the snapshot."""
    busy, _, segs, progs = _device_timers(anatomy[req])
    want_progs, want_segs = LAUNCHES[req]
    assert {k: t["count"] for k, t in progs.items()} == want_progs
    assert {k: t["count"] for k, t in segs.items()} == want_segs
    assert busy["count"] == sum(want_progs.values())
    assert len(anatomy[req]["device"]) == busy["count"]
    assert anatomy[req]["snap"]["counters"].get("device.lost", 0) == 0


def test_the_mesh_stage_is_on_the_clock(anatomy):
    """The stage's programs go through ``cached_jit`` (PR 43; until then
    the clock filed nothing under the stage): the warm request built
    nothing and its two launches are under their programs' names, under
    the mesh segment's and in the daemon's ``stats``."""
    snap = anatomy["mesh"]["snap"]
    assert snap["timers"]["plan.segment.mesh"]["count"] == 1
    assert snap["counters"]["plan.mesh_segments"] == 1
    assert snap["counters"].get("compile_cache.miss", 0) == 0
    assert snap["counters"]["compile_cache.hit"] == 2
    assert "jax.build" not in snap["timers"]
    for name in ("device.srt_mesh_counts", "device.srt_mesh_exchange"):
        assert snap["timers"][name]["total_s"] > 0.0
    assert snap["timers"]["device.plan.segment.mesh"]["count"] == 2
    assert snap["counters"].get("device.lost", 0) == 0
    assert [e["name"] for e in sorted(
        anatomy["mesh"]["device"], key=lambda e: e["t_ns"])] == [
        "device.srt_mesh_counts", "device.srt_mesh_exchange"]
    assert sorted(anatomy["mesh"]["stats_device"]["by_program"]) == [
        "srt_mesh_counts", "srt_mesh_exchange"]


@pytest.mark.parametrize("req", ["resident", "stream", "mesh"])
def test_segment_device_timers_sum_to_device_busy(anatomy, req):
    """``device.<program>`` sums to ``device.busy``; the segments' share
    leaves out what was launched outside any (the span read); busy and
    idle telescope to last completion - first enqueue."""
    run = anatomy[req]
    busy, idle, segs, progs = _device_timers(run)
    assert sum(t["total_s"] for t in progs.values()) == pytest.approx(
        busy["total_s"], abs=1e-9)
    in_segments = sum(t["total_s"] for t in segs.values())
    assert in_segments <= busy["total_s"] + 1e-9
    outside = sum(t["total_s"] for k, t in progs.items()
                  if k == "srt_bucketed_join_span")
    assert in_segments + outside == pytest.approx(busy["total_s"], abs=1e-9)
    recs = sorted(run["device"], key=lambda e: e["t_ns"])
    assert len(recs) == busy["count"] > 0
    extent = (recs[-1]["arg"]["end_ns"] - recs[0]["t_ns"]) / 1e9
    assert busy["total_s"] + idle["total_s"] == pytest.approx(
        extent, abs=1e-6)
    # one device, one queue: no two intervals overlap
    for a, b in zip(recs, recs[1:]):
        assert a["arg"]["end_ns"] <= b["t_ns"], (a, b)


@pytest.mark.parametrize("req", ["resident", "stream", "mesh"])
def test_device_records_carry_span_and_trace(anatomy, req):
    """The ring's device records: start before end, the span that
    launched them (by its qualified name) and the request's trace."""
    run = anatomy[req]
    names = {s["name"] for s in run["spans"]}
    assert run["device"]
    for e in run["device"]:
        arg = e["arg"]
        assert e["name"].startswith("device.srt_")
        assert e["t_ns"] <= arg["end_ns"]
        assert arg["span"] in names, arg
        ctx = tracing.parse_traceparent(arg["tp"])
        assert ctx is not None and ctx.trace_id == run["trace_id"]
    tagged = [e for e in tracing.assign_trace_ids(run["device"])]
    assert all(e["trace_id"] == run["trace_id"] for e in tagged)
    in_segment = [e for e in run["device"]
                  if "/plan.segment." in e["arg"]["span"]]
    assert len(in_segment) == sum(LAUNCHES[req][1].values())


def test_stats_carry_the_device_doc(anatomy):
    doc = anatomy["resident"]["stats_device"]
    busy, idle, _, progs = _device_timers(anatomy["resident"])
    assert doc["busy_s"] == pytest.approx(busy["total_s"])
    assert doc["idle_s"] == pytest.approx(idle["total_s"])
    assert doc["lost"] == 0 and doc["longest_ms"] > 0.0
    assert {k: v["count"] for k, v in doc["by_program"].items()} == {
        k: t["count"] for k, t in progs.items()}
    assert anatomy["updown"]["stats_device"]["by_program"] == {}


def test_segment_names_come_from_the_plan(anatomy):
    sigs = [plan_mod.segment_sig(ops)
            for _, ops in plan_mod.segment_plan(RESIDENT_PLAN)]
    # with nothing known of the dimension the join is a boundary ...
    assert sigs == ["filter", "join", "groupby", "sort_by"]
    # ... and the served plan, which read the dimension, ran two segments
    timers = anatomy["resident"]["snap"]["timers"]
    ran = sorted(t for t in timers if t.startswith("plan.segment."))
    assert ran == ["plan.segment.filter__join__groupby",
                   "plan.segment.sort_by"]
    (fused,) = plan_mod.segment_plan(STREAM_PLAN)
    assert plan_mod.segment_sig(fused[1]) == "filter__groupby"


def test_recv_does_not_hold_the_clients_thinking():
    """``serving.recv`` opens once the length prefix is here: a client
    that sleeps between two commands is in neither's span (the client's
    ``client.recv`` does include its wait for the daemon)."""
    config.set_flag("METRICS", True)
    try:
        with serving.serve(workers=1) as srv, \
                serving.Client(srv.port) as c:
            metrics.reset()
            c.stats()
            time.sleep(0.5)
            c.stats()
            _wait_for_requests(2)
            snap = metrics.snapshot()
    finally:
        config.clear_flag("METRICS")
        metrics.reset()
    recv = snap["timers"]["serving.recv"]
    assert recv["count"] == 2 and recv["total_s"] < 0.25, recv
    assert snap["timers"]["serving.request"]["count"] == 2
    assert snap["timers"]["client.recv"]["count"] == 2


@pytest.mark.parametrize("req", ALL)
def test_no_annotation_is_named_like_the_benchs(anatomy, req):
    """perfbench's trace reader keeps ``client.*`` and
    ``perfbench.window`` by name: the program's all start ``srt/``."""
    names = anatomy[req]["annotations"]
    assert len(names) == len(anatomy[req]["spans"])
    assert all(n.startswith(tracing.ANNOTATION_PREFIX) for n in names)
    assert not any(n.startswith("client.") or n == "perfbench.window"
                   for n in names)
    assert "srt/client.rpc" in names and "srt/serving.request" in names


def _bad_plan(c):
    c.plan([{"op": "no_such_op"}], [])


def _bad_stream(c):
    c.stream([{"op": "no_such_op"}], [_fact(16, 9)[0]])


def _bad_download(c):
    c.download(987654)


def _bad_free(c):
    c.free(987654)


@pytest.mark.parametrize(
    "call", [_bad_plan, _bad_stream, _bad_download, _bad_free],
    ids=lambda f: f.__name__.lstrip("_"))
def test_typed_error_ends_client_rpc_as_an_error(call):
    """The daemon's typed error is raised INSIDE ``client.rpc``: the
    span's E event names it (tools/tracequery.py prints ``!<type>``)
    and ``span.client.rpc.errors`` counts it; its frames are clean."""
    config.set_flag("METRICS", True)
    config.set_flag("FLIGHT", True)
    try:
        with serving.serve(workers=1) as srv, \
                serving.Client(srv.port) as c:
            metrics.reset()
            flight.reset()
            with pytest.raises(serving.ServingError) as err:
                call(c)
            _wait_for_requests(1)
            snap = metrics.snapshot()
            ends = [e for e in flight.tail_records()
                    if e["ph"] == "E" and e["name"] == "client.rpc"]
    finally:
        for f in FLAGS:
            config.clear_flag(f)
        flight.reset()
        metrics.reset()
    assert [e.get("arg") for e in ends] == [type(err.value).__name__]
    assert snap["counters"]["span.client.rpc.errors"] == 1
    assert snap["timers"]["client.rpc"]["count"] == 1
    assert "span.client.recv.errors" not in snap["counters"]
    assert "span.client.send.errors" not in snap["counters"]


@pytest.mark.parametrize("live", [False, True], ids=["planes-off", "live"])
def test_wire_out_waits_up_front_only_under_a_live_span(monkeypatch, live):
    """``wire.serialize.wait`` splits the device wait from the copy
    with one ``block_until_ready``; with every plane off nothing is
    added and the copies wait column by column, as before."""
    from spark_rapids_jni_tpu import runtime_bridge as rb

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        rb.jax, "block_until_ready",
        lambda x: (calls.append(len(x)), real(x))[1])
    for f in FLAGS + ("METRICS_DUMP", "PLANSTATS", "PLANSTATS_DIR"):
        config.clear_flag(f)
    if live:
        config.set_flag("METRICS", True)
    try:
        batch, _ = _fact(64, 10)
        tbl = rb._table_from_wire(*batch, None)
        out = rb._table_to_wire(tbl)
    finally:
        config.clear_flag("METRICS")
        metrics.reset()
    assert out[4] == 64 and out[2][0] == batch[2][0]
    assert calls == ([4] if live else [])


@pytest.mark.parametrize("scope", [None, "srt.filter"])
def test_cached_jit_traces_under_its_scope(scope):
    """``cached_jit(scope=...)`` is the one wrap the bucketed runners
    share: the op's name reaches every HLO op's metadata, and a jit
    without a scope is what it was."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.utils import buckets

    def build():
        return lambda x, n: (x * 2 + 1, n)

    fn = buckets.cached_jit(
        ("anatomy.scope", scope), build, "srt_anatomy_scope", scope=scope)
    x = jnp.arange(8)
    text = fn.lower(x, 3).as_text(debug_info=True)
    assert ("srt.filter" in text) is (scope is not None)
    out, n = fn(x, 3)
    assert n == 3 and out.tolist() == [2 * i + 1 for i in range(8)]
    assert fn.__name__ == "srt_anatomy_scope"


_SCOPED_OPS = {
    "filter": {"op": "filter", "mask": 1},
    "groupby": {"op": "groupby", "by": [0],
                "aggs": [{"column": 0, "agg": "count"}]},
    "sort_by": {"op": "sort_by", "keys": [{"column": 0}]},
    "join": {"op": "join", "on": [0]},
}


@pytest.mark.parametrize("op", sorted(_SCOPED_OPS))
def test_bucketed_runner_ops_carry_their_scope(op, monkeypatch):
    """Each plan op the resident cell runs through a bucketed runner
    asks ``cached_jit`` for its own ``srt.<op>`` scope, for every
    program it launches."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu import planops
    from spark_rapids_jni_tpu.column import Column, Table
    from spark_rapids_jni_tpu.utils import buckets

    scopes = []
    real = buckets.cached_jit

    def spy(key, build, name, donate_args=(), scope=None):
        scopes.append((name, scope))
        return real(key, build, name, donate_args=donate_args, scope=scope)

    monkeypatch.setattr(buckets, "cached_jit", spy)
    keys = Column(jnp.arange(40, dtype=jnp.int64) % 7, dt.INT64)
    mask = Column(jnp.arange(40) % 2 == 0, dt.BOOL8)
    rest = [Table([Column(jnp.arange(7, dtype=jnp.int64), dt.INT64)])]
    config.set_flag("METRICS", True)
    try:
        metrics.reset()
        planops.dispatch(_SCOPED_OPS[op], Table([keys, mask]),
                         rest if op == "join" else ())
        assert metrics.snapshot()["counters"]["bucket.dispatched"] == 1
    finally:
        config.clear_flag("METRICS")
    assert scopes and {s for _, s in scopes} == {"srt." + op}


def test_mesh_recv_in_the_session_doc(anatomy):
    """The stage's planned receive rows per device: the rows the filter
    kept, over the four devices."""
    run = anatomy["mesh"]
    recv = run["session"]["mesh_recv"]
    assert len(recv["rows"]) == 4 and all(r > 0 for r in recv["rows"])
    assert sum(recv["rows"]) == run["kept"]
    mean = run["kept"] / 4
    assert recv["imbalance"] == pytest.approx(max(recv["rows"]) / mean)
    assert 1.0 <= recv["imbalance"] < 2.0
    assert "mesh_recv" not in anatomy["stream"]["session"]


def test_mesh_path_is_inside_the_plan_span(anatomy):
    stage = [s for s in anatomy["mesh"]["spans"] if s["leaf"] == "mesh.stage"]
    assert stage[0]["name"].endswith(
        "serving.stream/plan/plan.segment.mesh/mesh.stage")
    assert stage[0]["name"].startswith("serving.request/")


def test_span_is_null_with_every_plane_off():
    for f in FLAGS + ("METRICS_DUMP", "PLANSTATS", "PLANSTATS_DIR"):
        config.clear_flag(f)
    assert metrics.span("serving.request") is metrics.NULL_SPAN
    assert metrics.current_span() is None
    with metrics.adopt(None) as a:
        a.credit(1.0)
        assert metrics.span_depth() == 0


def test_adopt_carries_parent_and_credits_child_time():
    import threading

    config.set_flag("METRICS", True)
    try:
        metrics.reset()
        with metrics.span("serving.request") as parent:
            def work():
                with metrics.adopt(parent) as a:
                    a.credit(0.05)
                    with metrics.span("serving.plan") as child:
                        time.sleep(0.02)
                        assert child.qualname == (
                            "serving.request/serving.plan")
                assert metrics.span_depth() == 0

            t = threading.Thread(target=work)
            t.start()
            t.join()
        snap = metrics.snapshot()
        total = snap["timers"]["serving.request"]["total_s"]
        # 0.05 s credited + 0.02 s of child: more than the parent took
        assert snap["span_self"]["serving.request"]["self_s"] == 0.0
        assert total >= 0.02
    finally:
        config.clear_flag("METRICS")
        metrics.reset()


def test_request_span_is_in_a_cpu_profile(tmp_path):
    """Under ``jax.profiler.trace`` the program's spans land in the
    xplane, on their threads' lines of the host plane."""
    from jax.profiler import ProfileData

    config.set_flag("METRICS", True)
    try:
        with serving.serve(workers=1) as srv, \
                serving.Client(srv.port, timeout=300.0) as c:
            batch, _ = _fact(2_000, 8)
            c.free(c.upload(batch))
            with jax.profiler.trace(str(tmp_path)):
                tid = c.upload(batch)
                assert c.download(tid)[4] == 2_000
    finally:
        config.clear_flag("METRICS")
        metrics.reset()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    by_line = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("srt/"):
                    by_line.setdefault((plane.name, line.name), set()).add(
                        e.name)
    names = set().union(*by_line.values()) if by_line else set()
    assert "srt/serving.request" in names, sorted(names)
    assert "srt/client.rpc" in names
    assert "srt/serving.request/serving.upload/wire.deserialize" in names
    assert all(p.startswith("/host:") for p, _ in by_line)
