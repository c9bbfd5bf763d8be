"""TPC-H Q3 through the served path: perfbench's ``tpch-q3-part`` request
(three served plans: the filtered customer side; orders filtered, joined
to it and reordered; lineitem filtered, joined to that, the revenue
product, a three-key aggregate and the top 10), on seeded tables at the
configuration's rehearsal sizes.

What is held here: the request, driven the way the benchmark drives it
(``perfbench.script.Session`` over ``serving.Client``), equals the plain
reference exactly on three seeds; both joins take ``bucketed._r_join``
(the order key is sparse, so the lineitem join searches, over one u32
word a side; the customer join's build side is the customer plan's
output at the customer table's bucket, a span its table holds, so it is
addressed; neither rides a fused segment); the three row counters tick
with the right rows; ``plancheck`` predicts the segments that run and
infers the joined schemas through ``project`` and the three-key
``groupby``; the float32 control is refused.
"""

import json
import os

import numpy as np
import pytest

from perfbench import compare, reference, script
from perfbench.wirefmt import TYPE_IDS, table_rows, wire
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import bucketed, plancheck, serving
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
I32, I64, D64 = (TYPE_IDS[k] for k in ("INT32", "INT64", "DECIMAL64"))


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "tpch-q3-part.json")
TRAFFIC = load("traffic", "q3-resident.json")
PLAN_A, PLAN_B, PLAN_C = [
    s["plan"] for s in TRAFFIC["request"] if s["do"] == "plan"]
SEEDS = [3, 1234567891, 2147483659]

SEGMENT_TIMERS = {
    "plan.segment.project__filter": 3, "plan.segment.join": 2,
    "plan.segment.project": 1, "plan.segment.project__groupby": 1,
    "plan.segment.sort_by__slice": 1,
}
COUNTERS = [
    "project.calls", "join.probe.search", "join.probe.direct",
    "join.probe.narrow", "join.materialised", "join.deferred", "join.probe_rows",
    "join.build_rows", "join.output_rows", "join.table_entries",
    "filter.compacted",
    "filter.deferred", "plan.calls", "plan.segments", "plan.fallbacks",
    "bucket.fallback_errors", "bucket.declined", "kernel.fallbacks",
]


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("METRICS")


def _timer_counts(names):
    timers = metrics.snapshot()["timers"]
    return {n: timers.get(n, {"count": 0})["count"] for n in names}


def _serve_one_request(seed):
    """One request the way ``perfbench.run`` sends it -> (answers, what
    the reference says, counter and timer-count deltas, the tables)."""
    config.set_flag("METRICS", True)
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=True)
    timed = list(SEGMENT_TIMERS) + ["plan"]
    with serving.Server(workers=2).start() as srv:
        with serving.Client(srv.port, timeout=600.0) as c:
            s = script.Session(c, data, TRAFFIC["request"])
            s.upload_resident()
            c0, t0 = metrics.counter_values(COUNTERS), _timer_counts(timed)
            got = s.request(1)
            c1, t1 = metrics.counter_values(COUNTERS), _timer_counts(timed)
    env = data.env(1)
    want = reference.run_request(TRAFFIC["request"], env)
    return (got, want, {k: c1[k] - c0[k] for k in COUNTERS},
            {k: t1[k] - t0[k] for k in timed}, env)


@pytest.mark.parametrize("seed", SEEDS)
def test_q3_request_equals_the_reference(seed):
    got, want, moved, spans, env = _serve_one_request(seed)
    assert sorted(got) == sorted(want) == ["result"]
    r = compare.compare(got["result"], want["result"],
                        TRAFFIC["answers"]["result"], 0.0)
    assert r["mismatched"] == 0, [c.values.tolist() for c in got["result"]]
    result = got["result"]
    assert [(c.type, c.scale) for c in result] == [
        ("INT64", 0), ("INT32", 0), ("INT32", 0), ("DECIMAL64", -4)]
    assert table_rows(result) == CONFIG["query"]["result_rows"]
    revenue = result[3].values
    assert (np.diff(revenue) <= 0).all() and revenue[0] > revenue[-1]
    # the daemon evaluated the predicates and the product, and moved the rows
    assert moved["project.calls"] == TRAFFIC["expect_counters"]["project.calls"] == 5
    assert moved["plan.calls"] == 3 and moved["plan.segments"] == 8
    assert moved["join.materialised"] == 2 and moved["join.deferred"] == 0
    # both keys are addressed (PR 41): the customer key's span (every
    # customer) and the sparse order key's, many times its rows, each
    # through a table one scatter of the build rows fills
    assert (moved["join.probe.search"], moved["join.probe.narrow"],
            moved["join.probe.direct"]) == (0, 0, 2)
    assert moved["filter.compacted"] == 3 and moved["filter.deferred"] == 0
    for k in ("plan.fallbacks", "bucket.fallback_errors", "bucket.declined",
              "kernel.fallbacks"):
        assert moved[k] == 0, k
    assert spans == dict(SEGMENT_TIMERS, plan=3)
    # logical rows of both joins' sides and results, by the reference
    building = reference.run_plan(PLAN_A, [env["customer"]])
    kept_orders = reference.run_plan(PLAN_B[:2], [env["orders"]])
    open_orders = reference.run_plan(PLAN_B, [env["orders"], building])
    kept_lines = reference.run_plan(PLAN_C[:2], [env["lineitem"]])
    joined = reference.run_plan(PLAN_C[:3], [env["lineitem"], open_orders])
    assert moved["join.probe_rows"] == table_rows(kept_orders) + table_rows(kept_lines)
    assert moved["join.build_rows"] == table_rows(building) + table_rows(open_orders)
    assert moved["join.output_rows"] == table_rows(open_orders) + table_rows(joined)
    assert table_rows(joined) > 0
    assert moved["join.table_entries"] == sum(
        max(1024, 1 << int(k.max() - k.min()).bit_length())
        for k in (building[0].values, open_orders[0].values))


def test_the_sparse_order_key_is_addressed_and_stays_a_boundary():
    """The lineitem join's build side, as the served path sees it: the
    span of its keys is many times their count, wider than both sides,
    and unique. The one choice of the probe answers a table as wide as
    the span with a row a key (PR 41), and the segmenter still leaves
    the join a boundary (`bucketed.selecting_table_size` None: riding a
    fused segment is priced differently, `PERF.md` §7)."""
    data = script.Data(CONFIG, TRAFFIC, 7, rehearse=True)
    env = data.env(0)
    building = reference.run_plan(PLAN_A, [env["customer"]])
    open_orders = reference.run_plan(PLAN_B, [env["orders"], building])
    keys = open_orders[0].values
    assert (keys.max() - keys.min() + 1) >= 16 * len(keys)
    build = rb._table_from_wire(*wire(open_orders), None)
    probe = rb._table_from_wire(*wire(reference.run_plan(PLAN_C[:2], [env["lineitem"]])), None)
    assert bucketed.selecting_table_size(PLAN_C[2], build, probe.row_count) is None
    size = 1 << int(keys.max() - keys.min()).bit_length()
    assert size > max(probe.row_count, 2 * build.row_count)
    assert bucketed._probe_choice(probe, build, [0]) == (size, False, True)


def _schema(table):
    return plancheck.schema_from_wire(
        [TYPE_IDS[c.type] for c in table], [c.scale for c in table])


def _pairs(report):
    return [(c["type_id"], c["scale"]) for c in report["out_schema"]]


def test_plancheck_predicts_the_segments_and_the_joined_schemas():
    data = script.Data(CONFIG, TRAFFIC, 11, rehearse=True)
    env = data.env(0)
    cust, orders, lines = env["customer"], env["orders"], env["lineitem"]
    want_segments = {
        "A": [("fused", "project__filter")],
        "B": [("fused", "project__filter"), ("exact", "join"), ("exact", "project")],
        "C": [("fused", "project__filter"), ("exact", "join"),
              ("fused", "project__groupby"), ("fused", "sort_by__slice")],
    }
    for name, ops in (("A", PLAN_A), ("B", PLAN_B), ("C", PLAN_C)):
        assert [(k, plan_mod.segment_sig(s)) for k, s in plan_mod.segment_plan(ops)] \
            == want_segments[name]
        # and no join is predicted to ride a segment, whatever is asked
        asked = []
        plancheck.predict_segments(ops, lambda i, op: asked.append(i) or False)
        assert asked == ([2] if name == "C" else [])
    a = plancheck.check_plan(PLAN_A, schema=_schema(cust), rows=table_rows(cust))
    assert _pairs(a) == [(I64, 0)]
    sa = plancheck.schema_from_wire(*zip(*_pairs(a)))
    b = plancheck.check_plan(PLAN_B, schema=_schema(orders), rows=table_rows(orders),
                             rest=[(sa, table_rows(cust))])
    assert _pairs(b) == [(I64, 0), (I32, 0), (I32, 0)]
    sb = plancheck.schema_from_wire(*zip(*_pairs(b)))
    c = plancheck.check_plan(PLAN_C, schema=_schema(lines), rows=table_rows(lines),
                             rest=[(sb, table_rows(orders))])
    assert _pairs(c) == [(I64, 0), (I32, 0), (I32, 0), (D64, -4)]
    assert [(s["kind"], len(s["ops"])) for s in c["segments"]] == [
        ("fused", 2), ("exact", 1), ("fused", 2), ("fused", 2)]


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_q3_float32_control_is_refused(seed):
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=True)
    env = data.env(0)
    want = reference.run_request(TRAFFIC["request"], env)["result"]
    low = reference.run_request(TRAFFIC["request"], env, lowprec=True)["result"]
    r = compare.compare(low, want, TRAFFIC["answers"]["result"], 0.0)
    assert r["mismatched"] > 0
    # the keys and dates that stay in place are exact: the products moved
    assert not np.array_equal(low[3].values, want[3].values)
