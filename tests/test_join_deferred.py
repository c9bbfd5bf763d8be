"""An inner join on a unique, directly addressed build key moves no row.

Inside a fused segment (``plan._run_segment_traced``) an ``inner`` join
whose build side shows one integer-family key column, dense and with no
repeated valid value, is a selection plus a row-local lookup
(``ops.join.lookup_unique``): its match bit joins the occupancy mask a
deferred filter already joins, and the groupby tail's sort puts the
unmatched rows last with the padding. The choice is read from the build
side before the plan is segmented (``plan._selecting_joins`` ->
``plancheck.predict_segments``). Held here, a case each so each counts:

* ``filter -> join -> groupby`` and ``join -> project -> groupby`` (a
  group-by and aggregates over BUILD-side columns) equal the exact path
  byte for byte, FLOAT64 sums included, over null keys on either side,
  a shuffled or offset build side, a padded probe side and an empty
  match, with ``join.deferred`` ticking and ``plan.fallbacks`` at 0;
* every join that does not qualify — a repeated, sparse, two-column or
  string key, ``left`` / ``semi`` / ``anti``, a tail that is not a
  groupby, an op behind the join that needs the prefix — segments
  exactly as with nothing known of its build side, ticks
  ``join.materialised`` and gives the same bytes;
* a failure inside the fused segment replays per-op with the build
  table the per-op path would have taken;
* the lowered text of a fused join holds one probe-wide gather for the
  match and one a word of each build column something reads.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import bucketed, dtype as dt, plancheck
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import buckets, config, metrics

from test_join_direct_probe import _scatters
from test_plan import _string_wire

I32, I64 = int(dt.TypeId.INT32), int(dt.TypeId.INT64)
F64, B8 = int(dt.TypeId.FLOAT64), int(dt.TypeId.BOOL8)
STR = int(dt.TypeId.STRING)

KEYS = 100  # the probe side's keys lie in [0, KEYS)
SIZES = (1500, 1024)  # a padded tail, and a whole bucket


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


def _device(cols, n):
    return rb._table_from_wire(
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n, None,
    )


def _valid(rng, n, share=0.1):
    return (rng.random(n) >= share).astype(np.uint8).tobytes()


def _fact(n, null_keys=False):
    """item INT64, store INT64, qty INT64 with nulls, price FLOAT64 with
    nulls, the BOOL8 mask, a STRING of the item."""
    rng = np.random.default_rng([n, null_keys])
    item = rng.integers(0, KEYS, n, dtype=np.int64)
    store = rng.integers(0, 4, n, dtype=np.int64)
    qty = rng.integers(1, 100, n, dtype=np.int64)
    price = rng.standard_normal(n) * 10.0 ** rng.integers(-3, 9, n)
    return [
        (I64, 0, item.tobytes(), _valid(rng, n) if null_keys else None),
        (I64, 0, store.tobytes(), None),
        (I64, 0, qty.tobytes(), _valid(rng, n)),
        (F64, 0, price.tobytes(), _valid(rng, n)),
        (B8, 0, (qty > 20).astype(np.uint8).tobytes(), None),
        (STR, 0, _string_wire([f"i{int(x)}" for x in item]), None),
    ]


def _dim(kind):
    """-> (columns, rows): the key, ``cat`` INT32 with nulls, ``weight``
    FLOAT64, and for the two-column and string cases a second key."""
    rng = np.random.default_rng(sorted(BUILDS).index(kind))
    keys = np.sort(rng.choice(KEYS, 66, replace=False)).astype(np.int64)
    key_valid = None
    if kind == "shuffled":
        keys = rng.permutation(keys)
    elif kind == "offset":  # kmin far from 0, below and above the probe's
        keys = keys - 40
    elif kind == "no_match":
        keys = keys + 1000
    elif kind == "null_build_keys":
        # two nulls hold the same value: nulls never count as repeats
        keys[5] = keys[6]
        key_valid = np.ones(66, np.uint8)
        key_valid[[5, 17]] = 0
        key_valid = key_valid.tobytes()
    elif kind == "repeated":
        keys[7] = keys[8]
    elif kind == "sparse":
        keys = keys * 50
    m = len(keys)
    cat = (keys * 7 % 5).astype(np.int32)
    weight = rng.standard_normal(m) * 1e3
    cols = [
        (I64, 0, keys.tobytes(), key_valid),
        (I32, 0, cat.tobytes(), _valid(rng, m, 0.2)),
        (F64, 0, weight.tobytes(), None),
    ]
    if kind == "two_columns":
        cols.insert(1, (I64, 0, (keys % 4).tobytes(), None))
    if kind == "string":  # at the index the fact's has behind a filter
        cols[3:] = [(I64, 0, keys.tobytes(), None),
                    (STR, 0, _string_wire([f"i{int(x)}" for x in keys]), None)]
    return cols, m


# build side -> does an inner join on it select?
BUILDS = {
    "unique_dense": True, "shuffled": True, "offset": True,
    "no_match": True, "null_build_keys": True,
    "repeated": False, "sparse": False, "two_columns": False,
    "string": False,
}

_AGGS = [{"column": 2, "agg": "sum"}, {"column": 2, "agg": "count"},
         {"column": 3, "agg": "sum"}]
FILTER = {"op": "filter", "mask": 4}
GROUP = {"op": "groupby", "by": [0], "aggs": _AGGS}
SORT = {"op": "sort_by", "keys": [{"column": 0}]}


def _join(kind="unique_dense", how=None):
    on = {"two_columns": [0, 1], "string": [4]}.get(kind, [0])
    op = {"op": "join", "on": on}
    if how:
        op["how"] = how
    return op


def _c(i):
    return {"col": i}


def _by_build_columns(kind="unique_dense"):
    """``join -> project -> groupby``: grouped by the dimension's
    ``cat`` (q42/q52/q55 group by an item attribute), a sum over the
    dimension's ``weight`` and over ``qty x weight`` beside the fact's."""
    # the join's output: the fact's six, then cat (6) and weight (7)
    return [
        _join(kind),
        {"op": "project", "exprs": [
            _c(6), _c(2), _c(3), _c(7),
            {"binary": "mul", "left": {"cast": _c(2), "type_id": F64},
             "right": _c(7)}]},
        {"op": "groupby", "by": [0], "aggs": [
            {"column": 1, "agg": "sum"}, {"column": 2, "agg": "sum"},
            {"column": 3, "agg": "sum"}, {"column": 4, "agg": "sum"},
            {"column": 3, "agg": "count"}]},
    ]


def _run(ops, fact, n, rest, flag=""):
    """-> (the result's wire bytes, the counters the plan moved)."""
    config.set_flag("BUCKETS", flag)
    config.set_flag("METRICS", True)
    table = _device(fact, n)
    tables = [_device(cols, m) for cols, m in rest]
    metrics.reset()
    out = rb._table_to_wire(plan_mod.run_plan(ops, table, tables))
    return out, metrics.snapshot()["counters"]


def _segments(ops, fact, n, rest):
    """The segmentation ``plan._run_segments`` runs for these tables."""
    config.set_flag("BUCKETS", "")
    join_selects, _ = plan_mod._selecting_joins(
        ops, _device(fact, n), tuple(_device(c, m) for c, m in rest))
    return plancheck.predict_segments(ops, join_selects)


# ---------------------------------------------------------------------------
# a join that selects: the same bytes, and it rode the segment
# ---------------------------------------------------------------------------

FUSING = sorted(k for k, fuses in BUILDS.items() if fuses)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("null_keys", [False, True],
                         ids=["", "null_probe_keys"])
@pytest.mark.parametrize("kind", FUSING)
def test_filter_join_groupby_equals_exact(kind, null_keys, n):
    ops = [FILTER, _join(), GROUP, SORT]
    fact, rest = _fact(n, null_keys), [_dim(kind)]
    fused, c = _run(ops, fact, n, rest)
    # parity means nothing if the segment fell back to per-op replay
    assert "plan.fallbacks" not in c and c["plan.fused_ops"] == 3
    assert (c["join.deferred"], c["filter.deferred"]) == (1, 1)
    assert c["join.probe.direct"] == 1 and "join.materialised" not in c
    exact, _ = _run(ops, fact, n, rest, flag="off")
    # byte-identical 5-tuples: group order, integer AND float64 sums
    # (the same bits, not a tolerance), validity, row counts
    assert fused == exact
    assert (fused[4] == 0) == (kind == "no_match")


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kind", FUSING)
def test_group_and_sum_over_build_columns_equal_exact(kind, n):
    ops = _by_build_columns()
    fact, rest = _fact(n, null_keys=True), [_dim(kind)]
    fused, c = _run(ops, fact, n, rest)
    assert "plan.fallbacks" not in c and c["plan.fused_ops"] == 3
    assert c["join.deferred"] == 1 and c["project.calls"] == 1
    exact, _ = _run(ops, fact, n, rest, flag="off")
    assert fused == exact


def test_two_joins_ride_one_segment():
    # item's dimension, then store's (its key is column 1 on both sides)
    n = 1500
    store = np.array([2, 0, 3], np.int64)
    second = ([(F64, 0, (store * 1.5).tobytes(), None),
               (I64, 0, store.tobytes(), None)], 3)
    ops = [FILTER, _join(), {"op": "join", "on": [1]},
           {"op": "groupby", "by": [5], "aggs": [
               {"column": 7, "agg": "sum"}, {"column": 2, "agg": "sum"}]}]
    fact, rest = _fact(n), [_dim("unique_dense"), second]
    assert _segments(ops, fact, n, rest) == [("fused", [0, 1, 2, 3])]
    fused, c = _run(ops, fact, n, rest)
    assert "plan.fallbacks" not in c and c["join.deferred"] == 2
    exact, _ = _run(ops, fact, n, rest, flag="off")
    assert fused == exact


# ---------------------------------------------------------------------------
# a join that does not qualify: segmentation, counters and bytes as before
# ---------------------------------------------------------------------------

NOT_FUSING = {
    **{kind: ([FILTER, _join(kind), GROUP], kind)
       for kind, fuses in BUILDS.items() if not fuses},
    **{how: ([FILTER, _join(how=how), GROUP], "unique_dense")
       for how in ("left", "semi", "anti")},
    "sort_tail": ([FILTER, _join(), SORT], "unique_dense"),
    "join_tail": ([FILTER, _join()], "unique_dense"),
    "behind_a_slice": (
        [FILTER, _join(), {"op": "slice", "start": 0, "stop": 900}, GROUP],
        "unique_dense"),
    "behind_a_sort": ([FILTER, _join(), SORT, GROUP], "unique_dense"),
    "before_an_unfit_join": (
        [_join(), _join("repeated"), GROUP], "unique_dense"),
}


@pytest.mark.parametrize("case", sorted(NOT_FUSING))
def test_a_join_that_does_not_qualify_stays_a_boundary(case):
    n = 1500
    ops, kind = NOT_FUSING[case]
    fact = _fact(n)
    rest = [_dim(kind)]
    if case == "before_an_unfit_join":
        rest.append(_dim("repeated"))
    want = plancheck.predict_segments(ops)
    assert plan_mod.segment_plan(ops) == [
        (k, [ops[i] for i in idxs]) for k, idxs in want]
    assert _segments(ops, fact, n, rest) == want
    got, c = _run(ops, fact, n, rest)
    assert "plan.fallbacks" not in c and "join.deferred" not in c
    joins = sum(o["op"] == "join" for o in ops)
    assert c["join.materialised"] == joins
    assert c["plan.segments"] == len(want)
    # a filter in front of a boundary is a segment of its own
    assert c.get("filter.compacted", 0) == (ops[0] is FILTER)
    exact, _ = _run(ops, fact, n, rest, flag="off")
    assert got == exact


@pytest.mark.parametrize("keys,probe_rows,rides,served", [
    # the resident query's shape: 6,666 of [0, 10,000) in a 2^13-row
    # dimension under 2^23 fact rows
    ("dense", 1 << 23, 1 << 14, (1 << 14, False, True)),
    # the same key 50 apart: the table is 64 x the dimension's bucket,
    # still narrower than the fact side. `_r_join` addresses it (PR
    # 41: a scatter fills it whatever the span), a segment does not
    # carry it (riding is priced by the build columns' gathers and the
    # groupby's sort at the probe's width, not by the table)
    ("sparse", 1 << 23, None, (1 << 19, False, True)),
    # dense, under a probe side narrower than the table
    ("dense", 1 << 13, None, (1 << 14, False, True)),
])
def test_a_unique_key_rides_only_within_the_two_limits(
    keys, probe_rows, rides, served
):
    rng = np.random.default_rng(6)
    k = np.sort(rng.choice(10_000, 6_666, replace=False)).astype(np.int64)
    if keys == "sparse":
        k = k * 50
    rt = bucketed._padded_input(
        _device([(I64, 0, k.tobytes(), None)], len(k)))
    assert rt.row_count == 1 << 13
    assert bucketed.selecting_table_size(_join(), rt, probe_rows) == rides
    assert bucketed._probe_choice(rt, rt, [0]) == served


@pytest.mark.parametrize("ops", [
    [FILTER, _join(), GROUP],
    [_join(), GROUP],
    _by_build_columns(),
    [FILTER, _join(), GROUP, SORT],
], ids=["filter_join_groupby", "join_groupby", "join_project_groupby",
        "resident_query"])
def test_nothing_known_of_the_build_side_leaves_the_join_a_boundary(ops):
    want = plancheck.predict_segments(ops)
    assert all(
        (kind, len(idxs)) == ("exact", 1)
        for kind, idxs in want if any(ops[i]["op"] == "join" for i in idxs))
    assert plancheck.predict_segments(ops, None) == want
    assert plancheck.predict_segments(ops, lambda i, op: False) == want
    # analyze reports the static prediction
    report = plancheck.analyze(ops)
    assert [(s["kind"], s["ops"]) for s in report["segments"]] == want


@pytest.mark.parametrize("ops,asked,riding", [
    ([FILTER, _join(), GROUP], [1], [("fused", [0, 1, 2])]),
    ([_join(), GROUP, SORT], [0], [("fused", [0, 1]), ("exact", [2])]),
    ([FILTER, _join(), SORT], [], None),
    ([FILTER, _join(how="left"), GROUP], [1], None),
    ([_join(), SORT, _join(), GROUP], [2],
     [("exact", [0]), ("fused", [1, 2, 3])]),
], ids=["rides", "rides_then_sorts", "sort_tail_never_asked",
        "left_says_no", "only_the_join_that_reaches_the_groupby"])
def test_the_segmenter_asks_only_joins_that_could_ride(ops, asked, riding):
    seen = []

    def join_selects(i, op):
        seen.append(i)
        return op.get("how", "inner") == "inner"

    got = plancheck.predict_segments(ops, join_selects)
    assert seen == asked
    assert got == (riding or plancheck.predict_segments(ops))


@pytest.mark.parametrize("ops,start", [
    ([FILTER, _join(), GROUP], 0),
    ([_join(), GROUP], 0),
    (_by_build_columns(), 0),
    ([FILTER, SORT, _join(), GROUP], 2),
    ([FILTER, _join(), SORT], 3),
], ids=["filter_join_groupby", "join_groupby", "join_project_groupby",
        "behind_a_sort", "sort_tail"])
def test_deferred_from_reaches_through_a_join(ops, start):
    assert planops.deferred_from(ops) == start
    # ... without the join becoming chunkable or shardable
    assert not planops.OPS["join"].row_local
    assert not planops.op_fusable(_join())


# ---------------------------------------------------------------------------
# the counters, warm: counted at launch, not at trace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,want", [
    ("unique_dense", (1, 0)), ("repeated", (0, 1)), ("sparse", (0, 1)),
])
def test_counters_say_which_way_the_join_went(kind, want):
    n = 700
    ops = [FILTER, _join(), GROUP]
    for _ in range(2):  # the second launch finds the executable cached
        _, c = _run(ops, _fact(n), n, [_dim(kind)])
        assert (c.get("join.deferred", 0),
                c.get("join.materialised", 0)) == want


def test_the_exact_path_counts_neither():
    n = 700
    _, c = _run([FILTER, _join(), GROUP], _fact(n), n,
                [_dim("unique_dense")], flag="off")
    assert "join.deferred" not in c and "join.materialised" not in c


# ---------------------------------------------------------------------------
# a failure inside the fused segment: per-op replay, the right build table
# ---------------------------------------------------------------------------


def test_fused_failure_replays_per_op_with_its_build_table(monkeypatch):
    n = 1500
    # the plan's second join must still find the SECOND rest table
    ops = [FILTER, _join(), GROUP, {"op": "join", "on": [0]}]
    fact = _fact(n)
    rest = [_dim("unique_dense"), _dim("shuffled")]
    want, c = _run(ops, fact, n, rest)
    assert c["join.deferred"] == 1 and "plan.fallbacks" not in c

    def boom(op, t, rv, build):
        raise RuntimeError("injected fused failure")

    monkeypatch.setitem(
        planops.OPS, "join",
        dataclasses.replace(planops.OPS["join"], select=boom),
    )
    buckets.cache_clear()  # a warm cache never reaches the patched body
    got, c = _run(ops, fact, n, rest)
    assert got == want
    assert c["plan.fallbacks"] == 1 and "join.deferred" not in c
    assert c["join.materialised"] == 2
    # the other order of the two dimensions is another answer
    other, _ = _run(ops, fact, n, rest[::-1])
    assert other != want


def test_a_probe_key_the_table_cannot_address_declines_to_per_op():
    # the build key is addressable, the probe's FLOAT64 is not: the
    # segment declines at trace and the per-op path answers
    n = 1500
    fact = _fact(n)
    fact[0] = (F64, 0, np.arange(n, dtype=np.float64).tobytes(), None)
    ops = [_join(), GROUP]
    rest = [_dim("unique_dense")]
    got, c = _run(ops, fact, n, rest)
    assert c["plan.declined"] == 1 and "plan.fallbacks" not in c
    assert c["join.probe.search"] == 1 and "join.deferred" not in c
    exact, _ = _run(ops, fact, n, rest, flag="off")
    assert got == exact


# ---------------------------------------------------------------------------
# the lowered text: what a fused join gathers at the probe's width
# ---------------------------------------------------------------------------


def _wide_gathers(ops, n=1500):
    config.set_flag("BUCKETS", "")
    pt = bucketed._padded_input(_device(_fact(n), n))
    cols, m = _dim("unique_dense")
    rt = bucketed._padded_input(_device(cols, m))
    size = bucketed.selecting_table_size(_join(), rt, 4 * pt.row_count)
    assert size == rt.row_count == 1024
    # the probe side at a width of its own, so that a gather's output
    # width says which side it serves
    pt = buckets.pad_table(buckets.unpad_table(pt), 4 * pt.row_count)
    text = jax.jit(
        lambda t, k, r, rk: plan_mod._run_segment_traced(
            ops, t, k, [(r, rk, size)])
    ).lower(
        bucketed._strip(pt), bucketed._n_dev(pt),
        bucketed._strip(rt), bucketed._n_dev(rt),
    ).as_text()
    assert "stablehlo.sort" in text
    # no compaction: the one scatter fills the probe's table (PR 41)
    assert _scatters(text) == [(size, rt.row_count)]
    return len(re.findall(
        rf"stablehlo\.gather.*-> tensor<{pt.row_count}x", text))


def test_fused_join_gathers_once_when_no_build_column_is_read():
    # the match; jit's dead-code elimination drops cat's and weight's
    assert _wide_gathers([FILTER, _join(), GROUP]) == 1


def test_fused_join_gathers_once_more_a_build_column_read():
    # cat: data and validity; weight: data (its bits_to_f64 reads are
    # the groupby's, and on the CPU no table lookup)
    assert _wide_gathers(_by_build_columns()) == 1 + 2 + 1
