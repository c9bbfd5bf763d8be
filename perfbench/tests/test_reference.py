"""The plain reference's own pieces, against values known from outside."""

import numpy as np
import pytest

from perfbench import compare, reference
from perfbench.wirefmt import Col, unwire, wire


def test_murmur3_long_matches_spark():
    # Spark SQL: SELECT hash(0L), hash(1L), hash(-1L) -> seed 42
    got = reference.murmur3_long(np.array([0, 1, -1], np.int64))
    assert got.tolist() == [-1670924195, -1712319331, -939490007]


def table():
    return [
        Col("INT64", 0, np.array([3, 1, 3, 2, 1, 3], np.int64)),
        Col("INT64", 0, np.array([10, 20, 30, 40, 50, 60], np.int64)),
        Col("FLOAT64", 0, np.array([0.5, 1.5, 2.5, 3.5, 4.5, 5.5])),
        Col("BOOL8", 0, np.array([1, 1, 0, 1, 1, 1], np.uint8)),
    ]


PLAN = [
    {"op": "filter", "mask": 3},
    {"op": "groupby", "by": [0], "aggs": [
        {"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"},
        {"column": 2, "agg": "sum"}]},
    {"op": "sort_by", "keys": [{"column": 1, "ascending": False},
                               {"column": 0}]},
]


def test_filter_groupby_sort_by_hand():
    out = reference.run_plan(PLAN, [table()])
    assert [c.values.tolist() for c in out] == [
        [1, 3, 2], [70, 70, 40], [2, 2, 1], [6.0, 6.0, 3.5]]


def test_join_keeps_left_columns_then_right_non_keys():
    dim = [Col("INT64", 0, np.array([1, 3], np.int64)),
           Col("INT64", 0, np.array([100, 300], np.int64))]
    out = reference.run_plan([{"op": "join", "on": [0]}], [table(), dim])
    assert len(out) == 5
    assert sorted(zip(out[0].values.tolist(), out[4].values.tolist())) == [
        (1, 100), (1, 100), (3, 300), (3, 300), (3, 300)]


def test_partition_is_a_stable_reorder_by_spark_partition_id():
    t = [Col("INT64", 0, np.arange(20, dtype=np.int64)),
         Col("INT64", 0, np.arange(20, dtype=np.int64) * 10)]
    out = reference.run_plan(
        [{"op": "partition", "kind": "hash", "keys": [0], "num": 4}], [t])
    h = reference.murmur3_long(out[0].values).astype(np.int64)
    pid = ((h % 4) + 4) % 4
    assert np.all(np.diff(pid) >= 0)
    for p in range(4):  # stable: source order kept inside a partition
        assert np.all(np.diff(out[0].values[pid == p]) > 0)
    assert np.array_equal(out[1].values, out[0].values * 10)


def test_nulls_in_a_plan_are_refused_not_guessed():
    t = table()
    t[1].valid = np.array([1, 0, 1, 1, 1, 1], bool)
    with pytest.raises(ValueError):
        reference.run_plan(PLAN, [t])


def test_wire_round_trip_and_packed_rows():
    t = table()
    t[2].valid = np.array([1, 1, 0, 1, 1, 1], bool)
    back = unwire(wire(t))
    assert compare.compare(back, t, {}, 0.0)["mismatched"] == 0
    rows = reference.run_plan([{"op": "to_rows"}], [t])
    (again,) = unwire(wire(rows))
    assert again.type == "LIST" and np.array_equal(again.values, rows[0].values)


@pytest.mark.parametrize("case, want_ok", [
    ("same", True), ("one_integer", False), ("float_inside", True),
    ("float_outside", False), ("validity", False), ("row_missing", False),
])
def test_compare_limits(case, want_ok):
    want = reference.run_plan(PLAN, [table()])
    got = [Col(c.type, c.scale, c.values.copy(), None) for c in want]
    if case == "one_integer":
        got[1].values[2] += 1
    elif case == "float_inside":
        got[3].values[0] += 1e-13
    elif case == "float_outside":
        got[3].values[0] += 1e-9
    elif case == "validity":
        got[2].valid = np.array([True, False, True])
    elif case == "row_missing":
        got = [Col(c.type, c.scale, c.values[:2], None) for c in got]
    spec = {"order": "served", "float64": "sum_tol"}
    folded = compare.fold([compare.compare(got, want, spec, 1e-12)])
    assert folded["ok"] is want_ok


def test_float32_control_is_refused_at_test_size():
    rng = np.random.default_rng(5)
    n = 20000
    t = [Col("INT64", 0, rng.integers(0, 50, n)),
         Col("INT64", 0, rng.integers(1, 100, n)),
         Col("FLOAT64", 0, rng.integers(50, 30000, n) / 100.0),
         Col("BOOL8", 0, np.ones(n, np.uint8))]
    steps = [{"do": "stream", "plan": PLAN[:2], "batches": ["b"],
              "out": ["r"], "answer": True}]
    want = reference.run_request(steps, {"b": t})["r"]
    low = reference.run_request(steps, {"b": t}, lowprec=True)["r"]
    spec = {"order": "by_column_0", "float64": "sum_tol"}
    r = compare.compare(low, want, spec, 1e-12)
    assert r["mismatched"] == 0 and r["f64_err"] > 3 * r["f64_limit"]
