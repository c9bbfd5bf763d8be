"""The ``tpch-lineitem-8m`` pieces, against values known from outside:
the ``lineitem`` generator (TPC-H 4.2.3), the reference's ``project``
and ``slice``, and Q1 whole by hand."""

import json
import os

import numpy as np
import pytest

from perfbench import compare, datagen, reference
from perfbench.plugins import (count_q1_scan_bytes, gen_lineitem_extendedprice,
                               refop_project, refop_slice)
from perfbench.wirefmt import TYPE_IDS, Col

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
D64, I32, B8 = (TYPE_IDS[k] for k in ("DECIMAL64", "INT32", "BOOL8"))


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "tpch-lineitem-8m.json")
TRAFFIC = load("traffic", "q1-resident.json")
NAMES = [c["name"] for c in CONFIG["tables"]["lineitem"]["columns"]]
(Q1,) = [s["plan"] for s in TRAFFIC["request"] if s["do"] == "plan"]


def lineitem(rows, seed):
    t = datagen.make_table(CONFIG["tables"]["lineitem"], rows,
                           np.random.default_rng([seed, 0, 0]))
    return t, dict(zip(NAMES, t))


def test_retail_price_is_the_specification_s():
    # p_retailprice of parts 1, 10, 1000 and 2,000,000 (TPC-H 4.2.3)
    got = gen_lineitem_extendedprice.retail_cents(
        np.array([1, 10, 1000, 2000000]))
    assert got.tolist() == [90100, 91001, 90100, 109991]


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_lineitem_follows_the_population_rules(seed):
    rows = 200000
    t, c = lineitem(rows, seed)
    assert [x.rows for x in t] == [rows] * 9  # every seed the same sizes
    assert [(x.type, x.scale) for x in t] == [
        ("INT64", 0), ("DECIMAL64", -2), ("DECIMAL64", -2), ("DECIMAL64", -2),
        ("DECIMAL64", -2), ("INT32", 0), ("INT32", 0), ("INT8", 0), ("INT8", 0)]
    assert all(x.valid is None for x in t)
    qty, price = c["l_quantity"].values, c["l_extendedprice"].values
    assert qty.min() == 100 and qty.max() == 5000 and not (qty % 100).any()
    assert np.array_equal(price, qty // 100 * gen_lineitem_extendedprice.retail_cents(
        c["l_partkey"].values))
    assert price.max() <= 10495000
    assert c["l_discount"].values.min() == 0 and c["l_discount"].values.max() == 10
    assert c["l_tax"].values.min() == 0 and c["l_tax"].values.max() == 8
    ship, receipt = c["l_shipdate"].values, c["l_receiptdate"].values
    assert 8036 <= ship.min() and ship.max() <= 10561
    lag = receipt.astype(np.int64) - ship
    assert lag.min() == 1 and lag.max() == 30
    flag, status = c["l_returnflag"].values, c["l_linestatus"].values
    assert np.array_equal(status == ord("O"), ship > 9298)
    assert np.array_equal(flag == ord("N"), receipt > 9298)
    assert set(np.unique(flag)) == {ord("A"), ord("N"), ord("R")}
    share = {k: float(np.mean((flag == ord(k[0])) & (status == ord(k[1]))))
             for k in ("AF", "NF", "NO", "RF")}
    assert sum(share.values()) == pytest.approx(1.0)  # four groups, no fifth
    assert 0.004 < share["NF"] < 0.009  # about 0.6% of the rows
    assert abs(share["AF"] - share["RF"]) < 0.01 and 0.45 < share["NO"] < 0.53
    assert 0.975 < float(np.mean(ship <= 10471)) < 0.992  # DELTA 90 keeps ~98%


def test_charge_stays_inside_the_stated_bound():
    # the largest row the rules can make: 50 x 2,099.00 at no discount, 8% tax
    top = [Col("DECIMAL64", -2, np.array([10495000], np.int64)),
           Col("DECIMAL64", -2, np.array([0], np.int64)),
           Col("DECIMAL64", -2, np.array([8], np.int64))]
    one = {"lit": 100, "type_id": D64, "scale": -2}
    disc = {"binary": "mul", "left": {"col": 0},
            "right": {"binary": "sub", "left": one, "right": {"col": 1}}}
    charge = {"binary": "mul", "left": disc,
              "right": {"binary": "add", "left": one, "right": {"col": 2}}}
    (out,) = refop_project.apply({"op": "project", "exprs": [charge]}, [top], False)
    assert (out.type, out.scale) == ("DECIMAL64", -6)
    assert int(out.values[0]) == 113346000000 < 1.14e11
    assert 8000000 * int(out.values[0]) < 9.1e17 < 2 ** 63
    # and a column whose sum could pass 63 bits is refused, not wrapped
    wide = [Col("DECIMAL64", -2, np.full(4, 2 ** 61, np.int64))]
    with pytest.raises(OverflowError):
        refop_project.apply({"op": "project", "exprs": [{"col": 0}]}, [wide], False)
    with pytest.raises(OverflowError):
        refop_project.apply({"op": "project", "exprs": [
            {"binary": "mul", "left": {"col": 0}, "right": {"col": 0}}]},
            [[Col("DECIMAL64", -2, np.array([2 ** 40], np.int64))]], False)


def small():
    return [
        Col("DECIMAL64", -2, np.array([12345, 99999, -505, 700], np.int64),
            np.array([1, 1, 1, 0], bool)),
        Col("DECIMAL64", -2, np.array([5, 0, 9, 1], np.int64)),
        Col("INT32", 0, np.array([10471, 10472, 9000, 1], np.int32)),
        Col("BOOL8", 0, np.array([1, 0, 1, 0], np.uint8),
            np.array([1, 1, 0, 0], bool)),
        Col("INT64", 0, np.array([2, 0, -3, 7], np.int64)),
    ]


def project(*exprs, lowprec=False):
    return refop_project.apply({"op": "project", "exprs": list(exprs)},
                               [small()], lowprec)


def test_project_by_hand():
    one = {"lit": 100, "type_id": D64, "scale": -2}
    col = lambda i: {"col": i}  # noqa: E731
    disc, kept, named, quot, both, either, isnull = project(
        {"binary": "mul", "left": col(0),
         "right": {"binary": "sub", "left": one, "right": col(1)}},
        {"binary": "le", "left": col(2), "right": {"lit": 10471, "type_id": I32}},
        {"binary": "mul", "left": col(0), "right": col(1),
         "type_id": D64, "scale": -2},
        {"binary": "div", "left": col(4), "right": col(4)},
        {"binary": "and", "left": col(3), "right": {"lit": False, "type_id": B8}},
        {"binary": "or", "left": col(3), "right": {"lit": True, "type_id": B8}},
        {"unary": "is_null", "arg": col(0)},
    )
    # 123.45 x 0.95 = 117.2775 exactly, at scale s1 + s2 = -4
    assert (disc.type, disc.scale) == ("DECIMAL64", -4)
    assert disc.values[:3].tolist() == [1172775, 9999900, -45955]
    assert disc.valid.tolist() == [True, True, True, False]
    assert kept.type == "BOOL8" and kept.values.tolist() == [1, 0, 1, 1]
    # the named coarser scale truncates toward zero: 6.1725 -> 6.17, -0.4545 -> -0.45
    assert named.scale == -2 and named.values[:3].tolist() == [617, 0, -45]
    # integer division: toward zero, null on a zero divisor
    assert quot.values[[0, 2, 3]].tolist() == [1, 1, 1]
    assert quot.valid.tolist() == [True, False, True, True]
    # three-valued: false wins over null, true wins over null
    assert both.values.tolist() == [0, 0, 0, 0] and both.valid.all()
    assert either.values.tolist() == [1, 1, 1, 1] and either.valid.all()
    assert isnull.values.tolist() == [0, 0, 0, 1] and isnull.valid is None


def test_project_in_float32_moves_the_products_only():
    e = {"binary": "mul", "left": {"col": 0},
         "right": {"lit": 9999, "type_id": D64, "scale": -2}}
    big = [Col("DECIMAL64", -2, np.array([10494650, 33554433], np.int64))]
    op = {"op": "project", "exprs": [e, {"col": 0}]}
    exact = refop_project.apply(op, [big], False)
    low = refop_project.apply(op, [big], True)
    assert exact[0].values.tolist() == [10494650 * 9999, 33554433 * 9999]
    assert low[0].values.tolist() != exact[0].values.tolist()
    assert np.array_equal(low[1].values, exact[1].values)


def test_slice_clamps_both_bounds():
    t = small()
    out = refop_slice.apply({"op": "slice", "start": 1, "stop": 3}, [t], False)
    assert out[0].values.tolist() == [99999, -505]
    assert out[0].valid.tolist() == [True, True] and out[1].valid is None
    assert refop_slice.apply({"op": "slice", "start": 2}, [t], False)[2].rows == 2
    assert refop_slice.apply({"op": "slice", "start": 9, "stop": 99}, [t], False)[0].rows == 0
    rows = reference.run_plan([{"op": "to_rows"}, {"op": "slice", "stop": 2}],
                              [[c for c in t if c.type != "BOOL8"]])
    assert rows[0].type == "LIST" and rows[0].values.shape[0] == 2


def test_q1_by_hand_and_in_float32():
    t, c = lineitem(20000, 3)
    got = reference.run_plan(Q1, [t])
    keep = c["l_shipdate"].values <= 10471
    price = c["l_extendedprice"].values[keep].astype(object)
    disc = price * (100 - c["l_discount"].values[keep].astype(object))
    charge = disc * (100 + c["l_tax"].values[keep].astype(object))
    flag, status = c["l_returnflag"].values[keep], c["l_linestatus"].values[keep]
    groups = sorted(set(zip(flag.tolist(), status.tolist())))
    assert list(zip(got[0].values.tolist(), got[1].values.tolist())) == groups
    for g, (f, s) in enumerate(groups):
        m = (flag == f) & (status == s)
        assert int(got[2].values[g]) == int(c["l_quantity"].values[keep][m].sum())
        assert int(got[4].values[g]) == sum(disc[m])   # Python integers
        assert int(got[5].values[g]) == sum(charge[m])
        assert int(got[7].values[g]) == int(m.sum())
    assert [(x.type, x.scale) for x in got] == [
        (k, s) for k, s in zip(CONFIG["query"]["result_types"],
                               [0, 0, -2, -2, -4, -6, -2, 0])]
    low = reference.run_plan(Q1, [t], lowprec=True)
    r = compare.compare(low, got, TRAFFIC["answers"]["result"], 0.0)
    assert 0 < r["mismatched"] <= 2 * len(groups)


def test_q1_scan_bytes_are_the_seven_columns_and_the_answer():
    rows = 8000000
    assert count_q1_scan_bytes.count(CONFIG, TRAFFIC, rows) == rows * 38 + 4 * 50
