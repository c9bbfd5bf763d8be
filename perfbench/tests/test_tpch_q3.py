"""The ``tpch-q3-part`` pieces, against values known from outside: the
three tables' generators (TPC-H 4.2.3; what ties ``lineitem`` to
``orders`` and ``orders`` to ``customer``), the least bytes of a Q3, and
Q3 whole by hand."""

import json
import os

import numpy as np
import pytest

from perfbench import compare, reference, script
from perfbench.plugins import (count_q3_scan_bytes, gen_lineitem_extendedprice,
                               gen_q3_orderkey)
from perfbench.wirefmt import Col, table_rows

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "tpch-q3-part.json")
TRAFFIC = load("traffic", "q3-resident.json")
PLAN_A, PLAN_B, PLAN_C = [
    s["plan"] for s in TRAFFIC["request"] if s["do"] == "plan"]
DATE, BUILDING = 9204, 1


def named(env, table):
    return dict(zip([c["name"] for c in CONFIG["tables"][table]["columns"]], env[table]))


def test_order_keys_are_dbgen_s_and_this_partition_s():
    keys = gen_q3_orderkey.partition_keys(5000, 0, 7, 15000000)
    assert (np.diff(keys) > 0).all() and keys.min() >= 1
    # dbgen's sparse keys: 8 of every 32 values
    assert ((keys - 1) % 32 < 8).all()
    pid = np.mod(reference.murmur3_long(keys).astype(np.int64), 7)
    assert (pid == 0).all()
    # and none of the partition's keys below the last is left out
    i = np.arange(int(keys[-1] // 32 + 1) * 8, dtype=np.int64)
    every = (i // 8) * 32 + i % 8 + 1
    mine = every[np.mod(reference.murmur3_long(every).astype(np.int64), 7) == 0]
    assert np.array_equal(mine[:5000], keys)
    lines = gen_q3_orderkey.order_lines(keys)
    assert lines.min() == 1 and lines.max() == 7 and 3.9 < lines.mean() < 4.1
    dates = gen_q3_orderkey.order_date(keys, 8035, 10440)
    assert 8035 <= dates.min() and dates.max() <= 10440


@pytest.mark.parametrize("rehearse,seed", [
    (True, 0), (True, 2147483659), (False, 7), (False, 2147483659)])
def test_the_tables_keep_what_must_hold(rehearse, seed):
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse)
    sizes = CONFIG["rehearse_rows"] if rehearse else {
        k: v["rows"] for k, v in CONFIG["tables"].items()}
    env = data.env(1)
    cust, orders, lines = (named(env, t) for t in ("customer", "orders", "lineitem"))
    # sizes equal for every seed
    for t in ("customer", "orders", "lineitem"):
        assert {c.rows for c in env[t]} == {sizes[t]}
        assert all(c.valid is None for c in env[t])
    assert [(c.type, c.scale) for c in env["lineitem"]] == [
        ("INT64", 0), ("INT64", 0), ("DECIMAL64", -2), ("DECIMAL64", -2),
        ("DECIMAL64", -2), ("INT32", 0)]
    assert [(c.type, c.scale) for c in env["orders"]] == [
        ("INT64", 0), ("INT64", 0), ("INT32", 0), ("INT32", 0)]
    assert [(c.type, c.scale) for c in env["customer"]] == [("INT64", 0), ("INT8", 0)]
    okey, ckey = orders["o_orderkey"].values, cust["c_custkey"].values
    # unique keys; the order key is sparse: its span is 16x its count or more
    assert len(np.unique(okey)) == len(okey) and len(np.unique(ckey)) == len(ckey)
    assert int(okey.max() - okey.min() + 1) >= 16 * len(okey)
    assert ckey.min() == 1 and ckey.max() == len(ckey)
    # referential: every line's order is in the table, every order's customer too
    assert np.isin(lines["l_orderkey"].values, okey).all()
    ocust = orders["o_custkey"].values
    assert np.isin(ocust, ckey).all() and (ocust % 3 != 0).all()
    assert ocust.min() >= 1 and ocust.max() <= len(ckey)
    # an order's lines: 1..7 each, and its date is the one the lines count from
    per_order = np.unique(lines["l_orderkey"].values, return_counts=True)[1]
    assert per_order.min() >= 1 and per_order.max() <= 7
    date_of = dict(zip(okey.tolist(), orders["o_orderdate"].values.tolist()))
    some = np.random.default_rng(1).integers(0, sizes["lineitem"], 2000)
    lag = (lines["l_shipdate"].values[some].astype(np.int64)
           - np.array([date_of[k] for k in lines["l_orderkey"].values[some].tolist()]))
    assert lag.min() >= 1 and lag.max() <= 121
    assert (orders["o_shippriority"].values == 0).all()
    assert set(np.unique(cust["c_mktsegment"].values)) == {0, 1, 2, 3, 4}
    # 4.2.3's price rule, as in tpch-lineitem-8m
    qty, price = lines["l_quantity"].values, lines["l_extendedprice"].values
    assert np.array_equal(price, qty // 100 * gen_lineitem_extendedprice.retail_cents(
        lines["l_partkey"].values))
    assert lines["l_discount"].values.min() == 0 and lines["l_discount"].values.max() == 10
    # the three filters keep 1/5, 1,169/2,406 and ~54%
    tol = 0.04 if rehearse else 0.004
    assert abs(np.mean(cust["c_mktsegment"].values == BUILDING) - 0.2) < tol
    assert abs(np.mean(orders["o_orderdate"].values < DATE) - 1169 / 2406) < tol
    assert abs(np.mean(lines["l_shipdate"].values > DATE) - 0.54) < tol
    # values move with the seed and the variant, sizes do not; lineitem is
    # in key order, largest first (row 0 holds a key above every build
    # key), so its key column alone is every variant's
    other = named(data.env(0), "lineitem")
    assert (np.diff(lines["l_orderkey"].values) <= 0).all()
    assert lines["l_orderkey"].values[0] >= np.quantile(okey, 0.99)
    assert np.array_equal(other["l_orderkey"].values, lines["l_orderkey"].values)
    for moved in ("l_partkey", "l_quantity", "l_discount", "l_shipdate"):
        assert not np.array_equal(other[moved].values, lines[moved].values)
    if rehearse:
        return
    # at the deployment's size: ~0.5% of lineitem joins, in 12,000-19,000 groups
    building = reference.run_plan(PLAN_A, [env["customer"]])
    open_orders = reference.run_plan(PLAN_B, [env["orders"], building])
    joined = reference.run_plan(PLAN_C[:3], [env["lineitem"], open_orders])
    groups = reference.run_plan(PLAN_C[:5], [env["lineitem"], open_orders])
    assert 290000 < table_rows(building) < 310000
    assert 185000 < table_rows(open_orders) < 205000
    assert 0.004 < table_rows(joined) / sizes["lineitem"] < 0.006
    assert 12000 <= table_rows(groups) <= 19000


def test_the_partition_s_orders_carry_its_lines():
    """``lineitem`` is cut at its rows; the orders it names must all be in
    ``orders``: the first 2,000,000 (4,000) carry 8,000,000 (16,000) or more."""
    for orders, lines in ((2000000, 8000000), (4000, 16000)):
        keys = gen_q3_orderkey.partition_keys(orders, 0, 7, 15000000)
        assert int(gen_q3_orderkey.order_lines(keys).sum()) >= lines


def cols(*specs):
    return [Col(t, s, np.array(v, dt)) for t, s, v, dt in specs]


def test_q3_by_hand_on_a_dozen_rows():
    customer = cols(("INT64", 0, [1, 2, 4, 5], np.int64),
                    ("INT8", 0, [1, 0, 1, 1], np.int8))
    orders = cols(
        ("INT64", 0, [7, 33, 39, 64, 70], np.int64),       # o_orderkey
        ("INT64", 0, [1, 2, 4, 4, 5], np.int64),           # o_custkey
        ("INT32", 0, [9200, 9100, 9204, 9150, 9203], np.int32),
        ("INT32", 0, [0, 0, 0, 0, 0], np.int32))
    # order 7 (cust 1, BUILDING, 9200): lines ship 9205 and 9204 -> one counts
    # order 33 (cust 2, not BUILDING): out; order 39 (date 9204, not < DATE): out
    # order 64 (cust 4, 9150): lines ship 9271 x2 -> both count
    # order 70 (cust 5, 9203): line ships 9204 -> none counts
    lineitem = cols(
        ("INT64", 0, [7, 7, 33, 39, 64, 64, 70, 64, 7, 70, 33, 39], np.int64),
        ("INT64", 0, [1] * 12, np.int64),
        ("DECIMAL64", -2, [100] * 12, np.int64),
        ("DECIMAL64", -2, [10000, 20000, 30000, 40000, 50000, 60000, 70000,
                           80000, 90000, 11000, 12000, 13000], np.int64),
        ("DECIMAL64", -2, [5, 0, 0, 0, 10, 0, 0, 1, 2, 3, 4, 5], np.int64),
        ("INT32", 0, [9205, 9204, 9300, 9300, 9271, 9271, 9204, 9100, 9321,
                      9324, 9205, 9205], np.int32))
    env = {"customer": customer, "orders": orders, "lineitem": lineitem}
    got = reference.run_request(TRAFFIC["request"], env)["result"]
    # order 7: 100.00 x 0.95 + 900.00 x 0.98 = 977.0000; order 64: 500 x 0.90 + 600
    # = 1,050.0000 (its third line shipped 9100); order 70: 110.00 x 0.97 = 106.7000
    assert got[0].values.tolist() == [64, 7, 70]
    assert got[1].values.tolist() == [9150, 9200, 9203]
    assert got[2].values.tolist() == [0, 0, 0]
    assert (got[3].type, got[3].scale) == ("DECIMAL64", -4)
    assert got[3].values.tolist() == [10500000, 9770000, 1067000]
    low = reference.run_request(TRAFFIC["request"], env, lowprec=True)["result"]
    assert compare.compare(low, got, TRAFFIC["answers"]["result"], 0.0)["mismatched"] == 0


def test_ties_at_the_cut_are_broken_by_date_then_key():
    t = cols(("INT64", 0, [5, 3, 9, 1], np.int64), ("INT32", 0, [2, 2, 1, 2], np.int32),
             ("INT32", 0, [0] * 4, np.int32), ("DECIMAL64", -4, [7, 7, 7, 8], np.int64))
    out = reference.run_plan(PLAN_C[5:], [t])
    assert out[0].values.tolist() == [1, 9, 3, 5]


def test_q3_scan_bytes_are_the_read_columns_and_the_answer():
    rows = 8000000
    assert count_q3_scan_bytes.count(CONFIG, TRAFFIC, rows) == (
        rows * 28 + 2000000 * 24 + 1500000 * 9 + 10 * 24)
    # 224 + 48 + 13.5 MB a request
    assert count_q3_scan_bytes.count(CONFIG, TRAFFIC, rows) == 285500240
