"""The ``tpcds-q95-wswh`` pieces, against values known from outside: the
partition's shape at the rehearsal size and at the real one (8..16
adjacent lines an order, every key in partition 0 of 200 and past 2^24,
the join's output rows against the bucket they have to fit, warehouses
1..20 moved by the seed over a key column that no seed moves), the least
bytes of the stage, and the controls of the cell's comparison, each
through ``perfbench.compare``: the reference with the key in the nearest
precision below, and answers that drop a pair or count one twice."""

import json
import os

import numpy as np
import pytest

from perfbench import compare, reference, script
from perfbench.plugins import count_q95_wswh_bytes
from perfbench.wirefmt import Col

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "tpcds-q95-wswh.selfjoin-resident"


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "tpcds-q95-wswh.json")
TRAFFIC = load("traffic", "selfjoin-resident.json")
SPEC = TRAFFIC["answers"]["answer"]
# rows, orders, the join's output rows (the sum of every order's lines
# squared: ONE number a size, the key column being every seed's), the
# bucket they run at
SIZES = {
    True: (6_000, 501, 75_138, 1 << 17),
    False: (648_000, 54_040, 8_128_758, 1 << 23),
}
# the standard deviation of one order's lines squared, 8..16 uniform
SD_PAIRS_AN_ORDER = float(np.std(np.arange(8, 17) ** 2))


def test_the_table_is_the_source_s_two_columns():
    cols = CONFIG["tables"]["web_sales"]["columns"]
    assert [(c["name"], c["type"]) for c in cols] == [
        ("ws_order_number", "INT64"), ("ws_warehouse_sk", "INT64")]
    assert CONFIG["tables"]["web_sales"]["rows"] == 648_000
    assert CONFIG["reduced"] == ["scale", "columns"]
    assert sorted(CONFIG["reduced_why"]) == ["columns", "scale"]
    assert len(CONFIG["assumed"]) >= 6
    (step, down, free) = TRAFFIC["request"]
    assert step["tables"] == ["web_sales", "web_sales"]
    assert [o["op"] for o in step["plan"]] == [
        "join", "project", "filter", "groupby"]
    assert step["plan"][1]["exprs"][1]["binary"] == "ne"
    assert (down["do"], free["do"]) == ("download", "free")
    assert TRAFFIC["expect_counters"] == {
        "project.calls": 1, "join.probe_rows": "rows_in",
        "join.build_rows": "rows_in"}


@pytest.mark.parametrize("rehearse", [True, False], ids=["rehearsal", "real"])
@pytest.mark.parametrize("seed", [0, 2147483659])
def test_the_partition_s_shape(seed, rehearse):
    rows, orders, pairs, bucket = SIZES[rehearse]
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=rehearse)
    (key, wh), (key1, wh1) = (data.env(v)["web_sales"] for v in range(2))
    assert len(key.values) == rows == script.rows_in(TRAFFIC, data)
    # an order's lines adjacent, order numbers ascending
    assert (np.diff(key.values) >= 0).all()
    number, lines = np.unique(key.values, return_counts=True)
    assert len(number) == orders
    # 8..16 lines an order (the cut takes lines off the last one only)
    assert lines[:-1].min() == 8 and lines.max() == 16
    assert 11.9 < lines[:-1].mean() < 12.1
    # partition 0 of Spark's 200, every key past 2^24, inside SF1000's
    pid = np.mod(reference.murmur3_long(number).astype(np.int64), 200)
    assert (pid == 0).all()
    assert number.min() == 48_000_094 > 1 << 24 and number.max() < 60_000_000
    # the direct probe's table: the span's next power of two
    span = int(number.max() - number.min())
    assert 1 << span.bit_length() == (1 << 24 if not rehearse else 1 << 17)
    # the join's output: under its bucket, and over the one below, by
    # more than five standard deviations of a fresh hash
    assert int((lines.astype(np.int64) ** 2).sum()) == pairs
    sd = SD_PAIRS_AN_ORDER * np.sqrt(orders)
    assert bucket - pairs > max(5 * sd, 100_000 if not rehearse else 0)
    assert pairs - bucket // 2 > 5 * sd
    assert 12.4 < pairs / rows < 12.7
    # warehouses 1..20, moved by the seed and the variant; the key is not
    assert wh.values.min() == 1 and wh.values.max() == 20
    assert (key.values == key1.values).all()
    assert (wh.values != wh1.values).any()
    other = script.Data(CONFIG, TRAFFIC, seed + 1, rehearse=rehearse)
    assert (other.env(0)["web_sales"][0].values == key.values).all()
    assert (other.env(0)["web_sales"][1].values != wh.values).any()


def answer_of(table):
    return reference.run_request(
        TRAFFIC["request"], {"web_sales": table})["answer"]


def refused(got, want) -> int:
    """Mismatched values (limit 0) by the comparison that decides ``correct``."""
    return compare.fold([compare.compare(got, want, SPEC, 0.0)])["mismatched_values"]


def with_column(table, i, values):
    out = list(table)
    out[i] = Col(table[i].type, table[i].scale, values, None)
    return out


@pytest.mark.parametrize("seed", [3, 2147483659])
def test_the_reference_s_answer_by_hand(seed):
    """One row an order number with two lines from different warehouses;
    beside it n^2 - sum(lines a warehouse ^2): every ordered pair of
    lines but those of one warehouse, the self-pairs among them."""
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=True)
    key, wh = table = data.env(0)["web_sales"]
    got_key, got_pairs = answer_of(table)
    want = {}
    for k in np.unique(key.values):
        _, m = np.unique(wh.values[key.values == k], return_counts=True)
        cross = int(m.sum()) ** 2 - int((m ** 2).sum())
        if cross:
            want[int(k)] = cross
    assert dict(zip(got_key.values.tolist(), got_pairs.values.tolist())) == want
    assert got_pairs.type == "INT64" and got_key.type == "INT64"
    # a line paired with itself (one candidate in twelve) and one pair in
    # twenty of the rest go
    assert 0.86 < sum(want.values()) / SIZES[True][2] < 0.89


@pytest.mark.parametrize("rehearse", [True, False], ids=["rehearsal", "real"])
def test_the_key_in_the_precision_below_is_refused(rehearse):
    """The control of the comparison, in the nearest precision below the
    configuration's: the plain reference with ws_order_number carried
    through float32. The order numbers lie in 2^25..2^26, float32 is 4
    apart there, and neighbouring orders of the partition (one number in
    200 on average) merge where they fall onto one value: fewer, larger
    groups, refused by row count. Warehouses (1..20) come through
    float32 whole, so the key alone decides. ``perfbench.run --control
    1`` narrows no INT64 (PERF.md section 7): this is that control, held
    here until the harness has it."""
    data = script.Data(CONFIG, TRAFFIC, 2147483659, rehearse=rehearse)
    table = data.env(0)["web_sales"]
    key, wh = table
    want = answer_of(table)
    assert refused(want, want) == 0
    low_wh = with_column(table, 1, wh.values.astype(np.float32).astype(np.int64))
    assert refused(answer_of(low_wh), want) == 0
    low_key = with_column(table, 0, key.values.astype(np.float32).astype(np.int64))
    got = answer_of(low_key)
    assert len(got[0].values) < len(want[0].values)
    assert refused(got, want) >= len(want[0].values)


@pytest.mark.parametrize("seed", [11, 2147483659])
def test_a_pair_dropped_or_counted_twice_is_refused(seed):
    """What holds the stage to 'every pair exactly once': the count
    beside the order number. The joined rows with ONE cross-warehouse
    pair taken out, and with one given twice, each move one count by
    one and are refused; the key set alone would not tell."""
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=True)
    table = data.env(1)["web_sales"]
    step = TRAFFIC["request"][0]
    joined = reference.run_plan(step["plan"][:1], [table, table])
    want = reference.run_plan(step["plan"][1:], [joined])
    assert refused(want, answer_of(table)) == 0
    cross = int(np.flatnonzero(joined[1].values != joined[2].values)[0])

    def behind(rows):
        return reference.run_plan(
            step["plan"][1:], [[Col(c.type, c.scale, c.values[rows], None)
                                for c in joined]])

    every = np.arange(len(joined[0].values))
    dropped = behind(np.delete(every, cross))
    doubled = behind(np.insert(every, cross, cross))
    for broken in (dropped, doubled):
        assert (broken[0].values == want[0].values).all()
        assert refused(broken, want) == 1


def test_the_least_bytes_of_the_stage_by_hand():
    """The table's two INT64 columns read once and one (order number,
    count) row for every sixteen input rows written."""
    got = count_q95_wswh_bytes.count(CONFIG, TRAFFIC, 648_000)
    assert got == 648_000 * 16 + 40_500 * 16 == 11_016_000
    assert count_q95_wswh_bytes.count(CONFIG, TRAFFIC, 6_000) == (
        6_000 * 16 + 375 * 16)
