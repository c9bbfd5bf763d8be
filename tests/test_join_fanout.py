"""A join that multiplies its rows (PR 50): a build key that repeats,
an output larger than both inputs, and one resident table as both sides
of one join, all through ``serving`` and held to the benchmark's plain
reference (``perfbench.reference.run_plan``: pandas) by the comparison
that decides ``correct`` (``perfbench.compare``), limit 0 mismatched.

The cases: the run's length riding the direct table's word (a build
bucket under 2^16 rows) and read by a second gather (at 2^16), and the
same joins by the search (a key span no table covers); an output
exactly at a bucket, one row under it, and past the ladder's top, where
the bucketed runner declines and the exact path answers; no match at
all; and ``tpcds-q95-wswh.selfjoin-resident``'s whole plan at its
rehearsal size, with the session's ``join_plan`` and the counters
``join.build.repeats`` / ``join.mat.cap_rows`` reading what the data
says. Every one of these outputs is wider than its probe side's bucket,
so its materialise takes the spread form (PR 51; ``join.mat.spread``
beside ``join.materialised``; ``tests/test_join_spread.py`` holds the
two forms to each other).
"""

import json
import os

import numpy as np
import pytest

from perfbench import compare, reference, script
from perfbench.wirefmt import Col, table_rows, unwire, wire
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import buckets, config, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOIN = [{"op": "join", "on": [0], "how": "inner"}]
WATCHED = [
    "join.probe.direct", "join.probe.search", "join.build.repeats",
    "join.mat.cap_rows", "join.mat.spread", "join.materialised",
    "join.output_rows", "join.probe_rows",
    "join.build_rows", "bucket.declined", "bucket.fallback_errors",
    "plan.fallbacks", "project.calls", "filter.deferred",
]


@pytest.fixture(autouse=True)
def _flags():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("METRICS")
    config.clear_flag("BUCKETS")


@pytest.fixture(scope="module")
def client():
    with serving.Server(session_hbm_fraction=1.0, workers=1).start() as srv:
        with serving.Client(srv.port, timeout=600.0).connect() as c:
            yield c


def lines_table(lines, first=48_000_001, step=1, seed=0):
    """(key INT64, value INT64): key ``first + i * step`` as often as
    ``lines[i]`` says, a key's rows adjacent, values from the seed."""
    lines = np.asarray(lines, np.int64)
    keys = first + step * np.arange(len(lines), dtype=np.int64)
    key = np.repeat(keys, lines)
    val = np.random.default_rng(seed).integers(1, 21, len(key), dtype=np.int64)
    return [Col("INT64", 0, key, None), Col("INT64", 0, val, None)]


def served(client, ops, tables):
    """The plan over uploaded host tables (one upload a distinct table:
    the same object twice is ONE resident id given twice) -> the
    downloaded answer, the counters it moved, the session's stats."""
    ids = {}
    for t in tables:
        if id(t) not in ids:
            ids[id(t)] = client.upload(wire(t))
    before = metrics.counter_values(WATCHED)
    out = client.plan(ops, [ids[id(t)] for t in tables])
    after = metrics.counter_values(WATCHED)
    got = unwire(client.download(out))
    client.free(out)
    for i in ids.values():
        client.free(i)
    (sess,) = client.stats()["sessions"]
    assert sess["tables"] == 0
    return got, {k: after[k] - before[k] for k in WATCHED}, sess


def mismatched(got, want) -> int:
    return compare.fold([compare.compare(
        got, want, {"order": "by_column_0"}, 0.0)])["mismatched_values"]


def pairs_of(table) -> int:
    _, n = np.unique(table[0].values, return_counts=True)
    return int((n.astype(np.int64) ** 2).sum())


@pytest.mark.parametrize("probe", ["direct", "search"])
@pytest.mark.parametrize("orders,bucket", [(900, 4096), (14000, 65536)],
                         ids=["build_under_2p16", "build_at_2p16"])
def test_a_repeating_build_key(client, orders, bucket, probe):
    """A build key of 2..6 rows a value: by the table, whose word holds
    the run's length while the build side's bucket is under 2^16 rows
    and which reads it by a second gather from there on; and by the
    search, where the keys are 2^21 apart and no table covers their span
    (past 2^30 values: over one u32 word at 900 keys, over every order
    word at 14,000)."""
    rng = np.random.default_rng(orders)
    step = 1 if probe == "direct" else 1 << 21
    t = lines_table(rng.integers(2, 7, orders), step=step, seed=orders)
    assert buckets.bucket_for(table_rows(t)) == bucket
    want = reference.run_plan(JOIN, [t, t])
    assert table_rows(want) == pairs_of(t) > 4 * table_rows(t)
    got, moved, sess = served(client, JOIN, [t, t])
    assert mismatched(got, want) == 0
    assert moved["join.probe." + probe] == 1
    # the search never learns whether the key repeats: only a table's
    # word has to hold a run's length
    assert moved["join.build.repeats"] == (probe == "direct")
    assert moved["bucket.declined"] == moved["bucket.fallback_errors"] == 0
    cap = buckets.bucket_for(table_rows(want))
    assert moved["join.mat.cap_rows"] == cap > bucket
    assert moved["join.mat.spread"] == moved["join.materialised"] == 1
    assert sess["join_plan"] == {
        "probe_rows": table_rows(t), "build_rows": table_rows(t),
        "output_rows": table_rows(want), "cap": cap,
        "fanout": table_rows(want) / table_rows(t),
        "pad_share": 1.0 - table_rows(want) / cap,
    }


def test_one_table_is_both_sides_and_stays_usable(client):
    """One resident id given twice; the table then serves another plan
    and is freed once."""
    t = lines_table([3, 1, 4, 1, 5, 9, 2, 6])
    tid = client.upload(wire(t))
    want = reference.run_plan(JOIN, [t, t])
    for _ in range(2):
        out = client.plan(JOIN, [tid, tid])
        assert mismatched(unwire(client.download(out)), want) == 0
        client.free(out)
    again = unwire(client.download(tid))
    assert mismatched(again, t) == 0
    client.free(tid)
    (sess,) = client.stats()["sessions"]
    assert sess["tables"] == 0
    with pytest.raises(KeyError):
        client.free(tid)


# orders whose lines squared sum to exactly 2^11, and to one less
AT_2048 = [16] * 8
UNDER_2048 = [16] * 7 + [15, 5, 2, 1]


@pytest.mark.parametrize("lines,total", [(AT_2048, 2048), (UNDER_2048, 2047)],
                         ids=["at_a_bucket", "one_row_under"])
def test_an_output_at_its_bucket(client, lines, total):
    t = lines_table(lines)
    assert pairs_of(t) == total
    want = reference.run_plan(JOIN, [t, t])
    got, moved, sess = served(client, JOIN, [t, t])
    assert table_rows(got) == total and mismatched(got, want) == 0
    assert moved["join.mat.cap_rows"] == sess["join_plan"]["cap"] == 2048
    # 128 rows sit in the ladder's first bucket, 1,024: the output is wider
    assert moved["join.mat.spread"] == moved["join.materialised"] == 1
    assert sess["join_plan"]["pad_share"] == 1.0 - total / 2048
    assert moved["bucket.declined"] == 0


def test_an_output_past_the_ladder_s_top_is_declined_cleanly(client):
    """The ladder's top lowered to 8,192 rows: 100 orders of 10 lines
    give 10,000 pairs, no bucket holds them, the bucketed runner
    declines (counted) and the exact path gives the same answer."""
    config.set_flag("BUCKETS", "1024:2:8192")
    t = lines_table([10] * 100)
    want = reference.run_plan(JOIN, [t, t])
    assert table_rows(want) == 10_000
    assert buckets.bucket_for(10_000) is None
    got, moved, _ = served(client, JOIN, [t, t])
    assert mismatched(got, want) == 0
    assert moved["bucket.declined"] == 1
    assert moved["join.output_rows"] == 10_000
    assert moved["join.mat.cap_rows"] == moved["join.mat.spread"] == 0
    assert moved["bucket.fallback_errors"] == moved["plan.fallbacks"] == 0


def test_a_left_join_that_widens(client):
    """A left outer join whose build key repeats: the unmatched probe
    rows emit once with a null build side, the others as often as their
    key has build rows, and the output (wider than the probe side's
    bucket: the spread form, its mask ONE spread word) is pandas' row
    for row."""
    import pandas as pd

    a = lines_table([3, 2, 4, 1, 2], first=48_000_001, step=2, seed=1)
    b = lines_table([5, 6, 7], first=48_000_003, step=2, seed=2)
    df = pd.DataFrame({"k": a[0].values, "v": a[1].values}).merge(
        pd.DataFrame({"k": b[0].values, "w": b[1].values}).astype(
            {"w": "Int64"}), on="k", how="left")
    assert len(df) == 3 + 2 * 5 + 4 * 6 + 1 * 7 + 2 > 1024 // 32
    config.set_flag("BUCKETS", "8:2:8388608")
    got, moved, sess = served(
        client, [{"op": "join", "on": [0], "how": "left"}], [a, b])
    assert table_rows(got) == len(df)
    assert moved["join.mat.cap_rows"] == 64 > buckets.bucket_for(12)
    assert moved["join.mat.spread"] == moved["join.materialised"] == 1
    assert moved["bucket.fallback_errors"] == moved["plan.fallbacks"] == 0
    rows = sorted(zip(
        got[0].values.tolist(), got[1].values.tolist(),
        [w if ok else None for w, ok in zip(
            got[2].values.tolist(),
            np.ones(len(df), bool) if got[2].valid is None else got[2].valid)],
    ), key=str)
    want = sorted(zip(
        df.k.tolist(), df.v.tolist(),
        [None if x is pd.NA else int(x) for x in df.w]), key=str)
    assert rows == want


def test_no_match_at_all(client):
    a = lines_table([2, 3, 4], first=48_000_001)
    b = lines_table([5, 6], first=49_000_001)
    want = reference.run_plan(JOIN, [a, b])
    assert table_rows(want) == 0
    got, moved, _ = served(client, JOIN, [a, b])
    assert table_rows(got) == 0 and len(got) == len(want)
    assert [c.type for c in got] == [c.type for c in want]
    assert moved["join.output_rows"] == moved["join.mat.cap_rows"] == 0
    assert moved["bucket.fallback_errors"] == moved["plan.fallbacks"] == 0


def _load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 2147483659])
def test_the_cell_s_plan_at_its_rehearsal_size(client, seed):
    config_ = _load("configs", "tpcds-q95-wswh.json")
    traffic = _load("traffic", "selfjoin-resident.json")
    data = script.Data(config_, traffic, seed, rehearse=True)
    t = data.env(1)["web_sales"]
    rows = config_["rehearse_rows"]["web_sales"]
    assert table_rows(t) == rows
    step = traffic["request"][0]
    assert step["tables"] == ["web_sales", "web_sales"]
    want = reference.run_plan(step["plan"], [t, t])
    got, moved, sess = served(client, step["plan"], [t, t])
    assert mismatched(got, want) == 0
    assert len(np.unique(got[0].values)) == table_rows(got) > rows // 16
    pairs = pairs_of(t)
    cap = buckets.bucket_for(pairs)
    # every pair of lines from different warehouses, both orientations:
    # all of an order's pairs but those within one warehouse
    _, same = np.unique(np.stack([t[0].values, t[1].values]), axis=1,
                        return_counts=True)
    assert int(got[1].values.sum()) == pairs - int(
        (same.astype(np.int64) ** 2).sum())
    assert moved == {
        "join.probe.direct": 1, "join.probe.search": 0,
        "join.build.repeats": 1, "join.mat.cap_rows": cap,
        # 6,000 rows in bucket 2^13, their pairs in 2^17: the new form
        "join.mat.spread": 1, "join.materialised": 1,
        "join.output_rows": pairs, "join.probe_rows": rows,
        "join.build_rows": rows, "bucket.declined": 0,
        "bucket.fallback_errors": 0, "plan.fallbacks": 0,
        "project.calls": 1, "filter.deferred": 1,
    }
    assert sess["join_plan"]["output_rows"] == pairs
    assert sess["join_plan"]["cap"] == cap
    assert 12.4 < sess["join_plan"]["fanout"] < 12.7
    assert sess["join_plan"]["pad_share"] == 1.0 - pairs / cap
