"""Correctness of the TPC-DS-shaped benchmark queries at tiny scale:
single-chip results against a pure-python oracle, distributed results
against single-chip (the 8-device virtual mesh from conftest)."""

from collections import defaultdict

import numpy as np
import pytest

from benchmarks import datagen, queries
from spark_rapids_jni_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def tables():
    return datagen.generate(2000, seed=11)


def _oracle_q5(tables, lo=100, hi=200):
    out = defaultdict(lambda: [0.0, 0.0])
    item_cat = dict(
        zip(
            tables["item"]["item_sk"].to_pylist(),
            tables["item"]["category_id"].to_pylist(),
        )
    )
    for t in (tables["store_sales"], tables["web_sales"]):
        d = t.to_pydict()
        for i in range(len(d["item_sk"])):
            if not (lo <= d["date_sk"][i] < hi):
                continue
            cat = item_cat[d["item_sk"][i]]
            out[cat][0] += d["quantity"][i] * d["sales_price"][i]
            out[cat][1] += d["net_profit"][i]
    return out


def test_q5_vs_oracle(tables):
    got = queries.q5(tables)
    want = _oracle_q5(tables)
    cats = got["category_id"].to_pylist()
    sums = got["sum_revenue"].to_pylist()
    profs = got["sum_net_profit"].to_pylist()
    assert sorted(cats) == sorted(want.keys())
    for c, s, p in zip(cats, sums, profs):
        assert s == pytest.approx(want[c][0], rel=1e-6), f"cat {c} revenue"
        assert p == pytest.approx(want[c][1], rel=1e-6), f"cat {c} profit"


def _oracle_q23(tables, min_count=4):
    d = tables["store_sales"].to_pydict()
    counts = defaultdict(int)
    for sk in d["item_sk"]:
        counts[sk] += 1
    hot = {k for k, v in counts.items() if v >= min_count}
    spend = defaultdict(float)
    for i in range(len(d["item_sk"])):
        if d["item_sk"][i] in hot:
            spend[d["customer_sk"][i]] += d["quantity"][i] * d["sales_price"][i]
    return spend


def test_q23_vs_oracle(tables):
    got = queries.q23(tables)
    want = _oracle_q23(tables)
    custs = got["customer_sk"].to_pylist()
    sums = got["sum_spend"].to_pylist()
    assert sorted(custs) == sorted(want.keys())
    for c, s in zip(custs, sums):
        assert s == pytest.approx(want[c], rel=1e-6)


def _oracle_q64(tables, max_price=150.0):
    item = tables["item"].to_pydict()
    # current_price is decimal: to_pydict yields unscaled values
    price_scale = tables["item"]["current_price"].dtype.scale
    cutoff = max_price * (10 ** -price_scale)
    cheap_brand = {
        item["item_sk"][i]: item["brand_id"][i]
        for i in range(len(item["item_sk"]))
        if item["current_price"][i] <= cutoff
    }
    cust = tables["customer"].to_pydict()
    state = dict(zip(cust["customer_sk"], cust["state_id"]))
    dates = tables["date_dim"].to_pydict()
    year = dict(zip(dates["date_sk"], dates["year"]))
    d = tables["store_sales"].to_pydict()
    out = defaultdict(lambda: [0.0, 0])
    for i in range(len(d["item_sk"])):
        if d["item_sk"][i] not in cheap_brand:
            continue
        key = (
            cheap_brand[d["item_sk"][i]],
            state[d["customer_sk"][i]],
            year[d["date_sk"][i]],
        )
        out[key][0] += d["quantity"][i] * d["sales_price"][i]
        out[key][1] += 1
    return out


def test_q64_vs_oracle(tables):
    got = queries.q64(tables)
    want = _oracle_q64(tables)
    keys = list(
        zip(
            got["brand_id"].to_pylist(),
            got["state_id"].to_pylist(),
            got["year"].to_pylist(),
        )
    )
    assert sorted(keys) == sorted(want.keys())
    sums = got["sum_revenue"].to_pylist()
    cnts = got["count_revenue"].to_pylist()
    for k, s, c in zip(keys, sums, cnts):
        assert s == pytest.approx(want[k][0], rel=1e-6), f"key {k}"
        assert c == want[k][1], f"key {k} count"


# ---------------------------------------------------------------------------
# distributed == single-chip (virtual 8-device mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _groupby_to_dict(table, key_names, val_names):
    keys = list(zip(*[table[k].to_pylist() for k in key_names]))
    vals = {v: table[v].to_pylist() for v in val_names}
    return {
        k: tuple(vals[v][i] for v in val_names) for i, k in enumerate(keys)
    }


def test_q5_distributed_matches(tables, mesh):
    single = queries.q5(tables)
    padded, counts, overflow = queries.q5_distributed(tables, mesh)
    assert int(np.asarray(overflow).max()) <= 0  # no dropped rows
    dist = queries._unpad_groupby(padded, counts)
    s = _groupby_to_dict(single, ["category_id"], ["sum_revenue"])
    d = _groupby_to_dict(dist, ["category_id"], ["sum_revenue"])
    assert set(s) == set(d)
    for k in s:
        assert d[k][0] == pytest.approx(s[k][0], rel=1e-6)


def test_q23_distributed_matches(tables, mesh):
    single = queries.q23(tables)
    padded, counts, overflow = queries.q23_distributed(tables, mesh)
    assert int(np.asarray(overflow).max()) <= 0  # no dropped rows
    dist = queries._unpad_groupby(padded, counts)
    s = _groupby_to_dict(single, ["customer_sk"], ["sum_spend"])
    d = _groupby_to_dict(dist, ["customer_sk"], ["sum_spend"])
    assert s.keys() == d.keys()
    for k in s:
        assert d[k][0] == pytest.approx(s[k][0], rel=1e-6)


def test_q64_distributed_matches(tables, mesh):
    single = queries.q64(tables)
    dist = queries.q64_distributed(tables, mesh)
    keys = ["brand_id", "state_id", "year"]
    s = _groupby_to_dict(single, keys, ["sum_revenue", "count_revenue"])
    d = _groupby_to_dict(dist, keys, ["sum_revenue", "count_revenue"])
    assert s.keys() == d.keys()
    for k in s:
        assert d[k][1] == s[k][1]
        assert d[k][0] == pytest.approx(s[k][0], rel=1e-6)


def test_bench_emit_daemon_provenance(monkeypatch, capsys):
    """A daemon-state 100M entry must not masquerade as a this-run
    measurement: headline_source carries its capture timestamp."""
    import json as json_mod

    import bench

    entry = {
        "name": "groupby_sum_100M_chunked",
        "seconds_median": 0.5,
        "source": "daemon_retry_loop",
        "measured_at": "2026-07-30T12:00:00Z",
    }
    bench._emit([entry], "tpu")
    doc = json_mod.loads(capsys.readouterr().out.strip())
    assert doc["headline_source"] == "daemon_retry_loop(2026-07-30T12:00:00Z)"
    assert doc["value"] == pytest.approx(2e8)
