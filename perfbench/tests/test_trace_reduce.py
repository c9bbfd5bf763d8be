"""The reduction from trace events to busy time, op time and gaps, on
events written by hand and on a trace recorded on the chip."""

import gzip
import json
import os

import pytest

from perfbench import readers, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def planes():
    dev = [
        ("fusion.1", 10 * MS, 20 * MS),
        ("while.2", 40 * MS, 40 * MS),       # 40..80, body below
        ("sort.7", 45 * MS, 10 * MS),
        ("sort.7", 60 * MS, 15 * MS),
        ("row_pack", 90 * MS, 5 * MS),
        ("fusion.1", 150 * MS, 10 * MS),     # outside the window
    ]
    host = [
        (trace_reduce.WINDOW, 0.0, 100 * MS),
        ("client.plan", 0.0, 85 * MS),
        ("client.download", 85 * MS, 15 * MS),
        ("unrelated", 0.0, 100 * MS),
    ]
    return {"/device:TPU:0": {"XLA Ops": dev, "Steps": [("x", 0.0, 1.0)]},
            "/host:CPU": {"main": host}}


def test_busy_self_time_and_gaps():
    r = trace_reduce.reduce(planes())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.065)  # 20 + 40 + 5 ms
    ops = r["devices"][0]["ops"]
    assert ops["sort.7"] == pytest.approx(0.025)
    assert ops["while.2"] == pytest.approx(0.015)  # 40 ms less its body
    assert ops["fusion.1"] == pytest.approx(0.020)  # the late one is cut off
    gaps = dict(r["breakdown"]["idle_gaps"])
    # idle: 0-10, 30-40, 80-85 under client.plan; 85-90, 95-100 under download
    assert gaps["client.plan"] == pytest.approx(0.025)
    assert gaps["client.download"] == pytest.approx(0.010)
    assert r["breakdown"]["device_ops"][0][0] == "sort.7"


def test_readers_on_the_reduced_trace():
    trace = trace_reduce.reduce(planes())
    ctx = {"trace": trace, "peaks": {"hbm_gbps": 819}, "rows_in": 1000,
           "config": {"tables": {"cols": {"columns": [{"type": "INT64"}]}}},
           "traffic": {"rows_in": "cols", "tables": {"cols": {"table": "cols"}}}}
    share = readers.read({"reader": "trace_time_share", "ops": "sort"}, ctx)
    assert share == pytest.approx(100 * 25 / 65)
    ms = readers.read({"reader": "trace_time_ms", "ops": "row_pack"}, ctx)
    assert ms == pytest.approx(5.0)
    roof = readers.read({"reader": "trace_roofline_share", "ops": "row_pack",
                         "count": "row_pack_unpack_bytes", "peak": "hbm_gbps"}, ctx)
    # one INT64 column: 2 x (8 + 1 + 16) B a row x 1000 rows over 819 GB/s, in 5 ms
    assert roof == pytest.approx(100 * (50_000 / 819e9) / 0.005)
    assert readers.read({"reader": "trace_time_ms", "ops": "all-to-all"}, ctx) is None
    assert readers.read({"reader": "trace_time_share", "ops": "sort"},
                        dict(ctx, trace=None)) is None


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"/host:CPU": {"main": [("client.plan", 0.0, 1.0)]}})


def test_recorded_chip_trace():
    path = os.path.join(HERE, "fixtures", "rowconv_tpu_v5e.events.json.gz")
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    r = trace_reduce.reduce(doc["planes"])
    want = doc["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert names[0].startswith("unpack_rows_pallas") and names[1].startswith("pack_rows_pallas")
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
