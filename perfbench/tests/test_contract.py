"""BENCHMARK.json against the limits a file is refused for before any run,
and against the data files the harness finds by name."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
STATISTICS = ("setup_s", "rows_per_s", "p50_s", "p95_s")  # run.py tells them by a name's ending
STREAM = "ss-star-8m.stream-c2"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SPLIT = [m["name"] for m in BENCH["per_layer"]
         if m["name"].split(".")[-1] in ("stream", "convert", "exchange")]


@pytest.fixture(scope="module")
def bench():
    return BENCH


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32 and all(line(w) for w in bench["command"])
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("perfbench/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in {c["name"] for c in bench["configs"]}
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "traffic", w["traffic"] + ".json"))
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for name in e2e:  # run.py tells the statistic by the name's ending
        assert sum(name.endswith(k) for k in STATISTICS) == 1
    reported = {n: set(m.get("workloads", cells)) for n, m in e2e.items()}
    for cell in cells:
        assert cell in reported["setup_s"]
        assert any(cell in v for n, v in reported.items() if n != "setup_s")
    seen = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["name"] not in seen
        seen.add(m["name"])
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= reported[m["moves"]]
        assert any(os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", stem + ".json"))
            for stem in (m["name"], m["name"].split(".")[0]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_peaks_are_keyed_by_device_kind():
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_gbps"] == 819
    assert all("source" in v for v in peaks.values())


# -- the split of a quantity by the end-to-end metric it moves (PR 34) --------

def harness():
    sys.path.insert(0, ROOT)
    from perfbench import run

    return run


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_one_rate_one_latency_and_setup(cell):
    """What ``run.load_cell`` hands a run of the cell: exactly one
    rate-like metric, one latency-like metric and ``setup_s``, each name
    ending in exactly one of the statistics ``run.main`` computes."""
    names = [m["name"] for m in harness().load_cell(cell)[3]]
    for name in names:
        assert sum(name.endswith(k) for k in STATISTICS) == 1, name
    rates = [n for n in names if n.endswith("rows_per_s")]
    waits = [n for n in names if n.endswith(("p50_s", "p95_s"))]
    assert len(rates) == 1 and len(waits) == 1, names
    assert sorted(names) == sorted(rates + waits + ["setup_s"])


def test_no_tight_bound_serves_the_two_tenant_stream(bench):
    """stream-c2 sits on a balance point of host and device (PERF.md §2):
    a metric bounded under 5% may not list it, or every PR is `unresolved`."""
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"]:
        if m["name"] != "setup_s" and STREAM in m.get("workloads", cells):
            assert m["bound"] >= 0.05, m["name"]
    assert STREAM in cells


@pytest.mark.parametrize("name", SPLIT)
def test_split_metric_shares_its_reader_and_no_cell_with_its_twin(bench, name):
    """``x.stream`` / ``x.convert`` / ``x.exchange`` is read by a reader
    file ``run.reader_file`` finds, lists its cells itself, and where a
    plain ``x`` stands beside it the two share no cell and no `moves`."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    run = harness()
    stem = name.rsplit(".", 1)[0]
    assert run.reader_file(name) in (name + ".json", stem + ".json")
    mine = by_name[name]
    assert mine.get("workloads"), "a split metric lists its cells"
    twin = by_name.get(stem)
    if twin is not None:
        assert run.reader_file(stem) == run.reader_file(name)
        assert not set(twin["workloads"]) & set(mine["workloads"])
        assert twin["moves"] != mine["moves"]
    for cell in mine["workloads"]:
        assert name in [s["name"] for s in run.load_cell(cell)[4]]
