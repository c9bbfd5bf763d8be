"""The flight recorder + Chrome-trace export plane (ISSUE 3 tentpole).

Covers the ring buffer (gating, capacity parsing, wraparound, the
8-writer no-lost/no-torn stress contract), the acceptance-criterion
overhead bound on the disabled path, the dump plane
(``SPARK_RAPIDS_TPU_FLIGHT_DUMP`` + atexit + exit sections), the
Chrome-trace exporter (golden file, schema validity, nesting, the
crash-shaped unterminated/truncated span repairs), the
``tools/trace2chrome.py`` CLI, and the resident-table leak report.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import config, flight, metrics, tracing

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GOLDEN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "flight_golden_trace.json",
)


@pytest.fixture(autouse=True)
def _flight_isolated(monkeypatch):
    monkeypatch.delenv("SPARK_RAPIDS_TPU_FLIGHT", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_FLIGHT_DUMP", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_METRICS", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_METRICS_DUMP", raising=False)
    flight.reset()
    metrics.reset()
    flight._WARNED_SPEC = False
    yield
    for f in ("FLIGHT", "FLIGHT_DUMP", "METRICS", "METRICS_DUMP", "TRACE"):
        config.clear_flag(f)
    flight.reset()
    metrics.reset()


class TestGate:
    def test_disabled_by_default(self):
        assert not flight.enabled()
        assert flight.capacity() == 0
        flight.record("I", "x")  # no-op, no crash
        assert flight.tail_records() == []
        assert flight.dropped() == 0

    def test_truthy_enables_default_capacity(self):
        config.set_flag("FLIGHT", True)
        assert flight.enabled()
        assert flight.capacity() == flight.DEFAULT_CAPACITY

    def test_integer_capacity_rounds_to_pow2(self):
        config.set_flag("FLIGHT", "100")
        assert flight.capacity() == 128

    def test_off_values_disable(self):
        for v in ("off", "0", "false", "none", "no"):
            config.set_flag("FLIGHT", v)
            assert not flight.enabled(), v

    def test_dump_path_implies_enabled(self, tmp_path):
        config.set_flag("FLIGHT_DUMP", str(tmp_path / "f.json"))
        assert flight.enabled()
        assert flight.capacity() == flight.DEFAULT_CAPACITY

    def test_invalid_spec_warns_once_and_defaults_on(self, capsys):
        # the log.py invalid-LOG_LEVEL discipline: a typo must not
        # silently disable the crash-telemetry plane
        config.set_flag("FLIGHT", "bogus")
        assert flight.enabled()
        assert flight.capacity() == flight.DEFAULT_CAPACITY
        config.set_flag("FLIGHT", "also-bogus")
        flight.enabled()
        err = capsys.readouterr().err
        assert err.count("[srt][flight][WARN]") == 1

    def test_huge_capacity_clamped(self):
        config.set_flag("FLIGHT", str(1 << 40))
        assert flight.capacity() == flight.MAX_CAPACITY


class TestRing:
    def test_order_and_fields(self):
        config.set_flag("FLIGHT", 64)
        flight.record("B", "spanA")
        flight.record("I", "note", 7)
        flight.record("E", "spanA")
        recs = flight.tail_records()
        assert [r["ph"] for r in recs] == ["B", "I", "E"]
        assert recs[1]["arg"] == 7
        assert "arg" not in recs[0]  # None args are omitted
        assert all(r["tid"] == threading.get_ident() for r in recs)
        # monotonic timestamps + contiguous sequence numbers
        assert recs[0]["t_ns"] <= recs[1]["t_ns"] <= recs[2]["t_ns"]
        assert [r["seq"] for r in recs] == [0, 1, 2]

    def test_wraparound_keeps_newest(self):
        config.set_flag("FLIGHT", 64)
        for i in range(100):
            flight.record("I", "e", i)
        recs = flight.tail_records()
        assert len(recs) == 64
        assert [r["arg"] for r in recs] == list(range(36, 100))
        assert flight.dropped() == 36
        assert [r["arg"] for r in flight.tail_records(10)] == list(
            range(90, 100)
        )

    def test_reset_clears(self):
        config.set_flag("FLIGHT", 64)
        flight.record("I", "x")
        flight.reset()
        assert flight.tail_records() == []


class TestThreadStress:
    def test_no_lost_or_torn_events_under_8_writers(self):
        """Satellite acceptance: 8 writer threads, every event lands
        exactly once with its own thread's payload — the lock-free
        ring's atomicity contract."""
        N, M = 8, 2000
        config.set_flag("FLIGHT", N * M)  # capacity >= total: no drops
        barrier = threading.Barrier(N)

        def writer(t):
            barrier.wait()
            for j in range(M):
                flight.record("I", f"w{t}", (t, j))

        threads = [
            threading.Thread(target=writer, args=(t,)) for t in range(N)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        recs = flight.tail_records()
        assert len(recs) == N * M  # no lost events
        assert len({r["seq"] for r in recs}) == N * M  # no dupes
        per_writer: dict = {t: [] for t in range(N)}
        tid_of: dict = {}
        for r in recs:
            t, j = r["arg"]
            # no torn events: name and payload were written together
            assert r["name"] == f"w{t}"
            # one OS thread per writer, stable across its events
            assert tid_of.setdefault(t, r["tid"]) == r["tid"]
            per_writer[t].append(j)
        for t in range(N):
            # seq order preserves each writer's program order
            assert per_writer[t] == list(range(M))
        assert len(set(tid_of.values())) == N


class TestOverhead:
    def test_disabled_record_cost_within_budget(self):
        """Acceptance criterion: the disabled-path cost stays ~1us/event.
        The real cost is one cached generation compare (~0.1-0.3us);
        the 5us bound leaves generous CI-noise margin."""
        assert not flight.enabled()
        flight.record("I", "warm")  # warm the gate cache
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            flight.record("I", "x")
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6, f"disabled flight.record costs {per * 1e6:.2f}us"

    def test_enabled_record_cost_bounded(self):
        """The enabled path is a seq fetch + timestamp + slot store —
        order O(100ns)-1us; bound it loosely so a lock or allocation
        sneaking into the hot path fails fast."""
        config.set_flag("FLIGHT", 1 << 14)
        flight.record("I", "warm")
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            flight.record("I", "x")
        per = (time.perf_counter() - t0) / n
        assert per < 5e-5, f"enabled flight.record costs {per * 1e6:.2f}us"


class TestSpansOnFlight:
    def test_flight_only_span_records_begin_end(self):
        """FLIGHT alone (METRICS off) must make spans real: the flight
        timeline is useful precisely when nothing else is on."""
        config.set_flag("FLIGHT", True)
        with metrics.span("solo"):
            pass
        recs = flight.tail_records()
        assert [(r["ph"], r["name"]) for r in recs] == [
            ("B", "solo"), ("E", "solo"),
        ]
        # the metrics registry stayed off
        assert metrics.snapshot()["timers"] == {}

    def test_nested_spans_record_qualified_names(self):
        config.set_flag("FLIGHT", True)
        with metrics.span("outer"):
            with metrics.span("inner"):
                pass
        names = [r["name"] for r in flight.tail_records()]
        assert names == [
            "outer", "outer/inner", "outer/inner", "outer",
        ]

    def test_pad_waste_counter_track_in_flight_only_mode(self):
        """The pad-waste counter track must survive FLIGHT-only mode:
        it keeps its own running total instead of piggybacking on the
        (disabled) metrics byte counter."""
        config.set_flag("FLIGHT", True)
        assert not metrics.enabled()
        n = 1500  # not a bucket size: forces padding to 2048
        k = np.arange(n, dtype=np.int64)
        i64 = int(dt.TypeId.INT64)
        op = json.dumps({"op": "sort_by", "keys": [{"column": 0}]})
        rb.table_op_wire(op, [i64], [0], [k.tobytes()], [None], n)
        cs = [
            r for r in flight.tail_records()
            if r["ph"] == "C" and r["name"] == "bucket.pad_waste_bytes"
        ]
        assert cs and cs[-1]["arg"] > 0

    def test_span_exception_records_error_arg(self):
        config.set_flag("FLIGHT", True)
        with pytest.raises(ValueError):
            with metrics.span("doomed"):
                raise ValueError("boom")
        end = flight.tail_records()[-1]
        assert end["ph"] == "E"
        assert end["arg"] == "ValueError"


class TestDump:
    def test_dump_writes_snapshot(self, tmp_path):
        path = str(tmp_path / "flight.json")
        config.set_flag("FLIGHT_DUMP", path)
        flight.record("I", "evt", 1)
        assert flight.dump() == path
        doc = json.loads(open(path).read())
        assert doc["version"] == 1
        assert doc["capacity"] == flight.DEFAULT_CAPACITY
        assert doc["dropped"] == 0
        assert doc["pid"] == os.getpid()
        assert doc["events"][-1]["name"] == "evt"
        assert "epoch_ns" in doc and "anchor_perf_ns" in doc

    def test_dump_without_path_is_noop(self):
        config.set_flag("FLIGHT", True)
        assert flight.dump() is None

    def test_dump_bad_path_warns_not_raises(self, capsys):
        config.set_flag("FLIGHT", True)
        flight.record("I", "x")
        assert flight.dump("/nonexistent-dir/x/flight.json") is None
        assert "[srt][flight][WARN]" in capsys.readouterr().err

    def test_exit_sections_ride_in_snapshot(self):
        config.set_flag("FLIGHT", True)
        flight.register_exit_section("_test_section", lambda: {"k": 1})
        flight.register_exit_section(
            "_test_broken", lambda: 1 / 0
        )
        try:
            snap = flight.snapshot()
        finally:
            flight._EXIT_SECTIONS.pop("_test_section", None)
            flight._EXIT_SECTIONS.pop("_test_broken", None)
        assert snap["sections"]["_test_section"] == {"k": 1}
        # a broken provider degrades to an error record, never raises
        assert "ZeroDivisionError" in snap["sections"]["_test_broken"]["error"]

    def test_atexit_dump_from_env(self, tmp_path):
        """SPARK_RAPIDS_TPU_FLIGHT_DUMP alone turns the recorder on and
        flushes the tail at interpreter exit — and never touches stdout
        (the bench-JSON wire protocol)."""
        dump = tmp_path / "flight.json"
        code = (
            "from spark_rapids_jni_tpu.utils import flight\n"
            "assert flight.enabled()\n"
            "flight.record('I', 'atexit-evt', 42)\n"
        )
        env = dict(os.environ)
        env.update({
            "SPARK_RAPIDS_TPU_FLIGHT_DUMP": str(dump),
            "JAX_PLATFORMS": "cpu",
        })
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=300, env=env, cwd=_ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout == ""
        doc = json.loads(dump.read_text())
        assert doc["events"][-1]["name"] == "atexit-evt"
        assert doc["events"][-1]["arg"] == 42


class TestChromeExport:
    _SYNTHETIC = [
        {"seq": 0, "t_ns": 1_000, "tid": 11, "ph": "E",
         "name": "wire.deserialize"},
        {"seq": 1, "t_ns": 2_000, "tid": 11, "ph": "B",
         "name": "dispatch.sort_by"},
        {"seq": 2, "t_ns": 3_000, "tid": 11, "ph": "B",
         "name": "dispatch.sort_by/bucketed.sort_by"},
        {"seq": 3, "t_ns": 3_500, "tid": 11, "ph": "I",
         "name": "compile_cache.miss", "arg": "srt_bucketed_sort"},
        {"seq": 4, "t_ns": 6_000, "tid": 11, "ph": "E",
         "name": "dispatch.sort_by/bucketed.sort_by"},
        {"seq": 5, "t_ns": 7_000, "tid": 11, "ph": "E",
         "name": "dispatch.sort_by"},
        {"seq": 6, "t_ns": 7_500, "tid": 22, "ph": "C",
         "name": "resident.live", "arg": 3},
        {"seq": 7, "t_ns": 8_000, "tid": 22, "ph": "B",
         "name": "wire.serialize"},
        {"seq": 8, "t_ns": 9_000, "tid": 22, "ph": "E",
         "name": "wire.serialize", "arg": "ValueError"},
        {"seq": 9, "t_ns": 10_000, "tid": 11, "ph": "B",
         "name": "dispatch.groupby"},
    ]

    def test_matches_golden_file(self):
        """Golden-file pin: the exporter's output for a fixed synthetic
        tail is byte-stable. Regenerate tests/data/flight_golden_trace
        .json deliberately when the schema changes."""
        got = tracing.to_chrome_trace(self._SYNTHETIC)
        want = json.loads(open(_GOLDEN).read())
        assert got == want

    def test_schema_valid(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        assert trace["displayTimeUnit"] == "ms"
        for e in trace["traceEvents"]:
            assert e["ph"] in ("X", "i", "C", "M"), e
            assert isinstance(e["pid"], int)
            assert isinstance(e["tid"], int)
            assert e["name"]
            if e["ph"] != "M":
                assert e["ts"] >= 0.0
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
        # JSON-serializable end to end
        json.dumps(trace)

    def test_category_is_leaf_subsystem(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        cats = {
            e["name"]: e["cat"]
            for e in trace["traceEvents"] if e["ph"] == "X"
        }
        # a nested span is categorized by the subsystem that RAN, not
        # its outermost wrapper
        assert cats["dispatch.sort_by/bucketed.sort_by"] == "bucketed"
        assert cats["dispatch.sort_by"] == "dispatch"

    def test_nesting_preserved(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        by_name = {
            e["name"]: e for e in trace["traceEvents"]
            if e["ph"] == "X"
        }
        outer = by_name["dispatch.sort_by"]
        inner = by_name["dispatch.sort_by/bucketed.sort_by"]
        assert outer["tid"] == inner["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]

    def test_crash_shapes_are_repaired(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        # an E whose B fell off the ring starts at the origin
        trunc = [e for e in xs if e.get("args", {}).get("truncated_begin")]
        assert [e["name"] for e in trunc] == ["wire.deserialize"]
        assert trunc[0]["ts"] == 0.0
        # a B that never ended (the SIGTERM case) runs to the tail end
        unterm = [e for e in xs if e.get("args", {}).get("unterminated")]
        assert [e["name"] for e in unterm] == ["dispatch.groupby"]
        # the errored span carries its exception type
        err = [e for e in xs if e.get("args", {}).get("error")]
        assert err[0]["name"] == "wire.serialize"
        assert err[0]["args"]["error"] == "ValueError"

    def test_counter_and_instant_tracks(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
        assert counters[0]["name"] == "resident.live"
        assert counters[0]["args"]["value"] == 3
        instants = [e for e in trace["traceEvents"] if e["ph"] == "i"]
        assert instants[0]["name"] == "compile_cache.miss"
        assert instants[0]["s"] == "t"

    def test_thread_metadata(self):
        trace = tracing.to_chrome_trace(self._SYNTHETIC)
        names = [
            e for e in trace["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        ]
        assert {e["tid"] for e in names} == {11, 22}

    def test_empty_events(self):
        assert tracing.to_chrome_trace([]) == {
            "traceEvents": [], "displayTimeUnit": "ms",
        }

    def test_traceparent_b_arg_lands_in_x_args(self):
        # the trace layer rides the span's B arg (utils/tracing.py):
        # the exporter must surface it as args.traceparent on the X —
        # including the unterminated crash shape
        tp = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        events = [
            {"seq": 0, "t_ns": 1_000, "tid": 1, "ph": "B",
             "name": "serving.stream", "arg": tp},
            {"seq": 1, "t_ns": 2_000, "tid": 1, "ph": "E",
             "name": "serving.stream"},
            {"seq": 2, "t_ns": 3_000, "tid": 1, "ph": "B",
             "name": "mesh.stage", "arg": tp},
        ]
        xs = {
            e["name"]: e
            for e in tracing.to_chrome_trace(events)["traceEvents"]
            if e["ph"] == "X"
        }
        assert xs["serving.stream"]["args"]["traceparent"] == tp
        unterm = xs["mesh.stage"]
        assert unterm["args"]["unterminated"] is True
        assert unterm["args"]["traceparent"] == tp

    def test_non_numeric_counter_degrades_to_instant(self):
        # a C sample with a string payload would break the Chrome
        # counter track — it must come back as a visible instant
        events = [
            {"seq": 0, "t_ns": 1_000, "tid": 1, "ph": "C",
             "name": "resident.live", "arg": "3 tables"},
            {"seq": 1, "t_ns": 2_000, "tid": 1, "ph": "C",
             "name": "resident.live", "arg": 3},
        ]
        out = tracing.to_chrome_trace(events)["traceEvents"]
        instants = [e for e in out if e["ph"] == "i"]
        counters = [e for e in out if e["ph"] == "C"]
        assert len(instants) == 1
        assert instants[0]["args"]["arg"] == "3 tables"
        assert len(counters) == 1
        assert counters[0]["args"]["value"] == 3

    def test_older_partial_formats_tolerated(self):
        # non-dict rows and missing seq/tid/t_ns keys (older dumps)
        # must degrade, not crash the postmortem tool
        events = [
            "junk-row",
            None,
            {"ph": "I", "name": "legacy.instant"},
            {"seq": 1, "t_ns": 2_000, "tid": 1, "ph": "B",
             "name": "legacy.span"},
            {"seq": 2, "t_ns": 3_000, "tid": 1, "ph": "E",
             "name": "legacy.span"},
        ]
        out = tracing.to_chrome_trace(events)["traceEvents"]
        assert [e["name"] for e in out if e["ph"] == "i"] == [
            "legacy.instant"
        ]
        assert [e["name"] for e in out if e["ph"] == "X"] == [
            "legacy.span"
        ]

    def test_live_dispatch_covers_three_subsystems(self):
        """Acceptance: a wire dispatch with flight on yields spans from
        >= 3 subsystems (dispatch, wire serde, bucketed) plus a counter
        track once a resident handle moves."""
        config.set_flag("FLIGHT", True)
        config.set_flag("METRICS", True)
        n = 2000
        k = np.arange(n, dtype=np.int64)[::-1].copy()
        i64 = int(dt.TypeId.INT64)
        op = json.dumps({"op": "sort_by", "keys": [{"column": 0}]})
        rb.table_op_wire(op, [i64], [0], [k.tobytes()], [None], n)
        tid = rb.table_upload_wire([i64], [0], [k.tobytes()], [None], n)
        rb.table_free(tid)
        trace = tracing.to_chrome_trace(flight.tail_records())
        cats = {
            e["cat"] for e in trace["traceEvents"] if e["ph"] == "X"
        }
        assert {"dispatch", "wire", "bucketed"} <= cats
        counter_names = {
            e["name"] for e in trace["traceEvents"] if e["ph"] == "C"
        }
        assert "resident.live" in counter_names


class TestTrace2ChromeCli:
    def test_converts_flight_dump(self, tmp_path):
        config.set_flag("FLIGHT", True)
        with metrics.span("cfg.smoke"):
            flight.record("I", "note")
        dump_path = str(tmp_path / "flight.json")
        assert flight.dump(dump_path) == dump_path
        out_path = str(tmp_path / "trace.json")
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "trace2chrome.py"),
             dump_path, "-o", out_path],
            capture_output=True, text=True, timeout=300, cwd=_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        trace = json.loads(open(out_path).read())
        assert any(
            e["ph"] == "X" and e["name"] == "cfg.smoke"
            for e in trace["traceEvents"]
        )
        assert "perfetto" in proc.stdout

    def test_no_events_exits_nonzero(self, tmp_path):
        p = tmp_path / "empty.json"
        p.write_text(json.dumps({"events": []}))
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "trace2chrome.py"),
             str(p)],
            capture_output=True, text=True, timeout=300, cwd=_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 1
        assert "no flight events" in proc.stderr


class TestLeakReport:
    def test_leaked_table_lists_allocating_span_stack(self):
        config.set_flag("METRICS", True)
        config.set_flag("FLIGHT", True)
        t = Table([Column.from_numpy(np.arange(64, dtype=np.int64))], ["k"])
        with metrics.span("cfg.load"):
            with metrics.span("upload"):
                tid = rb._resident_put(t)
        try:
            leaks = [
                r for r in rb.leak_report() if r["table_id"] == tid
            ]
            assert len(leaks) == 1
            rec = leaks[0]
            assert rec["rows"] == 64
            assert rec["columns"] == 1
            assert rec["allocated_under"] == ["cfg.load", "cfg.load/upload"]
            assert rec["approx_bytes"] > 0
            assert rec["age_s"] >= 0.0
            # the flight dump embeds the same report
            snap = flight.snapshot()
            ids = {
                r["table_id"]
                for r in snap["sections"]["resident_leaks"]
            }
            assert tid in ids
            json.dumps(snap)
        finally:
            rb.table_free(tid)
        assert all(
            r["table_id"] != tid for r in rb.leak_report()
        )
