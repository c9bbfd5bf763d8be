"""The op-level metrics registry + span plane (utils/metrics.py): the
``GpuMetric`` / SQL-UI-counters role of the reference stack.

Covers registry math (counters/bytes/timers/gauges/histograms), span
nesting + exception-path duration recording, thread safety under
concurrent ``_dispatch`` calls (the Python-tier sibling of
tests/test_concurrency.py), the resident-table round-trip acceptance
snapshot, stdout hygiene (LOG_LEVEL=TRACE + a metrics dump must never
touch stdout).
"""

import contextlib
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import config, flight, log, metrics, tracing

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _metrics_isolated(monkeypatch):
    monkeypatch.delenv("SPARK_RAPIDS_TPU_METRICS", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_METRICS_DUMP", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_LOG_LEVEL", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_FLIGHT", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_FLIGHT_DUMP", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_PLANSTATS", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_PLANSTATS_DIR", raising=False)
    # flag overrides leaked by an earlier module beat the env
    for f in ("METRICS", "METRICS_DUMP", "FLIGHT", "FLIGHT_DUMP",
              "PROFILE", "PROFILE_DUMP", "PLANSTATS", "PLANSTATS_DIR"):
        config.clear_flag(f)
    metrics.reset()
    flight.reset()
    yield
    for f in ("METRICS", "METRICS_DUMP", "LOG_LEVEL", "TRACE",
              "FLIGHT", "FLIGHT_DUMP", "PROFILE", "PROFILE_DUMP",
              "PLANSTATS", "PLANSTATS_DIR"):
        config.clear_flag(f)
    metrics.reset()
    flight.reset()
    log._WARNED_INVALID.clear()


def _on():
    config.set_flag("METRICS", True)


class TestRegistryMath:
    def test_counters(self):
        _on()
        metrics.counter_add("c")
        metrics.counter_add("c", 41)
        assert metrics.snapshot()["counters"]["c"] == 42

    def test_bytes(self):
        _on()
        metrics.bytes_add("b", 100)
        metrics.bytes_add("b", 28)
        assert metrics.snapshot()["bytes"]["b"] == 128

    def test_timer_fold(self):
        _on()
        for s in (0.5, 0.1, 0.9):
            metrics.timer_record("t", s)
        t = metrics.snapshot()["timers"]["t"]
        assert t["count"] == 3
        assert t["total_s"] == pytest.approx(1.5)
        assert t["min_s"] == pytest.approx(0.1)
        assert t["max_s"] == pytest.approx(0.9)

    def test_gauge_high_water(self):
        _on()
        for v in (1, 5, 2):
            metrics.gauge_set("g", v)
        g = metrics.snapshot()["gauges"]["g"]
        assert g["value"] == 2
        assert g["high_water"] == 5

    def test_histogram_buckets(self):
        _on()
        bounds = [1, 10, 100]
        for v in (0.5, 1, 5, 100, 1000):
            metrics.hist_observe("h", v, bounds=bounds)
        h = metrics.snapshot()["histograms"]["h"]
        # inclusive upper edges: {<=1: 2, <=10: 1, <=100: 1, overflow: 1}
        assert h["bounds"] == bounds
        assert h["counts"] == [2, 1, 1, 1]
        assert h["count"] == 5
        assert h["sum"] == pytest.approx(1106.5)

    def test_snapshot_is_json_able(self):
        _on()
        metrics.counter_add("c")
        metrics.timer_record("t", 0.25)
        metrics.gauge_set("g", 3)
        metrics.hist_observe("h", 7)
        json.dumps(metrics.snapshot())  # must not raise

    def test_disabled_mutators_no_op(self):
        metrics.counter_add("c")
        metrics.bytes_add("b", 1)
        metrics.timer_record("t", 1.0)
        metrics.gauge_set("g", 1)
        metrics.hist_observe("h", 1)
        snap = metrics.snapshot()
        assert snap["counters"] == {}
        assert snap["timers"] == {}

    def test_disabled_span_is_shared_null(self):
        # the disabled hot path allocates nothing per call
        assert metrics.span("x") is metrics.NULL_SPAN
        assert metrics.span("y") is metrics.NULL_SPAN


class TestSpans:
    def test_span_records_duration(self):
        _on()
        with metrics.span("work"):
            pass
        t = metrics.snapshot()["timers"]["work"]
        assert t["count"] == 1
        assert t["total_s"] >= 0.0

    def test_span_nesting_qualified_names(self, capsys):
        _on()
        config.set_flag("LOG_LEVEL", "TRACE")
        with metrics.span("outer") as outer:
            assert metrics.span_depth() == 1
            with metrics.span("inner") as inner:
                assert metrics.span_depth() == 2
                assert inner.qualname == "outer/inner"
            assert outer.qualname == "outer"
        assert metrics.span_depth() == 0
        timers = metrics.snapshot()["timers"]
        # aggregation stays under the plain name; the qualified path is
        # the trace/log label
        assert set(timers) == {"outer", "inner"}
        err = capsys.readouterr().err
        assert "[srt][span][TRACE] outer/inner" in err

    def test_span_exception_path_records(self):
        _on()
        with pytest.raises(ValueError):
            with metrics.span("doomed"):
                raise ValueError("boom")
        snap = metrics.snapshot()
        assert snap["timers"]["doomed"]["count"] == 1
        assert snap["counters"]["span.doomed.errors"] == 1
        assert metrics.span_depth() == 0  # stack unwound

    def test_span_self_time_excludes_children(self):
        import time as _time

        _on()
        with metrics.span("outer"):
            with metrics.span("inner"):
                _time.sleep(0.02)
        snap = metrics.snapshot()
        # inner has no children: self time == its duration
        assert snap["span_self"]["inner"]["self_s"] >= 0.015
        # outer's self time excludes inner — near zero, far below its
        # total (which contains the sleep)
        assert snap["timers"]["outer"]["total_s"] >= 0.015
        assert snap["span_self"]["outer"]["self_s"] < 0.015
        # and every span feeds its duration histogram
        assert snap["histograms"]["span_ms.inner"]["count"] == 1
        assert snap["histograms"]["span_ms.outer"]["count"] == 1

    def test_traced_decorator(self):
        _on()

        @metrics.traced("deco.fn")
        def fn(x):
            return x + 1

        assert fn(1) == 2
        assert metrics.snapshot()["timers"]["deco.fn"]["count"] == 1

    def test_span_opens_annotation_without_trace_flag(self, monkeypatch):
        # a live span always opens its profiler annotation, named
        # "srt/" + qualname: no TRACE gate (the benchmark sets METRICS
        # alone, and its device trace must hold the program's spans)
        _on()
        opened = []

        class FakeAnnotation:
            def __init__(self, name):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(metrics, "_ANNOTATION", FakeAnnotation)
        with metrics.span("ranged"):
            with metrics.span("inner"):
                pass
        assert opened == ["srt/ranged", "srt/ranged/inner"]


class TestThreadSafety:
    def test_registry_exact_under_contention(self):
        _on()
        N, M = 8, 1000
        barrier = threading.Barrier(N)

        def hammer():
            barrier.wait()
            for _ in range(M):
                metrics.counter_add("hot")
                metrics.timer_record("hot_t", 0.001)
                metrics.gauge_set("hot_g", 1)

        threads = [threading.Thread(target=hammer) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        snap = metrics.snapshot()
        assert snap["counters"]["hot"] == N * M
        assert snap["timers"]["hot_t"]["count"] == N * M

    def test_concurrent_dispatch_counts_exact(self):
        """The test_concurrency pattern on the pure-Python wire path:
        per-op counters must stay exact when executor threads dispatch
        concurrently."""
        _on()
        N_THREADS, OPS = 4, 3
        i64 = int(dt.TypeId.INT64)
        op = json.dumps({
            "op": "groupby", "by": [0],
            "aggs": [{"column": 1, "agg": "sum"}],
        })
        errors = []

        def worker(tid):
            try:
                rng = np.random.default_rng(tid)
                for _ in range(OPS):
                    n = 64
                    k = rng.integers(0, 8, n).astype(np.int64)
                    v = rng.integers(-50, 50, n).astype(np.int64)
                    _, _, od, _, rows = rb.table_op_wire(
                        op, [i64, i64], [0, 0],
                        [k.tobytes(), v.tobytes()], [None, None], n,
                    )
                    keys = np.frombuffer(od[0], np.int64, rows)
                    sums = np.frombuffer(od[1], np.int64, rows)
                    want = {
                        int(u): int(v[k == u].sum()) for u in np.unique(k)
                    }
                    if dict(zip(keys.tolist(), sums.tolist())) != want:
                        errors.append((tid, "oracle mismatch"))
            except Exception as e:  # pragma: no cover
                errors.append((tid, repr(e)))

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(N_THREADS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert errors == []
        snap = metrics.snapshot()
        assert snap["counters"]["op.groupby.calls"] == N_THREADS * OPS
        assert (
            snap["counters"]["op.groupby.rows_in"]
            == N_THREADS * OPS * 64
        )
        assert snap["bytes"]["wire.bytes_in"] == N_THREADS * OPS * 64 * 16
        assert snap["timers"]["dispatch.groupby"]["count"] == N_THREADS * OPS


class TestResidentRoundTrip:
    def test_snapshot_after_resident_groupby_round_trip(self):
        """Acceptance: non-zero op counts, wire bytes, and a resident
        handle high-water mark after an upload -> groupby -> download
        -> free chain."""
        _on()
        n = 128
        rng = np.random.default_rng(5)
        k = rng.integers(0, 10, n).astype(np.int64)
        v = rng.integers(-100, 100, n).astype(np.int64)
        i64 = int(dt.TypeId.INT64)
        tid = rb.table_upload_wire(
            [i64, i64], [0, 0], [k.tobytes(), v.tobytes()],
            [None, None], n,
        )
        gid = rb.table_op_resident(
            json.dumps({
                "op": "groupby", "by": [0],
                "aggs": [{"column": 1, "agg": "sum"}],
            }),
            [tid],
        )
        out = rb.table_download_wire(gid)
        rb.table_free(tid)
        rb.table_free(gid)
        assert out[4] > 0
        snap = metrics.snapshot()
        assert snap["counters"]["op.groupby.calls"] >= 1
        assert snap["bytes"]["wire.bytes_in"] >= n * 16
        assert snap["bytes"]["wire.bytes_out"] > 0
        assert snap["gauges"]["resident.live"]["high_water"] >= 2
        # the chain freed what it allocated: live back to zero but the
        # high-water mark preserves the peak (the leak-report analog)
        assert snap["gauges"]["resident.live"]["value"] == 0
        assert (
            snap["counters"]["resident.put"]
            == snap["counters"]["resident.free"]
        )
        assert snap["timers"]["wire.deserialize"]["count"] >= 1
        assert snap["timers"]["wire.serialize"]["count"] >= 1

    def test_hbm_plan_metrics(self):
        _on()
        from spark_rapids_jni_tpu.utils import hbm

        t = Table(
            [
                Column.from_numpy(np.arange(64, dtype=np.int64)),
                Column.from_numpy(np.arange(64, dtype=np.int64)),
            ],
            ["k", "v"],
        )
        hbm.join_plan(t, t, ["k"], ["k"])
        hbm.groupby_plan(t, ["k"], 16)
        snap = metrics.snapshot()
        assert snap["counters"]["hbm.plan.join"] == 1
        assert snap["counters"]["hbm.plan.groupby"] == 1
        assert snap["bytes"]["hbm.planned_bytes"] > 0
        assert snap["gauges"]["hbm.budget_bytes"]["value"] > 0


class TestStdoutHygiene:
    def test_trace_level_plus_dump_never_writes_stdout(self, tmp_path):
        """LOG_LEVEL=TRACE + METRICS + a dump path: stderr carries the
        telemetry, the dump file carries the snapshot, stdout stays
        EMPTY (it is the bench-JSON wire protocol)."""
        dump = tmp_path / "metrics.json"
        code = (
            "import json, numpy as np\n"
            "from spark_rapids_jni_tpu import dtype as dt\n"
            "from spark_rapids_jni_tpu import runtime_bridge as rb\n"
            "from spark_rapids_jni_tpu.utils import hbm\n"
            "from spark_rapids_jni_tpu.column import Column, Table\n"
            "k = np.arange(32, dtype=np.int64)[::-1].copy()\n"
            "op = json.dumps({'op': 'sort_by',"
            " 'keys': [{'column': 0}]})\n"
            "rb.table_op_wire(op, [int(dt.TypeId.INT64)], [0],"
            " [k.tobytes()], [None], 32)\n"
            "t = Table([Column.from_numpy(k)], ['k'])\n"
            "hbm.sort_plan(t, 1)\n"
            "tid = rb._resident_put(t)\n"
            "rb.table_free(tid)\n"
        )
        env = dict(os.environ)
        env.update({
            "SPARK_RAPIDS_TPU_LOG_LEVEL": "TRACE",
            "SPARK_RAPIDS_TPU_METRICS": "1",
            "SPARK_RAPIDS_TPU_METRICS_DUMP": str(dump),
            "JAX_PLATFORMS": "cpu",
        })
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=300, env=env, cwd=_ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout == ""
        assert "[srt]" in proc.stderr  # telemetry went to stderr
        # the atexit dump landed and parses
        snap = json.loads(dump.read_text())
        assert snap["counters"]["op.sort_by.calls"] == 1
        assert snap["bytes"]["wire.bytes_in"] > 0
        assert snap["gauges"]["resident.live"]["high_water"] >= 1

    def test_dump_helper_handles_bad_path(self, capsys):
        _on()
        config.set_flag("METRICS_DUMP", "/nonexistent-dir/x/metrics.json")
        assert metrics.dump() is None
        assert "[srt][metrics][WARN]" in capsys.readouterr().err


class TestCaptureTrace:
    def _fake_profiler(self, monkeypatch, writes=None):
        import types

        import jax

        calls = []

        @contextlib.contextmanager
        def fake_trace(log_dir):
            calls.append(log_dir)
            if writes:
                with open(os.path.join(log_dir, writes), "w") as f:
                    f.write("x")
            yield

        monkeypatch.setattr(
            jax, "profiler",
            types.SimpleNamespace(trace=fake_trace),
            raising=False,
        )
        return calls

    def test_creates_missing_dir_and_warns_when_empty(
        self, tmp_path, monkeypatch, capsys
    ):
        target = str(tmp_path / "deep" / "traces")
        calls = self._fake_profiler(monkeypatch)
        with tracing.capture_trace(target):
            pass
        assert calls == [target]
        assert os.path.isdir(target)
        assert "[srt][trace][WARN]" in capsys.readouterr().err

    def test_no_warn_when_capture_produced_files(
        self, tmp_path, monkeypatch, capsys
    ):
        target = str(tmp_path / "traces")
        self._fake_profiler(monkeypatch, writes="trace.pb")
        with tracing.capture_trace(target):
            pass
        assert "[srt][trace][WARN]" not in capsys.readouterr().err
