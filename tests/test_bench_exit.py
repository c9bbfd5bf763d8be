"""bench.py exit-clean + fast-fail guards (ISSUE 2 satellites) and the
SIGTERM telemetry-flush integration (ISSUE 3 satellite).

Two consecutive rounds ended ``rc=124, parsed=null``: the driver's
timeout killed the ladder between a progress line and the next emit.
These tests pin the repair surface: structured skip records, the
unreachable-failure classifier behind the fast-fail ladder, the
last-emitted-line guarantee the SIGTERM handler re-prints — and, since
the flight-recorder PR, that the same handler flushes the METRICS_DUMP
and FLIGHT_DUMP artifacts before ``os._exit`` (atexit never runs past
it), so an rc=124 run still leaves its telemetry behind.
"""

import json
import os
import subprocess
import sys
import textwrap

import bench

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFailureRecords:
    def test_skipped_flag(self):
        e = bench._failure_record(
            "groupby100m", "skipped: budget 3300s exhausted",
            exc_type="BudgetExceeded", elapsed_s=3301.2, skipped=True,
        )
        assert e["failure"]["type"] == "BudgetExceeded"
        assert e["failure"]["skipped"] is True
        assert e["failure"]["elapsed_s"] == 3301.2
        # old readers still see the flat error string
        assert "budget" in e["error"]

    def test_default_not_skipped(self):
        e = bench._failure_record("join", ValueError("boom"))
        assert e["failure"]["skipped"] is False
        assert e["failure"]["type"] == "ValueError"


class TestUnreachableClassifier:
    def test_unreachable_markers(self):
        for msg in (
            "device unreachable",
            "UNAVAILABLE: socket closed",
            "DEADLINE_EXCEEDED while fetching",
            "failed to connect to device",
            "Failed to connect to remote host",  # capitalized gRPC text
            "Socket closed",
        ):
            e = bench._failure_record("cfg", msg, exc_type="SubprocessFailed")
            assert bench._unreachable_failure(e), msg

    def test_timeout_type_counts_as_unreachable(self):
        e = bench._failure_record(
            "cfg", "timeout 1800s", exc_type="TimeoutExpired"
        )
        assert bench._unreachable_failure(e)

    def test_structured_timeout_record_counts_as_unreachable(self):
        # the per-arm {type:"timeout"} record (an arm overrunning its
        # wall-clock slice) classifies transient like TimeoutExpired
        e = bench._failure_record("cfg", "timeout 900s", exc_type="timeout")
        assert e["failure"]["type"] == "timeout"
        assert bench._unreachable_failure(e)

    def test_genuine_crash_is_not_unreachable(self):
        e = bench._failure_record(
            "cfg", "assertion failed: groupby-sum mismatch vs numpy",
            exc_type="SubprocessFailed",
        )
        assert not bench._unreachable_failure(e)

    def test_tolerates_old_records_without_failure_block(self):
        assert not bench._unreachable_failure({"name": "x", "error": "boom"})
        assert bench._unreachable_failure(
            {"name": "x", "error": "device unreachable"}
        )


class TestSigtermTelemetryFlush:
    def test_sigterm_flushes_metrics_and_flight_dumps(self, tmp_path):
        """A SIGTERM'd bench process must leave BOTH dump files behind
        and still print the headline JSON as its final stdout line —
        the rc=124 postmortem contract. The span is deliberately left
        open when the signal lands: that is exactly the state a killed
        run dies in, and the flight tail must show it."""
        mdump = tmp_path / "metrics.json"
        fdump = tmp_path / "flight.json"
        script = textwrap.dedent(
            f"""
            import os, signal, sys, time
            sys.path.insert(0, {_ROOT!r})
            import bench
            bench._install_exit_handlers()
            bench._metrics_enable()
            from spark_rapids_jni_tpu.utils import flight, metrics
            bench._LAST_LINE = '{{"metric": "sigterm-test"}}'
            with metrics.span("cfg.doomed"):
                flight.record("I", "probe.device_retry")
                os.kill(os.getpid(), signal.SIGTERM)
                time.sleep(30)
                sys.exit(3)  # handler never fired
            """
        )
        env = dict(os.environ)
        env.update({
            "SPARK_RAPIDS_TPU_METRICS_DUMP": str(mdump),
            "SPARK_RAPIDS_TPU_FLIGHT_DUMP": str(fdump),
            "JAX_PLATFORMS": "cpu",
        })
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=300, env=env, cwd=_ROOT,
        )
        assert proc.returncode == 0, (proc.returncode, proc.stderr[-2000:])
        # the final stdout line is the re-printed headline JSON
        last = proc.stdout.strip().splitlines()[-1]
        assert json.loads(last)["metric"] == "sigterm-test"
        # metrics snapshot flushed by the handler (atexit never ran)
        snap = json.loads(mdump.read_text())
        assert "counters" in snap
        # flight tail flushed too: the open span's B, the instant, and
        # the handler's own sigterm marker
        doc = json.loads(fdump.read_text())
        names = [e["name"] for e in doc["events"]]
        assert "cfg.doomed" in names
        assert "probe.device_retry" in names
        assert names[-1] == "bench.sigterm"
        # the span never closed — no E event for it (the crash shape
        # tools/trace2chrome.py renders as an unterminated X)
        assert not any(
            e["ph"] == "E" and e["name"] == "cfg.doomed"
            for e in doc["events"]
        )


class TestBudgetExhaustedRun:
    def test_zero_budget_run_exits_clean_with_parseable_headline(self):
        """A fully budget-starved run must still exit 0 with the
        headline JSON as the final stdout line, every ladder arm
        recorded as a skipped BudgetExceeded, and the mesh/Arrow tail
        skipped by its floors instead of starting unbounded work —
        the repair for the rc=124, parsed=null rounds."""
        env = dict(os.environ)
        env.update({
            "SRT_BENCH_BUDGET_S": "0",
            "JAX_PLATFORMS": "cpu",
        })
        proc = subprocess.run(
            [sys.executable, "bench.py"], capture_output=True,
            text=True, timeout=280, env=env, cwd=_ROOT,
        )
        assert proc.returncode == 0, (proc.returncode, proc.stderr[-2000:])
        last = proc.stdout.strip().splitlines()[-1]
        doc = json.loads(last)
        assert doc["metric"] == "groupby_sum_100M_int64"
        by_name = {c["name"]: c for c in doc["configs"]}
        # every budgeted ladder arm is present as a structured skip
        assert set(bench._LADDER) <= set(by_name)
        for arm in bench._LADDER:
            c = by_name[arm]
            assert c["failure"]["type"] == "BudgetExceeded"
            assert c["failure"]["skipped"] is True
        # the mesh tail arms likewise carry typed skip records instead
        # of vanishing into a progress line: the skew arm is
        # budget-starved, the TPC-DS-from-parquet arm is opt-in
        skew = by_name[
            "config 4: distributed zipf skew, 8-device CPU mesh"
        ]
        assert skew["failure"]["type"] == "BudgetExceeded"
        assert skew["failure"]["skipped"] is True
        tpcds = by_name[
            "config 4: TPC-DS q5/q23/q64 from parquet, 8-dev mesh"
        ]
        assert tpcds["failure"]["type"] == "OptInSkipped"
        assert tpcds["failure"]["skipped"] is True
        # the tail floors declined to start the unbounded stages
        assert "skipping arrow baseline" in proc.stderr

    def test_walk_reserves_a_tail_window(self):
        # the walk must end early enough that both mesh stages and the
        # Arrow baseline can still start inside the budget deadline
        assert bench._TAIL_RESERVE_S > (
            2 * bench._MESH_STAGE_FLOOR_S + bench._ARROW_FLOOR_S
        )

    def test_superseded_slow_arms_are_manual(self):
        # losers of the packed/chunked A/Bs no longer walk: each alone
        # could eat the whole tail window
        for arm in (
            "groupby16m_packed_pallas32",
            "groupby100m_packed_pallas32",
            "groupby100m_packed",
            "groupby100m_chunked",
        ):
            assert bench._ARM_TIERS[arm] == "manual"
            assert arm not in bench._LADDER
            # still runnable one-off
            assert arm in bench._SUBPROCESS_CONFIGS


class TestEmitGuarantee:
    def test_emit_stores_last_line_parseable(self, capsys):
        bench._emit([{"name": "x", "error": "boom",
                      "failure": {"type": "Error", "message": "boom",
                                  "elapsed_s": None, "retries": 0,
                                  "skipped": False}}], "cpu")
        out = capsys.readouterr().out.strip().splitlines()[-1]
        doc = json.loads(out)
        assert doc["metric"] == "groupby_sum_100M_int64"
        # the SIGTERM handler re-prints exactly this line
        assert bench._LAST_LINE == out
        assert json.loads(bench._LAST_LINE)["configs"][0]["name"] == "x"
