"""srt-check static analyzer: every pass, pragma grammar, baseline.

Each pass gets a violating fixture and a clean fixture; the pragma and
baseline machinery get their own coverage; and the repo itself must
scan clean against the committed baseline (the CI gate this tool backs
— see ci/premerge-build.sh).
"""

import importlib.util
import json
import os
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "srt_check", os.path.join(REPO_ROOT, "tools", "srt_check.py")
)
srt = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(srt)

# package-relative paths: SRT002/SRT003 only fire inside the runtime
# package, and utils/config.py is SRT001's one sanctioned home
PKG = "spark_rapids_jni_tpu"


def scan(tmp_path, rel, src):
    full = tmp_path / rel
    full.parent.mkdir(parents=True, exist_ok=True)
    full.write_text(textwrap.dedent(src))
    return srt.scan_file(str(full), str(tmp_path))


def passes_of(findings):
    return [f.pass_id for f in findings]


class TestEnvOutsideConfig:
    def test_prefixed_read_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            V = os.environ.get("SPARK_RAPIDS_TPU_FOO", "0")
        """)
        assert passes_of(got) == ["SRT001"]
        assert "SPARK_RAPIDS_TPU_FOO" in got[0].message

    def test_all_read_shapes_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            A = os.getenv("SPARK_RAPIDS_TPU_A")
            B = os.environ["SPARK_RAPIDS_TPU_B"]
            C = "SPARK_RAPIDS_TPU_C" in os.environ
        """)
        assert passes_of(got) == ["SRT001"] * 3

    def test_config_py_exempt(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/config.py", """
            import os
            V = os.environ.get("SPARK_RAPIDS_TPU_FOO")
        """)
        assert got == []

    def test_write_is_not_a_read(self, tmp_path):
        # tests and fixtures SET knobs through the environment; only
        # reads bypass the flag plane
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            os.environ["SPARK_RAPIDS_TPU_FOO"] = "1"
        """)
        assert got == []

    def test_unprefixed_module_level_read_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            HOME = os.environ.get("HOME")
        """)
        assert got == []


class TestBroadExcept:
    def test_swallow_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                except Exception:
                    return None
        """)
        assert passes_of(got) == ["SRT002"]

    def test_bare_reraise_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                except Exception:
                    cleanup()
                    raise
        """)
        assert got == []

    def test_faults_routing_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import faults
            def f():
                try:
                    g()
                except Exception as e:
                    raise faults.classify(e, "foo")
        """)
        assert got == []

    def test_breaker_feed_counts_as_routing(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f(breaker):
                try:
                    g()
                except BaseException as e:
                    breaker.note_failure(e)
        """)
        assert got == []

    def test_pragma_suppresses(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                except Exception:  # srt: allow-broad-except(best-effort cleanup)
                    return None
        """)
        assert got == []

    def test_pragma_on_line_above_suppresses(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                # srt: allow-broad-except(best-effort cleanup)
                except Exception:
                    return None
        """)
        assert got == []

    def test_outside_package_not_flagged(self, tmp_path):
        # tools are offline drivers without the taxonomy
        got = scan(tmp_path, "tools/foo.py", """
            def f():
                try:
                    g()
                except Exception:
                    return None
        """)
        assert got == []


class TestHotEnvRead:
    def test_read_in_function_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            def hot():
                return os.environ.get("SOME_KNOB") == "1"
        """)
        assert passes_of(got) == ["SRT003"]

    def test_module_level_read_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            KNOB = os.environ.get("SOME_KNOB")
        """)
        assert got == []

    def test_prefixed_in_function_reports_srt001_once(self, tmp_path):
        # one finding per site: the sharper pass wins, no double report
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            def hot():
                return os.environ.get("SPARK_RAPIDS_TPU_FOO")
        """)
        assert passes_of(got) == ["SRT001"]


class TestWallclockInReplay:
    def test_time_time_in_faults_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/faults.py", """
            import time
            def decide():
                return time.time() % 2 == 0
        """)
        assert passes_of(got) == ["SRT004"]

    def test_random_in_buckets_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/buckets.py", """
            import random
            def pick():
                return random.random()
        """)
        assert passes_of(got) == ["SRT004"]

    def test_monotonic_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/faults.py", """
            import time
            def interval():
                return time.monotonic()
        """)
        assert got == []

    def test_other_modules_unscoped(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import time
            def now():
                return time.time()
        """)
        assert got == []


class TestRetryOnDonated:
    def test_donated_retry_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import faults
            def f(exe, table):
                return faults.run_with_retry(
                    lambda: exe(table, donate=True), site="seg"
                )
        """)
        assert passes_of(got) == ["SRT005"]

    def test_donate_false_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import faults
            def f(exe, table):
                return faults.run_with_retry(
                    lambda: exe(table, donate=False), site="seg"
                )
        """)
        assert got == []


class TestMetricNameConvention:
    def test_bad_shape_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import metrics
            def f():
                metrics.counter_add("Bad Name")
        """)
        assert passes_of(got) == ["SRT006"]

    def test_unregistered_namespace_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import metrics
            def f():
                metrics.counter_add("nonexistentns.thing")
        """)
        assert passes_of(got) == ["SRT006"]
        assert "nonexistentns" in got[0].message

    def test_registered_dotted_name_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import flight, metrics
            def f():
                metrics.counter_add("op.groupby.calls")
                metrics.bytes_add("wire.bytes_in", 4)
                flight.record("I", "spill.evict", 1)
        """)
        assert got == []

    def test_dynamic_names_skipped(self, tmp_path):
        # computed names can't be checked statically — not a finding
        got = scan(tmp_path, f"{PKG}/foo.py", """
            from .utils import metrics
            def f(name):
                metrics.counter_add("op." + name)
        """)
        assert got == []


class TestStatsAppend:
    def test_raw_append_in_planstats_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/planstats.py", """
            def sneak(path):
                return open(path, "ab")
        """)
        assert "SRT010" in passes_of(got)

    def test_helper_site_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/planstats.py", """
            def _open_append(path):
                return open(path, "ab")
        """)
        assert got == []

    def test_read_mode_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/planstats.py", """
            def load(path):
                with open(path, "rb") as f:
                    return f.read()
        """)
        assert got == []

    def test_mode_keyword_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/planstats.py", """
            def sneak(path):
                return open(path, mode="a")
        """)
        assert "SRT010" in passes_of(got)

    def test_stats_path_append_elsewhere_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def dump(planstats_path, rec):
                with open(planstats_path, "a") as f:
                    f.write(rec)
        """)
        assert "SRT010" in passes_of(got)

    def test_stats_dirname_literal_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            import os
            def dump(rec):
                with open(os.path.join("/tmp/srt-planstats", "x.wal"),
                          "ab") as f:
                    f.write(rec)
        """)
        assert "SRT010" in passes_of(got)

    def test_unrelated_append_elsewhere_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def log(path, line):
                with open(path, "a") as f:
                    f.write(line)
        """)
        assert got == []

    def test_pragma_suppresses(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/utils/planstats.py", """
            def migrate(path):
                # srt: allow-stats-append(one-shot v0 store migration)
                return open(path, "ab")
        """)
        assert got == []

    def test_repo_planstats_has_one_sanctioned_site(self):
        # the shipped module must route every append through the helper
        findings = srt.scan_file(os.path.join(
            REPO_ROOT, PKG, "utils", "planstats.py"
        ))
        assert [f for f in findings if f.pass_id == "SRT010"] == []


class TestPragmaGrammar:
    def test_empty_reason_is_a_finding(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                except Exception:  # srt: allow-broad-except()
                    return None
        """)
        # the pragma doesn't suppress AND is itself flagged
        assert sorted(passes_of(got)) == ["SRT000", "SRT002"]

    def test_unknown_slug_is_a_finding(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            X = 1  # srt: allow-everything(why not)
        """)
        assert passes_of(got) == ["SRT000"]
        assert "allow-everything" in got[0].message

    def test_docstring_mention_is_not_a_pragma(self, tmp_path):
        # only real COMMENT tokens parse as pragmas: docs quoting the
        # grammar (like this tool's own docstring) are inert
        got = scan(tmp_path, f"{PKG}/foo.py", '''
            """Docs: write # srt: allow-broad-except(reason) above it."""
            MSG = "add '# srt: allow-broad-except(<reason>)' if deliberate"
        ''')
        assert got == []

    def test_wrong_slug_does_not_suppress(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/foo.py", """
            def f():
                try:
                    g()
                except Exception:  # srt: allow-wallclock(wrong pass)
                    return None
        """)
        assert "SRT002" in passes_of(got)


class TestBaseline:
    SRC = """
        import os
        V = os.environ.get("SPARK_RAPIDS_TPU_FOO")
    """

    def _write(self, tmp_path):
        full = tmp_path / PKG / "foo.py"
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(textwrap.dedent(self.SRC))
        return full

    def test_new_finding_fails_baselined_passes(self, tmp_path, capsys):
        self._write(tmp_path)
        bl = tmp_path / "baseline.json"
        argv = [f"{PKG}/foo.py", "--root", str(tmp_path),
                "--baseline", str(bl)]
        assert srt.main(argv) == 1  # new finding -> gate fails
        assert srt.main(argv + ["--write-baseline"]) == 0
        assert srt.main(argv) == 0  # grandfathered -> passes
        out = capsys.readouterr().out
        assert "[baselined]" in out

    def test_fixed_finding_reports_stale_entry(self, tmp_path):
        full = self._write(tmp_path)
        bl = tmp_path / "baseline.json"
        argv = [f"{PKG}/foo.py", "--root", str(tmp_path),
                "--baseline", str(bl)]
        srt.main(argv + ["--write-baseline"])
        full.write_text("V = None\n")  # fix the violation
        findings = srt.scan_file(str(full), str(tmp_path))
        assert findings == []
        doc = json.loads(bl.read_text())
        assert len(doc["fingerprints"]) == 1  # now stale, prunable

    def test_fingerprint_survives_line_motion(self, tmp_path):
        full = self._write(tmp_path)
        before = srt.scan_file(str(full), str(tmp_path))[0].fingerprint
        full.write_text("# a comment\n\n" + textwrap.dedent(self.SRC))
        after = srt.scan_file(str(full), str(tmp_path))[0].fingerprint
        assert before == after  # content-hashed, not line-numbered

    def test_json_output_shape(self, tmp_path, capsys):
        self._write(tmp_path)
        rc = srt.main([f"{PKG}/foo.py", "--root", str(tmp_path),
                       "--baseline", str(tmp_path / "none.json"),
                       "--json"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["counts"]["new"] == 1
        f = doc["findings"][0]
        assert f["pass"] == "SRT001"
        assert f["path"].endswith("foo.py") and f["line"] >= 1


class TestRepoClean:
    def test_repo_scans_clean_against_committed_baseline(self):
        """The acceptance gate: the tree + tools/srt_check_baseline.json
        must make `python tools/srt_check.py` exit 0."""
        findings = srt.scan_repo(repo_root=REPO_ROOT)
        baseline = srt.load_baseline(srt.DEFAULT_BASELINE)
        new = [f.render() for f in findings
               if f.fingerprint not in baseline]
        assert new == []


class TestHostSync:
    def test_item_flagged_in_hot_module(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/plan.py", """
            def f(col):
                return col.data.item()
        """)
        assert passes_of(got) == ["SRT009"]
        assert "sync" in got[0].message

    def test_int_over_device_local_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/bucketed.py", """
            import jax.numpy as jnp

            def f(a):
                count = jnp.sum(a)
                return int(count)
        """)
        assert passes_of(got) == ["SRT009"]
        assert "int()" in got[0].message

    def test_np_asarray_flagged(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/plan.py", """
            import numpy as np

            def f(x):
                return np.asarray(x)
        """)
        assert passes_of(got) == ["SRT009"]

    def test_host_attr_reads_are_clean(self, tmp_path):
        # Table/Column bookkeeping is host data — int() over it is free
        got = scan(tmp_path, f"{PKG}/plan.py", """
            def f(table):
                n = int(table.row_count)
                m = int(table.logical_row_count)
                return n + m
        """)
        assert got == []

    def test_host_call_results_are_clean(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/plan.py", """
            def f(xs, table):
                n = len(xs)
                b = int(table_bytes(table))
                return int(n) + b
        """)
        assert got == []

    def test_pragma_suppresses(self, tmp_path):
        got = scan(tmp_path, f"{PKG}/plan.py", """
            import jax.numpy as jnp

            def f(a):
                count = jnp.sum(a)
                # srt: allow-host-sync(segment boundary: one sizing read)
                return int(count)
        """)
        assert got == []

    def test_only_hot_modules_in_scope(self, tmp_path):
        # outside plan.py/bucketed.py a sync is someone else's problem
        got = scan(tmp_path, f"{PKG}/ops/foo.py", """
            def f(col):
                return col.data.item()
        """)
        assert got == []

    def test_rebound_host_local_is_clean(self, tmp_path):
        # a name rebound from device to host drops out of the taint set
        got = scan(tmp_path, f"{PKG}/plan.py", """
            import jax.numpy as jnp

            def f(a):
                x = jnp.sum(a)
                x = len([1])
                return int(x)
        """)
        assert got == []


class TestPruneBaseline:
    def test_prune_drops_only_stale_entries(self, tmp_path, capsys):
        full = tmp_path / PKG / "foo.py"
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(textwrap.dedent("""
            import os
            A = os.environ.get("SPARK_RAPIDS_TPU_A")
            B = os.environ.get("SPARK_RAPIDS_TPU_B")
        """))
        bl = tmp_path / "baseline.json"
        argv = [f"{PKG}/foo.py", "--root", str(tmp_path),
                "--baseline", str(bl)]
        assert srt.main(argv + ["--write-baseline"]) == 0
        assert len(json.loads(bl.read_text())["fingerprints"]) == 2
        # fix ONE violation: its fingerprint goes stale
        full.write_text(textwrap.dedent("""
            import os
            A = os.environ.get("SPARK_RAPIDS_TPU_A")
        """))
        assert srt.main(argv + ["--prune-baseline"]) == 0
        out = capsys.readouterr().out
        assert "pruned 1 stale" in out
        doc = json.loads(bl.read_text())
        # the still-live grandfathered entry survives the prune
        assert len(doc["fingerprints"]) == 1
        assert srt.main(argv) == 0  # gate still green afterwards

    def test_prune_without_stale_is_a_noop(self, tmp_path):
        full = tmp_path / PKG / "foo.py"
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(
            'import os\nV = os.environ.get("SPARK_RAPIDS_TPU_V")\n'
        )
        bl = tmp_path / "baseline.json"
        argv = [f"{PKG}/foo.py", "--root", str(tmp_path),
                "--baseline", str(bl)]
        srt.main(argv + ["--write-baseline"])
        before = bl.read_text()
        assert srt.main(argv + ["--prune-baseline"]) == 0
        assert bl.read_text() == before

    def test_prune_missing_baseline_is_safe(self, tmp_path):
        assert srt.prune_baseline(str(tmp_path / "none.json"), set()) == 0
