"""Plan-statistics store + drift layer (utils/planstats.py, ISSUE 16).

Covers the crash contract (torn tails recover silently — the
serving/durable.py WAL discipline, minus the typed quarantine: stats
are telemetry, so a reader never raises), the record hook through the
profiler, the drift checks, rotation, and the <5µs disabled-path bound
for the new dispatch hooks.
"""

import json
import os
import struct
import time
import zlib

import pytest

from spark_rapids_jni_tpu.utils import config, metrics, planstats, profiler


@pytest.fixture(autouse=True)
def _isolated_store(tmp_path, monkeypatch):
    """Every test gets its own store dir; flags and module state reset.
    Env overrides leaked by an earlier module (bench helpers run
    in-process export PLANSTATS_DIR for their subprocesses) are
    dropped so the flag below is the only knob."""
    for env in ("PLANSTATS", "PLANSTATS_DIR"):
        monkeypatch.delenv("SPARK_RAPIDS_TPU_" + env, raising=False)
    planstats.reset()
    profiler.reset()
    metrics.reset()
    config.set_flag("PLANSTATS_DIR", str(tmp_path / "stats"))
    yield
    for name in ("PLANSTATS", "PLANSTATS_DIR", "PLANSTATS_ROTATE_MB",
                 "DRIFT_ROWS_FACTOR", "DRIFT_HBM_FACTOR", "PROFILE"):
        config.clear_flag(name)
    planstats.reset()
    profiler.reset()
    metrics.reset()


STATIC = {
    "segments": [
        {"kind": "fused", "ops": [0, 1], "rows_bound": 100,
         "est_hbm_bytes": 4000},
    ],
    "rows_out_bound": 100,
    "est_hbm_peak_bytes": 4000,
}


def _run_once(rows_out=50, out_bytes=400, label="t", bucket=None,
              plan=None, static=STATIC, kind="fused", seg_ops=("filter",)):
    """One profile session with one segment — the shape every dispatch
    entry produces."""
    with profiler.profile_session(
        plan or [{"op": "filter"}], label=label, schema="i32,i64",
        bucket=bucket, static=static,
    ):
        tok = profiler.segment_begin(
            0, kind, [{"op": name} for name in seg_ops], rows_in=100
        )
        profiler.segment_end(tok, rows_out=rows_out, out_bytes=out_bytes)


class TestStoreRoundTrip:
    def test_every_session_appends_one_record(self):
        for _ in range(3):
            _run_once()
        recs = planstats.load()
        assert len(recs) == 3
        r = recs[-1]
        assert r["fp"] == planstats.plan_fingerprint([{"op": "filter"}])
        assert r["schema"] == "i32,i64"
        assert r["label"] == "t"
        seg = r["segments"][0]
        assert seg["rows_in"] == 100
        assert seg["rows_out"] == 50
        assert seg["out_bytes"] == 400
        assert r["bytes_moved"] == 400
        assert r["pred"]["segments"][0]["rows_bound"] == 100

    def test_disabled_gate_appends_nothing(self):
        config.clear_flag("PLANSTATS_DIR")
        config.set_flag("PROFILE", "on")  # sessions still open
        _run_once()
        assert planstats.record_session({"plan": None}) is None

    def test_counter_deltas_ride_the_record(self):
        base = planstats.counter_snapshot()
        metrics.counter_add("retry.attempts", 3)
        rec = planstats.record_session(
            {"plan": [{"op": "filter"}], "segments": []}, base
        )
        assert rec["counters"] == {"retry.attempts": 3}

    def test_fingerprint_is_stable_across_key_order(self):
        a = planstats.plan_fingerprint([{"op": "filter", "mask": 1}])
        b = planstats.plan_fingerprint([{"mask": 1, "op": "filter"}])
        assert a == b


class TestTornTail:
    def test_truncation_at_every_byte_recovers_complete_records(self):
        """kill -9 mid-append leaves a prefix; EVERY prefix must load
        to exactly the records whose frames fit whole — never an
        error, never a phantom record, tail dropped silently (the
        satellite-2 contract)."""
        for i in range(4):
            _run_once(rows_out=10 + i)
        (path,) = [
            os.path.join(planstats.stats_dir(), f)
            for f in os.listdir(planstats.stats_dir())
        ]
        blob = open(path, "rb").read()
        # frame ends from the framing itself
        ends = [len(planstats._MAGIC)]
        off = len(planstats._MAGIC)
        while off < len(blob):
            length, _crc = planstats._FRAME.unpack_from(blob, off)
            off += planstats._FRAME.size + length
            ends.append(off)
        assert ends[-1] == len(blob)
        cut_path = path + ".cut"
        for cut in range(len(planstats._MAGIC), len(blob) + 1):
            with open(cut_path, "wb") as f:
                f.write(blob[:cut])
            recs, torn = planstats.read_stats_file(cut_path)
            whole = max(i for i, e in enumerate(ends) if e <= cut)
            assert len(recs) == whole, f"cut={cut}"
            assert torn == (0 if cut in ends else 1), f"cut={cut}"
            for i, r in enumerate(recs):
                assert r["segments"][0]["rows_out"] == 10 + i
        os.remove(cut_path)

    def test_load_skips_torn_tail_silently(self):
        _run_once(rows_out=1)
        _run_once(rows_out=2)
        (path,) = [
            os.path.join(planstats.stats_dir(), f)
            for f in os.listdir(planstats.stats_dir())
        ]
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:-7])  # mid-record truncation
        recs = planstats.load()
        assert [r["segments"][0]["rows_out"] for r in recs] == [1]

    def test_mid_file_corruption_stops_scan_without_raising(self):
        """Unlike durable journals (client-acknowledged state, typed
        quarantine) a corrupt stats file degrades to what survived."""
        _run_once(rows_out=1)
        _run_once(rows_out=2)
        (path,) = [
            os.path.join(planstats.stats_dir(), f)
            for f in os.listdir(planstats.stats_dir())
        ]
        blob = bytearray(open(path, "rb").read())
        blob[len(planstats._MAGIC) + planstats._FRAME.size + 2] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(blob))
        recs, torn = planstats.read_stats_file(path)
        assert recs == [] and torn == 0
        assert planstats.stats_doc().get("planstats.corrupt_files", 0) >= 1

    def test_bad_magic_is_not_fatal(self, tmp_path):
        p = str(tmp_path / "junk.wal")
        with open(p, "wb") as f:
            f.write(b"not a stats file")
        recs, torn = planstats.read_stats_file(p)
        assert recs == [] and torn == 0

    def test_append_self_heals_after_torn_write(self):
        _run_once(rows_out=1)
        w = planstats._writer()
        with w._lock:
            w._f.write(b"\x01\x02\x03")  # torn frame fragment
            w._f.flush()
        _run_once(rows_out=2)
        recs = planstats.load()
        assert [r["segments"][0]["rows_out"] for r in recs] == [1, 2]


class TestRotation:
    def test_rotation_keeps_one_old_generation(self):
        config.set_flag("PLANSTATS_ROTATE_MB", 0.0005)  # ~524 bytes
        for i in range(8):
            _run_once(rows_out=i + 1)
        files = sorted(os.listdir(planstats.stats_dir()))
        assert any(f.endswith(".wal.1") for f in files)
        assert planstats.stats_doc()["planstats.rotations"] >= 1
        # load() still reads both generations
        assert len(planstats.load()) >= 2


class TestDrift:
    def test_steady_state_raises_no_findings(self):
        for _ in range(4):
            _run_once()
        assert not planstats.stats_doc()["findings"]

    def test_history_skew_flags_cardinality(self):
        config.set_flag("DRIFT_ROWS_FACTOR", 2.0)
        for _ in range(3):
            _run_once(rows_out=50, out_bytes=400)
        _run_once(rows_out=5000, out_bytes=40000)
        last = planstats.load()[-1]
        kinds = [f["type"] for f in last["drift"]]
        assert "cardinality" in kinds
        assert planstats.stats_doc()["drift.cardinality"] >= 1

    def test_static_bound_violation_flags_cardinality(self):
        _run_once(rows_out=500)  # bound is 100
        last = planstats.load()[-1]
        assert any(
            f["type"] == "cardinality" and "static" in f["detail"]
            for f in last["drift"]
        )

    def test_hbm_overrun_flags_hbm(self):
        # proxy = rows_in*width + out_bytes with width 400 -> ~44000B
        # vs est 4000 * factor 2
        _run_once(rows_out=100, out_bytes=40000)
        last = planstats.load()[-1]
        assert any(f["type"] == "hbm" for f in last["drift"])

    def test_bucket_scales_the_hbm_estimate(self):
        # same bytes but bucket 1024 over bound 100 scales est x10.24:
        # no finding
        _run_once(rows_out=100, out_bytes=40000, bucket=1024)
        last = planstats.load()[-1]
        assert not any(
            f["type"] == "hbm" for f in last.get("drift") or []
        )

    def test_segmentation_change_flags_once(self):
        static = {
            "segments": [
                {"kind": "fused", "ops": [0], "rows_bound": 100,
                 "est_hbm_bytes": 4000},
                {"kind": "exact", "ops": [1], "rows_bound": 100,
                 "est_hbm_bytes": 4000},
            ],
            "rows_out_bound": 100,
            "est_hbm_peak_bytes": 4000,
        }
        _run_once(static=static)  # observed: ONE fused segment
        last = planstats.load()[-1]
        assert any(f["type"] == "segmentation" for f in last["drift"])

    def test_mesh_segment_is_not_segmentation_drift(self):
        _run_once(kind="mesh")
        last = planstats.load()[-1]
        assert not any(
            f["type"] == "segmentation"
            for f in last.get("drift") or []
        )

    def test_a_join_that_rode_its_segment_is_not_segmentation_drift(self):
        # the static prediction knows nothing of the build side: three
        # boundaries where the run, having read it, fused (PR 38)
        static = {
            "segments": [
                {"kind": "exact", "ops": [i], "rows_bound": 10,
                 "est_hbm_bytes": 40} for i in range(3)],
            "rows_out_bound": 10,
            "est_hbm_peak_bytes": 40,
        }
        _run_once(static=static, seg_ops=("filter", "join", "groupby"))
        last = planstats.load()[-1]
        assert not (last.get("drift") or [])

    def test_history_seeds_from_disk_across_reset(self):
        config.set_flag("DRIFT_ROWS_FACTOR", 2.0)
        for _ in range(3):
            _run_once(rows_out=50)
        planstats.reset()  # fresh process analog: in-memory history gone
        config.set_flag("PLANSTATS_DIR", planstats.stats_dir())
        _run_once(rows_out=5000, out_bytes=40000)
        last = planstats.load()[-1]
        assert any(
            f["type"] == "cardinality" and "history" in f["detail"]
            for f in last["drift"]
        )


class TestReport:
    def test_percentiles_and_pred_per_segment(self):
        for i in range(5):
            _run_once(rows_out=40 + i)
        rep = planstats.drift_report()
        assert rep["records"] == 5
        (g,) = rep["groups"]
        assert g["runs"] == 5
        (seg,) = g["segments"]
        assert seg["rows_out"]["n"] == 5
        assert seg["rows_out"]["p50"] == 42
        assert seg["rows_out"]["max"] == 44
        assert seg["pred"]["rows_bound"] == 100
        text = planstats.render_drift(rep)
        assert "rows_out p50/p95/max" in text
        assert "pred bound 100" in text

    def test_groups_key_on_fp_schema_bucket(self):
        _run_once(bucket=128)
        _run_once(bucket=256)
        _run_once(plan=[{"op": "cast"}])
        rep = planstats.drift_report()
        assert len(rep["groups"]) == 3

    def test_summary_block_shape(self):
        _run_once(rows_out=500)  # triggers a finding
        s = planstats.summary()
        assert s["records"] == 1
        assert s["plans"] == 1
        assert s["findings"].get("cardinality", 0) >= 1

    def test_summary_none_when_empty(self):
        assert planstats.summary() is None


class TestDisabledOverhead:
    def test_disabled_maybe_session_under_5us(self):
        """The acceptance bound: with everything off, the dispatch-
        plane hook (maybe_session + the planstats gate) costs <5µs."""
        config.clear_flag("PLANSTATS_DIR")
        assert not profiler.enabled()
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with profiler.maybe_session([{"op": "filter"}]):
                pass
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6, f"{per * 1e6:.2f}us"

    def test_disabled_record_session_is_none(self):
        config.clear_flag("PLANSTATS_DIR")
        assert planstats.record_session({"plan": None}) is None


class TestFraming:
    def test_frame_layout_matches_wal_discipline(self):
        """len|crc32|payload after the SRTS1 magic — the durable.py
        framing with a distinct magic, so neither reader misparses the
        other's files."""
        rec = planstats.record_session(
            {"plan": [{"op": "filter"}], "segments": []}
        )
        (path,) = [
            os.path.join(planstats.stats_dir(), f)
            for f in os.listdir(planstats.stats_dir())
        ]
        blob = open(path, "rb").read()
        assert blob.startswith(b"SRTS1\n")
        length, crc = struct.unpack_from("<II", blob, 6)
        payload = blob[6 + 8:6 + 8 + length]
        assert zlib.crc32(payload) & 0xFFFFFFFF == crc
        assert json.loads(payload.decode()) == rec
