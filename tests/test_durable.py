"""Durable serving plane: crash-safe checkpoint/restore, reconnect.

The ISSUE-14 contract under test: with ``SPARK_RAPIDS_TPU_DURABLE=on``
the daemon journals every namespace mutation (upload / plan output /
free / bye) to a per-session write-ahead log with CRC-framed fsync'd
records; a restarted daemon replays the journals into live sessions —
tables byte-identical, budgets and HBM accounting re-charged, the
idempotency window intact — BEFORE the listener accepts traffic, and
warm-starts the compile cache from the plan manifest so replayed plans
recompile nothing. Torn journal tails (crash mid-append) are truncated
and recovered; mid-file corruption quarantines that one session and
never crashes the daemon. Clients reconnect with a resume token and
replay mutating commands by request id for at-most-once application.
The disabled path (the default) costs under 5µs per mutation.
"""

import os
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import pipeline
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.serving import durable, frames
from spark_rapids_jni_tpu.utils import config, faults, metrics, spill

I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)
STR = int(dt.TypeId.STRING)

# one jittable op so warm-start exercises the compile cache
CAST = [{"op": "cast", "column": 1, "type_id": F64}]


@pytest.fixture(autouse=True)
def _durable_env(tmp_path):
    """Every test runs durable-on against its own checkpoint dir
    (tests that need the disabled path clear the flag themselves)."""
    config.set_flag("DURABLE", "on")
    config.set_flag("CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    durable.reset()
    yield
    pipeline.drain()
    for name in ("DURABLE", "CHECKPOINT_DIR", "METRICS", "FAULTS",
                 "PIPELINE", "BUCKETS", "HBM_BUDGET_GB",
                 "SERVE_MAX_SESSIONS", "SERVE_QUEUE_DEPTH",
                 "SERVE_SESSION_HBM_FRACTION", "SERVE_PORT"):
        config.clear_flag(name)
    pipeline.depth()


def _string_wire(strings):
    payload = b"".join(s.encode() for s in strings)
    offs = np.zeros(len(strings) + 1, np.int32)
    np.cumsum([len(s.encode()) for s in strings], out=offs[1:])
    return offs.tobytes() + payload


def _batch(n: int, seed: int = 0):
    rng = np.random.default_rng(n + 7919 * seed)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % 5 != 0).astype(np.uint8)
    strs = [("s" * (int(x) % 3 + 1)) for x in k]
    return (
        [I64, I64, STR], [0, 0, 0],
        [k.tobytes(), v.tobytes(), _string_wire(strs)],
        [None, valid.tobytes(), None],
        n,
    )


def _canon(batch):
    type_ids, scales, datas, valids, n = batch
    return (
        list(type_ids), list(scales),
        [bytes(b) for b in datas],
        [None if v is None else bytes(v) for v in valids],
        int(n),
    )


# ---------------------------------------------------------------------------
# journal format: framing, torn tails, mid-file corruption
# ---------------------------------------------------------------------------
class TestJournal:
    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "a.wal")
        j = durable.Journal(p)
        recs = [
            {"t": "open", "name": "s", "weight": 1.0, "budget": 9,
             "token": "x"},
            {"t": "put", "local": 1, "bytes": 10, "file": "f.npz"},
            {"t": "free", "local": 1, "bytes": 10},
        ]
        for r in recs:
            j.append(r)
        j.close()
        got, torn, _ = durable.read_journal(p)
        assert torn == 0
        assert got == recs

    def test_truncation_at_every_byte_is_a_torn_tail(self, tmp_path):
        """Crash-mid-append leaves a prefix of the file; EVERY prefix
        must replay to exactly the records whose frames fit whole —
        never an error, never a phantom record."""
        p = str(tmp_path / "a.wal")
        j = durable.Journal(p)
        ends = [j._good]  # offset after magic = 0 records
        for i in range(4):
            j.append({"t": "put", "local": i, "bytes": i * 3,
                      "file": f"f{i}.npz"})
            ends.append(j._good)
        j.close()
        blob = open(p, "rb").read()
        assert ends[-1] == len(blob)
        cut_path = str(tmp_path / "cut.wal")
        for cut in range(len(durable._MAGIC), len(blob) + 1):
            with open(cut_path, "wb") as f:
                f.write(blob[:cut])
            got, torn, good = durable.read_journal(cut_path)
            whole = max(i for i, e in enumerate(ends) if e <= cut)
            assert len(got) == whole, f"cut={cut}"
            assert good == ends[whole], f"cut={cut}"
            assert torn == (0 if cut in ends else 1), f"cut={cut}"
            for i, r in enumerate(got):
                assert r["local"] == i

    def test_magic_missing_is_corrupt(self, tmp_path):
        p = str(tmp_path / "b.wal")
        with open(p, "wb") as f:
            f.write(b"not a journal at all")
        with pytest.raises(durable.CheckpointCorrupt):
            durable.read_journal(p)

    def test_mid_file_corruption_is_corrupt_not_torn(self, tmp_path):
        """A bad CRC with MORE bytes after it is disk corruption, not
        a crash artifact: typed error, never silent truncation."""
        p = str(tmp_path / "c.wal")
        j = durable.Journal(p)
        j.append({"t": "put", "local": 1, "bytes": 4, "file": "x"})
        first_end = j._good
        j.append({"t": "free", "local": 1, "bytes": 4})
        j.close()
        blob = bytearray(open(p, "rb").read())
        flip = len(durable._MAGIC) + durable._FRAME.size + 2
        assert flip < first_end
        blob[flip] ^= 0xFF
        with open(p, "wb") as f:
            f.write(blob)
        with pytest.raises(durable.CheckpointCorrupt) as ei:
            durable.read_journal(p)
        assert "mid-journal" in str(ei.value)

    def test_append_self_heals_after_torn_write(self, tmp_path):
        """An injected torn write (chaos site ``checkpoint``) leaves a
        partial frame; the NEXT append truncates back to the last good
        offset first, so one degraded record never poisons the log."""
        p = str(tmp_path / "d.wal")
        j = durable.Journal(p)
        j.append({"t": "put", "local": 1, "bytes": 2, "file": "x"})
        config.set_flag("FAULTS", "seed=3,checkpoint:permanent:1:1")
        try:
            with pytest.raises(faults.FaultError):
                j.append({"t": "put", "local": 2, "bytes": 2, "file": "y"})
        finally:
            config.set_flag("FAULTS", "")
        assert os.path.getsize(p) > j._good  # torn bytes on disk
        j.append({"t": "put", "local": 3, "bytes": 2, "file": "z"})
        j.close()
        got, torn, _ = durable.read_journal(p)
        assert torn == 0
        assert [r["local"] for r in got] == [1, 3]

    def test_restore_scan_truncates_torn_tail(self, tmp_path):
        d = str(tmp_path / "scan")
        os.makedirs(d)
        j = durable.Journal(os.path.join(d, "s1.wal"))
        j.append({"t": "open", "name": "n", "weight": 1.0, "budget": 8,
                  "token": "t"})
        good = j._good
        j.close()
        with open(os.path.join(d, "s1.wal"), "ab") as f:
            f.write(b"\x99" * 7)  # torn partial frame
        sessions, quarantined = durable.restore_scan(d)
        assert not quarantined
        assert len(sessions) == 1 and sessions[0].sid == "s1"
        assert os.path.getsize(os.path.join(d, "s1.wal")) == good

    def test_restore_scan_quarantines_corrupt_journal(self, tmp_path):
        d = str(tmp_path / "scan2")
        os.makedirs(d)
        j = durable.Journal(os.path.join(d, "bad.wal"))
        j.append({"t": "open", "name": "n", "weight": 1.0, "budget": 8,
                  "token": "t"})
        j.append({"t": "free", "local": 1, "bytes": 0})
        j.close()
        blob = bytearray(open(os.path.join(d, "bad.wal"), "rb").read())
        blob[len(durable._MAGIC) + durable._FRAME.size] ^= 0xFF
        with open(os.path.join(d, "bad.wal"), "wb") as f:
            f.write(blob)
        sessions, quarantined = durable.restore_scan(d)
        assert sessions == []
        assert "bad" in quarantined
        assert os.path.exists(os.path.join(d, "bad.wal.quarantined"))
        assert not os.path.exists(os.path.join(d, "bad.wal"))

    def test_bye_erases_session(self, tmp_path):
        d = str(tmp_path / "bye")
        os.makedirs(d)
        dlog = durable.SessionLog("s9", d)
        dlog.log_open("n", 1.0, 8, "tok")
        dlog.log_bye()
        sessions, quarantined = durable.restore_scan(d)
        assert sessions == [] and not quarantined
        assert not os.path.exists(os.path.join(d, "s9.wal"))


# ---------------------------------------------------------------------------
# checkpoint dir knob + sweep regression
# ---------------------------------------------------------------------------
class TestCheckpointDir:
    def test_parser_rejects_whitespace(self, monkeypatch):
        config.clear_flag("CHECKPOINT_DIR")
        monkeypatch.setenv("SPARK_RAPIDS_TPU_CHECKPOINT_DIR", "   ")
        with pytest.raises(ValueError) as ei:
            config.get_flag("CHECKPOINT_DIR")
        assert "SPARK_RAPIDS_TPU_CHECKPOINT_DIR" in str(ei.value)

    def test_parser_rejects_file_path(self, tmp_path, monkeypatch):
        config.clear_flag("CHECKPOINT_DIR")
        f = tmp_path / "plain-file"
        f.write_text("x")
        monkeypatch.setenv("SPARK_RAPIDS_TPU_CHECKPOINT_DIR", str(f))
        with pytest.raises(ValueError) as ei:
            config.get_flag("CHECKPOINT_DIR")
        assert "not a directory" in str(ei.value)

    def test_sweep_spares_checkpoint_files(self, tmp_path):
        """THE sweep regression: ``spill._sweep_at_exit`` (and
        ``spill.reset``) unconditionally unlink everything registered
        in ``_FILES``. Checkpoint payloads written through the same
        ``.npz`` serde must survive a sweep — a daemon restart that
        also tears down spill must not eat its own durable state."""
        ckpt_dir = config.get_flag("CHECKPOINT_DIR")
        os.makedirs(ckpt_dir, exist_ok=True)
        keep = os.path.join(ckpt_dir, "sess-t1.npz")
        with open(keep, "wb") as f:
            f.write(b"payload")
        gone = str(tmp_path / "spilled.npz")
        with open(gone, "wb") as f:
            f.write(b"spill")
        spill._FILES.update({keep, gone})
        try:
            spill._sweep_at_exit()
            assert os.path.exists(keep), "sweep ate a checkpoint file"
            assert not os.path.exists(gone)
            assert keep not in spill._FILES
        finally:
            spill._FILES.discard(keep)
            spill._FILES.discard(gone)

    def test_reset_spares_checkpoint_files(self):
        ckpt_dir = config.get_flag("CHECKPOINT_DIR")
        os.makedirs(ckpt_dir, exist_ok=True)
        keep = os.path.join(ckpt_dir, "sess-t2.npz")
        with open(keep, "wb") as f:
            f.write(b"payload")
        spill._FILES.add(keep)
        try:
            spill.reset()
            assert os.path.exists(keep)
        finally:
            os.unlink(keep)


# ---------------------------------------------------------------------------
# table payload serde (spill .npz round trip)
# ---------------------------------------------------------------------------
class TestPayloadSerde:
    def test_round_trip_bytes(self, tmp_path):
        wire = _batch(97, seed=3)
        t = rb._table_from_wire(*wire, None)
        tid = rb._resident_put(t)
        p = str(tmp_path / "t.npz")
        n = spill.save_table_npz(p, t)
        assert n > 0 and os.path.exists(p)
        t2 = spill.load_table_npz(p)
        tid2 = rb._resident_put(t2)
        assert _canon(rb.table_download_wire(tid2)) == _canon(
            rb.table_download_wire(tid)
        )
        rb.table_free(tid)
        rb.table_free(tid2)

    def test_load_payload_wraps_read_errors(self, tmp_path):
        p = str(tmp_path / "junk.npz")
        with open(p, "wb") as f:
            f.write(b"not an npz")
        with pytest.raises(durable.CheckpointCorrupt):
            durable.load_payload(p)


# ---------------------------------------------------------------------------
# disabled path: the default must stay effectively free
# ---------------------------------------------------------------------------
class TestDisabledPath:
    def test_disabled_gate_under_5us(self):
        config.clear_flag("DURABLE")
        durable.enabled()  # prime the generation cache
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            durable.enabled()
        per = (time.perf_counter() - t0) / n
        assert durable.enabled() is False
        assert per < 5e-6, f"disabled gate {per * 1e6:.2f}us >= 5us"

    def test_disabled_server_journals_nothing(self, tmp_path):
        config.clear_flag("DURABLE")
        ckpt = config.get_flag("CHECKPOINT_DIR")
        with serving.Server(workers=1) as srv:
            with serving.Client(srv.port, name="d") as c:
                assert c.resume_token is None
                t1 = c.upload(_batch(16), req="u1")
                c.free(t1, req="f1")
        assert not os.path.exists(ckpt) or not os.listdir(ckpt)


# ---------------------------------------------------------------------------
# server restore: crash, restart, byte parity, budgets, warm start
# ---------------------------------------------------------------------------
class TestRestore:
    def test_crash_restart_recovers_sessions_bytes_and_dedup(self):
        config.set_flag("METRICS", "on")
        wire_a, wire_b = _batch(200, seed=1), _batch(64, seed=2)
        srv = serving.Server(workers=2)
        srv.start()
        ca = serving.Client(srv.port, name="a").connect()
        cb = serving.Client(srv.port, name="b").connect()
        ta1 = ca.upload(wire_a, req="a-up-1")
        ta2 = ca.plan(CAST, [ta1], req="a-plan-1")
        tb1 = cb.upload(wire_b, req="b-up-1")
        want_a = _canon(ca.download(ta2))
        want_b = _canon(cb.download(tb1))
        sid_a, tok_a = ca.session, ca.resume_token
        sid_b, tok_b = cb.session, cb.resume_token
        assert tok_a and tok_b and tok_a != tok_b
        ca.kill()
        cb.kill()
        srv.stop()  # simulated crash: no bye, files stay

        srv2 = serving.Server(workers=2)
        srv2.start()
        try:
            doc = srv2.stats()["durability"]
            assert doc["restore"]["sessions"] == 2
            assert doc["restore"]["quarantined"] == {}
            assert doc["restore"]["warm_compiles"] >= 1
            assert doc["restore"]["warm_failures"] == 0

            ca2 = serving.Client(
                srv2.port, session=sid_a, resume=tok_a).connect()
            cb2 = serving.Client(
                srv2.port, session=sid_b, resume=tok_b).connect()
            assert _canon(ca2.download(ta2)) == want_a
            assert _canon(cb2.download(tb1)) == want_b
            # the idempotency window survived the restart: a replayed
            # request id returns the original response, applies nothing
            assert ca2.upload(wire_a, req="a-up-1") == ta1
            assert ca2.plan(CAST, [ta1], req="a-plan-1") == ta2
            # replayed plans land on the warmed compile cache
            snap = metrics.snapshot()["counters"]
            miss0 = snap.get("compile_cache.miss", 0)
            t_new = ca2.plan(CAST, [ta1], req="a-plan-2")
            ca2.download(t_new)
            snap = metrics.snapshot()["counters"]
            assert snap.get("compile_cache.miss", 0) == miss0
            # budgets were re-charged, not zeroed: the restored bytes
            # count against the session
            stats = srv2.stats()
            sess_a = next(s for s in stats["sessions"]
                          if s["session"] == sid_a)
            assert sess_a["resident_bytes"] > 0
            ca2.close()
            cb2.close()
        finally:
            srv2.stop()
        # clean byes erased both sessions' durable state
        ckpt = config.get_flag("CHECKPOINT_DIR")
        left = [f for f in os.listdir(ckpt) if f != "manifest.wal"]
        assert left == []

    def test_free_is_journaled(self):
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="f").connect()
        t1 = c.upload(_batch(32), req="u1")
        t2 = c.upload(_batch(48), req="u2")
        c.free(t1, req="f1")
        sid, tok = c.session, c.resume_token
        c.kill()
        srv.stop()
        srv2 = serving.Server(workers=1)
        srv2.start()
        try:
            c2 = serving.Client(
                srv2.port, session=sid, resume=tok).connect()
            with pytest.raises(serving.ServingTableError):
                c2.download(t1)
            assert _canon(c2.download(t2)) == _canon(_batch(48))
            c2.close()
        finally:
            srv2.stop()

    def test_resume_token_enforced(self):
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="r").connect()
        sid = c.session
        c.kill()
        try:
            with pytest.raises(serving.ServingResumeDenied):
                serving.Client(
                    srv.port, session=sid, resume="wrong").connect()
            with pytest.raises(serving.ServingResumeDenied):
                serving.Client(srv.port, session=sid).connect()
        finally:
            srv.stop()

    def test_donating_plan_drops_input_payload(self):
        """A donated plan input is consumed: its checkpoint payload is
        dropped with the journal record, and a restart restores only
        the output."""
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="d").connect()
        t1 = c.upload(_batch(128, seed=5), req="u1")
        t2 = c.plan(CAST, [t1], donate=True, req="p1")
        want = _canon(c.download(t2))
        sid, tok = c.session, c.resume_token
        c.kill()
        srv.stop()
        ckpt = config.get_flag("CHECKPOINT_DIR")
        names = os.listdir(ckpt)
        assert f"{sid}-t{t1}.npz" not in names
        assert f"{sid}-t{t2}.npz" in names
        srv2 = serving.Server(workers=1)
        srv2.start()
        try:
            c2 = serving.Client(
                srv2.port, session=sid, resume=tok).connect()
            assert _canon(c2.download(t2)) == want
            with pytest.raises(serving.ServingTableError):
                c2.download(t1)
            c2.close()
        finally:
            srv2.stop()


# ---------------------------------------------------------------------------
# chaos: checkpoint faults degrade, never crash
# ---------------------------------------------------------------------------
class TestChaos:
    def test_journal_fault_degrades_not_fails_request(self):
        """A torn journal write during a live upload degrades
        durability (counted) but the request still succeeds — memory
        is authoritative."""
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="c").connect()
        config.set_flag("FAULTS", "seed=11,checkpoint:permanent:1:1")
        try:
            t1 = c.upload(_batch(16), req="u1")
        finally:
            config.set_flag("FAULTS", "")
        assert _canon(c.download(t1)) == _canon(_batch(16))
        stats = srv.stats()["durability"]
        assert stats.get("checkpoint.errors", 0) >= 1
        c.close()
        srv.stop()

    def test_restore_read_fault_quarantines_session_daemon_survives(self):
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="q").connect()
        c.upload(_batch(32), req="u1")
        sid, tok = c.session, c.resume_token
        c.kill()
        srv.stop()
        # every restore-time payload read faults: the session is
        # quarantined; the daemon starts and serves new sessions
        config.set_flag("FAULTS", "seed=2,checkpoint:permanent:1:99")
        try:
            srv2 = serving.Server(workers=1)
            srv2.start()
        finally:
            config.set_flag("FAULTS", "")
        try:
            doc = srv2.stats()["durability"]
            assert sid in doc["restore"]["quarantined"]
            assert doc["quarantined_sessions"] == 1
            with pytest.raises(serving.ServingQuarantined):
                serving.Client(
                    srv2.port, session=sid, resume=tok).connect()
            # the daemon is healthy for fresh tenants
            with serving.Client(srv2.port, name="fresh") as c2:
                t = c2.upload(_batch(8), req="u1")
                assert _canon(c2.download(t)) == _canon(_batch(8))
            ckpt = config.get_flag("CHECKPOINT_DIR")
            assert os.path.exists(
                os.path.join(ckpt, f"{sid}.wal.quarantined"))
        finally:
            srv2.stop()


# ---------------------------------------------------------------------------
# reconnect + idempotent replay across a dropped socket
# ---------------------------------------------------------------------------
class TestReconnect:
    def test_replay_after_socket_loss_applies_once(self):
        """The crash-mid-reply window: the client sends a mutating
        command, the socket dies before the reply lands, the client
        reconnects and resends the SAME request id. Exactly one
        application; byte-identical result."""
        srv = serving.Server(workers=1)
        srv.start()
        config.set_flag("METRICS", "on")
        try:
            c = serving.Client(srv.port, name="rc").connect()
            wire = _batch(77, seed=9)
            # send the upload frame, then kill the socket without
            # reading the reply — the server applies it; the client
            # cannot know
            meta, buffers = frames.batch_to_parts(wire)
            frames.send_frame(
                c._sock, {"cmd": "upload", "batch": meta, "req": "u-77"},
                buffers)
            # applied = the table is registered AND the request id is
            # recorded: the handler does both before it replies, one
            # after the other, and a kill between the two is another
            # window (a daemon that died mid-command)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if any(s.dedup_get("u-77")
                       for s in list(srv._sessions.values())):
                    break
                time.sleep(0.01)
            c.kill()
            c2 = c.reconnect()
            t1 = c2.upload(wire, req="u-77")  # replayed, not re-applied
            assert [s["tables"] for s in srv.stats()["sessions"]] == [1]
            snap = metrics.snapshot()["counters"]
            assert snap.get("serving.idempotent_replays", 0) >= 1
            assert _canon(c2.download(t1)) == _canon(wire)
            # plan + free replay the same way
            t2 = c2.plan(CAST, [t1], req="p-77")
            assert c2.plan(CAST, [t1], req="p-77") == t2
            n = c2.free(t2, req="f-77")
            assert c2.free(t2, req="f-77") == n
            c2.close()
        finally:
            srv.stop()

    def test_dedup_window_is_bounded(self):
        from spark_rapids_jni_tpu.serving import session as session_mod
        s = session_mod.Session("x", "x", 1.0, 1 << 20)
        for i in range(durable.DEDUP_CAP + 10):
            s.dedup_put(f"r{i}", {"table": i}, cap=durable.DEDUP_CAP)
        assert s.dedup_get("r0") is None
        assert s.dedup_get(f"r{durable.DEDUP_CAP + 9}") is not None
        s.teardown()


# ---------------------------------------------------------------------------
# drain: the rolling-restart handshake
# ---------------------------------------------------------------------------
class TestDrain:
    def test_drain_rejects_new_work_then_stops(self):
        srv = serving.Server(workers=1)
        srv.start()
        c = serving.Client(srv.port, name="dr").connect()
        t1 = c.upload(_batch(24), req="u1")
        res = c.drain(deadline_s=10.0)
        assert res.get("drained") is True
        # draining (or already-stopped) daemon refuses device work
        with pytest.raises((serving.ServingDraining, OSError,
                            RuntimeError)):
            c.upload(_batch(8), req="u2")
            serving.Client(srv.port, name="late").connect()
        srv.stop()  # waits for the drain-triggered stop to finish
        # the checkpoint survived: a successor restores the session
        srv2 = serving.Server(workers=1)
        srv2.start()
        try:
            assert srv2.stats()["durability"]["restore"]["sessions"] == 1
        finally:
            srv2.stop()


# ---------------------------------------------------------------------------
# warm-start manifest
# ---------------------------------------------------------------------------
class TestManifest:
    def test_note_dedupes_and_survives_reload(self, tmp_path):
        d = str(tmp_path / "man")
        os.makedirs(d)
        t = rb._table_from_wire(*_batch(50), None)
        tid = rb._resident_put(t)
        m = durable.Manifest(d)
        for _ in range(3):
            m.note(CAST, [t], False)
        assert len(m.records()) == 1
        m.close()
        m2 = durable.Manifest(d)
        assert len(m2.records()) == 1
        compiled, failed = m2.warm_start()
        assert compiled == 1 and failed == 0
        m2.close()
        rb.table_free(tid)

    def test_corrupt_manifest_starts_fresh(self, tmp_path):
        d = str(tmp_path / "man2")
        os.makedirs(d)
        j = durable.Journal(os.path.join(d, "manifest.wal"))
        j.append({"t": "plan", "ops": [], "donate": False, "tables": []})
        j.append({"t": "plan", "ops": [1], "donate": False, "tables": []})
        j.close()
        blob = bytearray(
            open(os.path.join(d, "manifest.wal"), "rb").read())
        blob[len(durable._MAGIC) + durable._FRAME.size] ^= 0xFF
        with open(os.path.join(d, "manifest.wal"), "wb") as f:
            f.write(blob)
        m = durable.Manifest(d)  # must not raise
        assert m.records() == []
        m.close()
        assert os.path.exists(
            os.path.join(d, "manifest.wal.quarantined"))
