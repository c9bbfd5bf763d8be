"""Pipelined dispatch plane: parity, concurrency, donation, errors.

The ISSUE-5 contract under test: with ``SPARK_RAPIDS_TPU_PIPELINE`` on,
resident dispatch enqueues and the blocking points
(``table_download_wire`` / ``table_num_rows``) return results
BYTE-IDENTICAL to the synchronous path at bucket-edge row counts
(1023/1024/1025) — from single callers, from multi-threaded producers
at depths {1, 2, 8}, and through the one-call ``table_stream_wire``
driver. Worker failures replay synchronously and surface the
originating op's own error; ``=off`` is byte-identical to today's sync
path; donation consumes the input id, reports ``hbm.donated_bytes``
and changes nothing downloaded; unknown/double-freed table ids raise
the labeled KeyError naming the id and live count.
"""

import json
import threading

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import pipeline, planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, metrics

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)
STR = int(dt.TypeId.STRING)

BOUNDARY_SIZES = (1023, 1024, 1025)

CHAIN = [
    {"op": "filter", "mask": 2},
    {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
    {"op": "sort_by", "keys": [{"column": 0}]},
]


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    pipeline.drain()
    config.clear_flag("PIPELINE")
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")
    pipeline.depth()  # flag now off: tears the worker pool down


def _string_wire(strings):
    payload = b"".join(s.encode() for s in strings)
    offs = np.zeros(len(strings) + 1, np.int32)
    np.cumsum([len(s.encode()) for s in strings], out=offs[1:])
    return offs.tobytes() + payload


def _batch(n: int):
    """One wire batch: int64 key, int64 value (with nulls), BOOL8 mask,
    ragged STRING payload."""
    rng = np.random.default_rng(n)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % 5 != 0).astype(np.uint8)
    m = (v > 0).astype(np.uint8)
    strs = [("s" * (int(x) % 3 + 1)) for x in k]
    return (
        [I64, I64, B8, STR], [0, 0, 0, 0],
        [k.tobytes(), v.tobytes(), m.tobytes(), _string_wire(strs)],
        [None, valid.tobytes(), None, None], n,
    )


def _sync_want(n):
    config.set_flag("PIPELINE", "off")
    b = _batch(n)
    want = rb.table_plan_wire(json.dumps(CHAIN), *b)
    config.clear_flag("PIPELINE")
    return b, want


def _resident_chain(b, donate=False):
    cur = rb.table_upload_wire(*b)
    for op in CHAIN:
        nxt = rb.table_op_resident(json.dumps(op), [cur], donate=donate)
        if not donate:
            rb.table_free(cur)
        cur = nxt
    out = rb.table_download_wire(cur)
    rb.table_free(cur)
    return out


class TestDepthSpec:
    def test_off_values(self):
        for v in ("", "off", "none", "0", "false"):
            config.set_flag("PIPELINE", v)
            assert not pipeline.enabled(), v

    def test_depths(self):
        config.set_flag("PIPELINE", "3")
        assert pipeline.depth() == 3
        config.set_flag("PIPELINE", "on")
        assert pipeline.depth() == pipeline.DEFAULT_DEPTH

    def test_invalid_spec_fails_loudly(self):
        config.set_flag("PIPELINE", "fast")
        with pytest.raises(ValueError, match="PIPELINE"):
            pipeline.depth()
        config.set_flag("PIPELINE", "-2")
        with pytest.raises(ValueError, match="0..64"):
            pipeline.depth()
        config.set_flag("PIPELINE", str(pipeline.MAX_DEPTH + 1))
        with pytest.raises(ValueError, match="0..64"):
            pipeline.depth()  # silently clamping would mislabel runs

    def test_pool_tears_down_when_flag_goes_off(self):
        import sys as _sys
        import time as _time

        before = _sys.getswitchinterval()
        b, want = _sync_want(1023)
        config.set_flag("PIPELINE", "2")
        assert rb.table_stream_wire(json.dumps(CHAIN), [b]) == [want]
        assert any(
            t.name.startswith("srt-pipeline") for t in threading.enumerate()
        )
        pipeline.drain()
        config.set_flag("PIPELINE", "off")
        pipeline.depth()  # observes the flag change -> shutdown
        assert _sys.getswitchinterval() == before  # interval restored
        deadline = _time.time() + 10
        while _time.time() < deadline and any(
            t.name.startswith("srt-pipeline") for t in threading.enumerate()
        ):
            _time.sleep(0.02)
        assert not any(
            t.name.startswith("srt-pipeline") for t in threading.enumerate()
        ), "worker threads survived PIPELINE=off"


class TestParity:
    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_async_resident_chain_matches_sync(self, n):
        b, want = _sync_want(n)
        config.set_flag("PIPELINE", "off")
        sync_out = _resident_chain(b)
        assert sync_out == want
        config.set_flag("PIPELINE", "2")
        assert _resident_chain(b) == want

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_stream_matches_sync_and_off(self, n):
        b, want = _sync_want(n)
        pj = json.dumps(CHAIN)
        config.set_flag("PIPELINE", "off")
        off = rb.table_stream_wire(pj, [b, b])
        assert off == [want, want]  # =off IS today's sync path
        config.set_flag("PIPELINE", "2")
        on = rb.table_stream_wire(pj, [b] * 5)
        assert on == [want] * 5  # ordered completion, byte parity

    def test_blocking_points_resolve_pending(self):
        b, want = _sync_want(1024)
        config.set_flag("PIPELINE", "1")
        tid = rb.table_upload_wire(*b)
        out = rb.table_plan_resident(json.dumps(CHAIN), [tid])
        assert rb.table_num_rows(out) == want[4]
        assert rb.table_download_wire(out) == want
        rb.table_free(tid)
        rb.table_free(out)


class TestConcurrentProducers:
    @pytest.mark.parametrize("depth", (1, 2, 8))
    def test_threaded_chains_byte_parity(self, depth):
        # one sync oracle per boundary size, then N producer threads
        # each driving its own chain through the shared pipeline
        oracle = {n: _sync_want(n) for n in BOUNDARY_SIZES}
        config.set_flag("PIPELINE", str(depth))
        live_before = rb.resident_table_count()
        errors = []

        def producer(tid_):
            try:
                for rep in range(2):
                    n = BOUNDARY_SIZES[(tid_ + rep) % len(BOUNDARY_SIZES)]
                    b, want = oracle[n]
                    got = _resident_chain(b)
                    if got != want:
                        errors.append((tid_, n, "parity mismatch"))
            except Exception as e:  # pragma: no cover - failure detail
                errors.append((tid_, repr(e)))

        threads = [
            threading.Thread(target=producer, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "producer hung"
        assert errors == []
        pipeline.drain()
        assert rb.resident_table_count() == live_before  # no leaks


class TestWorkerFailureReplay:
    def test_transient_worker_failure_replays_sync(self, monkeypatch):
        # fail ONLY on pipeline worker threads: the sync replay on the
        # resolving thread then succeeds — pipelining healed a flake
        # without changing results
        b, want = _sync_want(1024)
        real = planops.dispatch

        def flaky(op, table, rest=()):
            if threading.current_thread().name.startswith("srt-pipeline"):
                raise RuntimeError("injected worker failure")
            return real(op, table, rest)

        monkeypatch.setattr(planops, "dispatch", flaky)
        config.set_flag("METRICS", True)
        config.set_flag("PIPELINE", "2")
        metrics.reset()
        got = _resident_chain(b)
        assert got == want
        c = metrics.snapshot()["counters"]
        assert c.get("pipeline.replays", 0) >= 1

    def test_genuine_op_error_surfaces_at_blocking_point(self):
        # a broken op enqueues fine; the blocking point replays it
        # synchronously and raises the op's OWN error (same type and
        # message as the sync path)
        b, _ = _sync_want(1024)
        config.set_flag("PIPELINE", "2")
        tid = rb.table_upload_wire(*b)
        out = rb.table_op_resident(json.dumps({"op": "explode_wrong"}),
                                   [tid])
        with pytest.raises(ValueError, match="unknown table op"):
            rb.table_download_wire(out)
        # the terminal error is sticky: a second blocking point raises
        # it again instead of replaying twice
        with pytest.raises(ValueError, match="unknown table op"):
            rb.table_num_rows(out)
        rb.table_free(tid)
        rb.table_free(out)  # freeing the failed handle must not raise

    def test_unknown_input_id_raises_synchronously(self):
        config.set_flag("PIPELINE", "2")
        with pytest.raises(KeyError, match="999999"):
            rb.table_op_resident(json.dumps(CHAIN[0]), [999999])


class TestDonation:
    def test_donated_plan_chain_same_bytes_nonzero_donation(self):
        b, want = _sync_want(1025)
        config.set_flag("METRICS", True)
        metrics.reset()
        # table_plan_wire consumes its upload by construction: the
        # fused chain donates, the downloaded bytes must not change
        got = rb.table_plan_wire(json.dumps(CHAIN), *b)
        assert got == want
        snap = metrics.snapshot()
        assert snap["bytes"].get("hbm.donated_bytes", 0) > 0
        assert snap["counters"].get("hbm.donations", 0) >= 1

    def test_donate_consumes_resident_input_id(self):
        b, want = _sync_want(1024)
        config.set_flag("PIPELINE", "off")
        tid = rb.table_upload_wire(*b)
        out = rb.table_op_resident(
            json.dumps(CHAIN[0]), [tid], donate=True
        )
        # the input id was consumed at op time — the labeled KeyError
        # names the id and the live count
        with pytest.raises(KeyError, match=rf"{tid}.*\d+ table\(s\) live"):
            rb.table_download_wire(tid)
        got = rb.table_download_wire(out)
        rb.table_free(out)
        config.set_flag("PIPELINE", "2")
        tid2 = rb.table_upload_wire(*b)
        out2 = rb.table_op_resident(
            json.dumps(CHAIN[0]), [tid2], donate=True
        )
        assert rb.table_download_wire(out2) == got
        rb.table_free(out2)


class TestDonationSafety:
    def test_aliasing_boundary_segment_never_donates_caller_buffers(self):
        # a single-table concat is an identity-aliasing exact boundary
        # (jnp.concatenate([x]) returns x's buffer): the fused segment
        # after it must NOT donate buffers the caller still owns —
        # 1024 rows == the bucket, so no pad copy breaks the alias
        from spark_rapids_jni_tpu import plan as plan_mod
        from spark_rapids_jni_tpu.column import Column, Table

        n = 1024
        rng = np.random.default_rng(3)
        k = rng.integers(0, 9, n, dtype=np.int64)
        v = rng.integers(-50, 50, n, dtype=np.int64)
        t = Table(
            [Column.from_numpy(k), Column.from_numpy(v)], ["k", "v"]
        )
        plan = [
            {"op": "concat"},
            {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
            {"op": "cast", "column": 0, "type_id": int(dt.TypeId.INT32)},
        ]
        out = plan_mod.run_plan(plan, t, donate_input=False)
        assert int(out.logical_row_count) == n
        # the caller's buffers must still be alive and byte-identical
        assert not t.columns[0].data.is_deleted()
        assert np.asarray(t.columns[0].data).tobytes() == k.tobytes()
        assert np.asarray(t.columns[1].data).tobytes() == v.tobytes()

    def test_bad_rest_id_leaves_donated_input_intact(self):
        # the labeled KeyError for a bad rest id must fire BEFORE the
        # donated input is consumed: the call fails, the input survives
        b, _ = _sync_want(1024)
        tid = rb.table_upload_wire(*b)
        with pytest.raises(KeyError, match="31337"):
            rb.table_op_resident(
                json.dumps({"op": "join", "on": [0]}), [tid, 31337],
                donate=True,
            )
        assert rb.table_num_rows(tid) == 1024  # still alive
        rb.table_free(tid)

    def test_donate_waits_for_inflight_readers_of_same_id(self, monkeypatch):
        # op1 reads A (slowed down on the worker); op2 donate-consumes
        # A right after: the donate barrier must keep A's buffers alive
        # until op1's dispatch is done — without it, op2's executable
        # deletes them mid-read and op1 dies with a deleted-array error
        # the synchronous ordering can never produce
        import time as _time

        sort_op = {"op": "sort_by", "keys": [{"column": 0}]}
        b, _ = _sync_want(1024)  # 1024 == the bucket: no pad copy
        config.set_flag("PIPELINE", "off")
        a0 = rb.table_upload_wire(*b)
        w1 = rb.table_op_resident(json.dumps(sort_op), [a0])
        want1 = rb.table_download_wire(w1)
        w2 = rb.table_op_resident(json.dumps(CHAIN[0]), [a0], donate=True)
        want2 = rb.table_download_wire(w2)
        for t in (w1, w2):
            rb.table_free(t)

        real = planops.dispatch

        def slow(op, table, rest=()):
            if (
                threading.current_thread().name.startswith("srt-pipeline")
                and op.get("op") == "sort_by"
            ):
                _time.sleep(0.3)
            return real(op, table, rest)

        monkeypatch.setattr(planops, "dispatch", slow)
        config.set_flag("PIPELINE", "2")
        A = rb.table_upload_wire(*b)
        r1 = rb.table_op_resident(json.dumps(sort_op), [A])
        r2 = rb.table_op_resident(json.dumps(CHAIN[0]), [A], donate=True)
        assert rb.table_download_wire(r1) == want1  # reader unharmed
        assert rb.table_download_wire(r2) == want2
        for t in (r1, r2):
            rb.table_free(t)

    def test_donated_async_failure_surfaces_op_error(self, monkeypatch):
        # non-replayable donated work: the worker's own (genuine) op
        # error is what the blocking point raises — no deleted-buffer
        # error from a doomed replay. The fault must be injected
        # mid-flight: a statically-bad plan never reaches the worker —
        # plancheck rejects it at submit and the donated input survives
        from spark_rapids_jni_tpu import plan as plan_mod

        b, _ = _sync_want(1024)
        config.set_flag("PIPELINE", "2")
        tid = rb.table_upload_wire(*b)
        plan = [
            {"op": "filter", "mask": 2},
            {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
        ]
        with pytest.raises(ValueError, match="plancheck: op\\[2\\]"):
            rb.table_plan_resident(
                json.dumps(plan + [{"op": "nope_not_an_op"}]), [tid],
                donate=True,
            )
        assert rb.table_num_rows(tid) == 1024  # static reject kept it

        real = plan_mod.run_plan

        def boom(ops, table, rest=(), **kw):
            if threading.current_thread().name.startswith("srt-pipeline"):
                raise ValueError("unknown table op (injected mid-flight)")
            return real(ops, table, rest, **kw)

        monkeypatch.setattr(plan_mod, "run_plan", boom)
        out = rb.table_plan_resident(json.dumps(plan), [tid], donate=True)
        with pytest.raises(ValueError, match="unknown table op"):
            rb.table_download_wire(out)
        rb.table_free(out)


class TestLabeledKeyErrors:
    def test_unknown_and_double_free(self):
        b, _ = _sync_want(1023)
        tid = rb.table_upload_wire(*b)
        live = rb.resident_table_count()
        with pytest.raises(
            KeyError, match=rf"424242.*{live} table\(s\) live"
        ):
            rb.table_download_wire(424242)
        rb.table_free(tid)
        with pytest.raises(KeyError, match=str(tid)):
            rb.table_free(tid)  # double free names the freed id
        with pytest.raises(KeyError, match="unknown or already-freed"):
            rb.table_num_rows(tid)


class TestStageSpansOnWorkerTids:
    def test_worker_stages_record_on_worker_threads(self):
        # the Chrome-trace overlap story: decode/encode stage spans
        # must land on pipeline worker tids, not the caller's
        from spark_rapids_jni_tpu.utils import flight

        b, want = _sync_want(1024)
        config.set_flag("METRICS", True)
        config.set_flag("FLIGHT", "on")
        config.set_flag("PIPELINE", "2")
        got = rb.table_stream_wire(json.dumps(CHAIN), [b] * 4)
        assert got == [want] * 4
        pipeline.drain()
        evs = flight.tail_records()
        stage_tids = {
            e["tid"] for e in evs
            if e["ph"] == "B"
            and e["name"].split("/")[-1] in ("pipeline.decode",
                                             "pipeline.encode")
        }
        assert stage_tids, "no stage spans recorded"
        assert threading.get_ident() not in stage_tids
        config.clear_flag("FLIGHT")
