"""Fault-tolerant execution plane: taxonomy, injection, retry, cancel.

The ISSUE-10 contract under test: a deterministic, seeded fault plan
(``SPARK_RAPIDS_TPU_FAULTS``) can provoke every failure kind at every
registered injection site on CPU, and the execution plane recovers
with results BYTE-IDENTICAL to a faults-off run at bucket-boundary row
counts (1023/1024/1025) — transient faults retry with backoff, OOM
faults degrade to half-batch chunks (row-local segments) or the exact
path, permanent faults surface typed. Retry is at-most-once for
donated work (a consumed input is never replayed), cancellation and
deadlines abort between segments with a clean ``leak_report()``, the
serving circuit breaker walks open -> half-open -> closed, and the
whole plane costs one int compare per checkpoint when off (< 5 µs/op,
the metrics-gate overhead class).
"""

import json
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import buckets, config, faults, metrics

I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)

BOUNDARY_SIZES = (1023, 1024, 1025)

# all ops row-local: OOM degradation may chunk this chain
ROW_LOCAL_CHAIN = [
    {"op": "filter", "mask": 1},
    {"op": "cast", "column": 0, "type_id": F64},
]

# ends in a global op: OOM degradation must NOT chunk this chain
GLOBAL_CHAIN = [
    {"op": "cast", "column": 0, "type_id": F64},
    {"op": "sort_by", "keys": [{"column": 0}]},
]

FAULT_FLAGS = (
    "FAULTS", "RETRY_MAX", "RETRY_BASE_MS", "DEADLINE_DEFAULT_S",
    "BREAKER_THRESHOLD", "BREAKER_PROBE_S",
)


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    for name in FAULT_FLAGS + ("BUCKETS", "METRICS", "PIPELINE"):
        config.clear_flag(name)


def _cols(n: int, seed: int = 0):
    rng = np.random.default_rng(n + seed)
    k = rng.integers(-50, 50, n, dtype=np.int64)
    mask = (k > 0).astype(np.uint8)
    return ([I64, B8], [0, 0], [k.tobytes(), mask.tobytes()],
            [None, None])


def _run(chain, n, seed=0):
    return rb.table_plan_wire(json.dumps(chain), *_cols(n, seed), n)


def _norm(wire):
    t, s, d, v, n = wire
    return (
        [int(x) for x in t], [int(x) for x in s],
        [None if x is None else bytes(x) for x in d],
        [None if x is None else bytes(x) for x in v], int(n),
    )


# ---------------------------------------------------------------------------
# spec parsing: loud-fail naming the env var
# ---------------------------------------------------------------------------


class TestSpecParsing:
    @pytest.mark.parametrize("bad,needle", [
        ("bogus:transient:1", "unknown site"),
        ("dispatch:meteor:1", "unknown kind"),
        ("dispatch:transient:nope", "bad probability"),
        ("dispatch:transient:1.5", "must be in [0, 1]"),
        ("dispatch:transient:1:x", "bad count"),
        ("dispatch:transient:1:-2", "count must be >= 0"),
        ("seed=pi,dispatch:transient:1", "bad seed"),
        ("dispatch:transient", "site:kind:prob"),
    ])
    def test_bad_spec_names_env_var(self, bad, needle):
        with pytest.raises(ValueError) as ei:
            faults.parse_spec(bad)
        assert "SPARK_RAPIDS_TPU_FAULTS" in str(ei.value)
        assert needle in str(ei.value)

    def test_bad_env_value_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_FAULTS", "junk")
        with pytest.raises(ValueError) as ei:
            config.get_flag("FAULTS")
        assert "SPARK_RAPIDS_TPU_FAULTS" in str(ei.value)

    @pytest.mark.parametrize("name,bad", [
        ("RETRY_MAX", "-1"),
        ("RETRY_BASE_MS", "0"),
        ("DEADLINE_DEFAULT_S", "-3"),
        ("BREAKER_THRESHOLD", "0"),
        ("BREAKER_PROBE_S", "-1"),
    ])
    def test_knob_env_fails_loudly(self, monkeypatch, name, bad):
        monkeypatch.setenv(f"SPARK_RAPIDS_TPU_{name}", bad)
        with pytest.raises(ValueError) as ei:
            config.get_flag(name)
        assert name in str(ei.value)  # loud-fail names the knob

    def test_good_spec_round_trips(self):
        p = faults.parse_spec(
            "seed=9,dispatch:transient:0.5:3,serde:oom:1"
        )
        assert p.seed == 9
        assert set(p.stats()) == {"dispatch:transient", "serde:oom"}


# ---------------------------------------------------------------------------
# classifier
# ---------------------------------------------------------------------------


class TestClassifier:
    @pytest.mark.parametrize("type_name,msg,want", [
        ("XlaRuntimeError", "UNAVAILABLE: socket closed",
         faults.TransientDeviceError),
        ("RuntimeError", "failed to connect to coordination service",
         faults.TransientDeviceError),
        ("DeviceUnreachable", "anything", faults.TransientDeviceError),
        ("TimeoutExpired", "probe", faults.TransientDeviceError),
        ("XlaRuntimeError", "RESOURCE_EXHAUSTED: out of memory "
         "allocating 1GB", faults.ResourceExhausted),
        ("MemoryError", "failed to allocate", faults.ResourceExhausted),
        ("RuntimeError", "operation was cancelled", faults.Cancelled),
        ("ValueError", "unknown op 'zorp'", faults.PermanentError),
        ("KeyError", "table id 7", faults.PermanentError),
    ])
    def test_classify_text(self, type_name, msg, want):
        assert faults.classify_text(type_name, msg) is want

    def test_typed_errors_classify_as_themselves(self):
        for cls in (faults.TransientDeviceError, faults.PermanentError,
                    faults.ResourceExhausted, faults.Cancelled,
                    faults.DeadlineExceeded, faults.Degraded):
            assert faults.classify(cls("x")) is cls

    def test_retryable_classes(self):
        assert faults.retryable_class(faults.TransientDeviceError)
        assert faults.retryable_class(faults.ResourceExhausted)
        assert not faults.retryable_class(faults.PermanentError)
        assert not faults.retryable_class(faults.Cancelled)
        assert not faults.retryable_class(faults.DeadlineExceeded)
        assert not faults.retryable_class(faults.Degraded)


# ---------------------------------------------------------------------------
# deterministic injection
# ---------------------------------------------------------------------------


def _decisions(spec, site, calls):
    plan = faults.parse_spec(spec)
    out = []
    for _ in range(calls):
        try:
            plan.fire(site)
            out.append(False)
        except faults.FaultError:
            out.append(True)
    return out


class TestInjectionDeterminism:
    def test_same_seed_same_decisions(self):
        spec = "seed=11,dispatch:transient:0.5"
        a = _decisions(spec, "dispatch", 64)
        b = _decisions(spec, "dispatch", 64)
        assert a == b
        assert any(a) and not all(a)  # prob 0.5 actually mixes

    def test_different_seed_different_decisions(self):
        a = _decisions("seed=1,dispatch:transient:0.5", "dispatch", 64)
        b = _decisions("seed=2,dispatch:transient:0.5", "dispatch", 64)
        assert a != b

    def test_count_limits_injections(self):
        hits = _decisions("dispatch:oom:1:2", "dispatch", 10)
        assert sum(hits) == 2
        assert hits[:2] == [True, True]  # prob 1: the first two calls

    def test_unregistered_site_is_silent(self):
        plan = faults.parse_spec("dispatch:oom:1")
        plan.fire("serde")  # no rule armed there: no-op

    def test_kinds_raise_their_taxonomy_class(self):
        for kind, cls in (
            ("transient", faults.TransientDeviceError),
            ("oom", faults.ResourceExhausted),
            ("permanent", faults.PermanentError),
        ):
            plan = faults.parse_spec(f"serde:{kind}:1:1")
            with pytest.raises(cls):
                plan.fire("serde")


# ---------------------------------------------------------------------------
# retry with backoff
# ---------------------------------------------------------------------------


class TestRetry:
    def test_transient_recovers_within_budget(self):
        config.set_flag("METRICS", "1")
        config.set_flag("RETRY_BASE_MS", "1")
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("UNAVAILABLE: connection reset")
            return "ok"

        assert faults.run_with_retry(flaky, "t") == "ok"
        assert calls["n"] == 3
        c = metrics.snapshot()["counters"]
        assert c.get("retry.attempts", 0) >= 2

    def test_permanent_raw_error_surfaces_unchanged(self):
        err = ValueError("unknown op 'zorp'")

        def bad():
            raise err

        with pytest.raises(ValueError) as ei:
            faults.run_with_retry(bad, "t")
        assert ei.value is err  # exact object: type AND message pinned

    def test_exhaustion_raises_typed_chained(self):
        config.set_flag("METRICS", "1")
        config.set_flag("RETRY_MAX", "2")
        config.set_flag("RETRY_BASE_MS", "0.1")

        def always():
            raise RuntimeError("UNAVAILABLE: socket closed")

        with pytest.raises(faults.TransientDeviceError) as ei:
            faults.run_with_retry(always, "t")
        assert "retries exhausted" in str(ei.value)
        assert isinstance(ei.value.__cause__, RuntimeError)
        c = metrics.snapshot()["counters"]
        assert c.get("retry.giveups", 0) >= 1

    def test_backoff_is_deterministic_and_grows(self):
        a = faults.backoff_ms(1, "site")
        assert a == faults.backoff_ms(1, "site")
        # jitter is [0.5x, 1.0x): attempt 3's floor (2x base) beats
        # attempt 1's ceiling (1x base)
        assert faults.backoff_ms(3, "site") > a


# ---------------------------------------------------------------------------
# chaos matrix: every site x recoverable kind, byte parity afterwards
# ---------------------------------------------------------------------------


MATRIX_SITES = ("dispatch", "compile", "serde")


class TestChaosMatrix:
    @pytest.mark.parametrize("kind", ("transient", "oom"))
    @pytest.mark.parametrize("site", MATRIX_SITES)
    def test_recoverable_kind_byte_parity(self, site, kind):
        config.set_flag("BUCKETS", "")
        config.set_flag("RETRY_BASE_MS", "1")
        n = 1024
        # fault-armed run FIRST, against a cold executable cache, so
        # the compile site genuinely fires (it only arms on a miss)
        buckets.cache_clear()
        config.set_flag("FAULTS", f"seed=5,{site}:{kind}:1:1")
        got = _norm(_run(ROW_LOCAL_CHAIN, n))
        stats = faults.injection_stats()
        assert stats[f"{site}:{kind}"]["injected"] == 1
        config.set_flag("FAULTS", "")
        want = _norm(_run(ROW_LOCAL_CHAIN, n))
        assert got == want

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_transient_parity_at_bucket_boundaries(self, n):
        config.set_flag("BUCKETS", "")
        config.set_flag("RETRY_BASE_MS", "1")
        config.set_flag("FAULTS", "seed=7,dispatch:transient:1:2")
        got = _norm(_run(ROW_LOCAL_CHAIN, n))
        config.set_flag("FAULTS", "")
        assert got == _norm(_run(ROW_LOCAL_CHAIN, n))

    def test_oom_chunks_row_local_segment(self):
        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", "1")
        config.set_flag("RETRY_BASE_MS", "1")
        n = 1025
        config.set_flag("FAULTS", "seed=3,dispatch:oom:1:1")
        got = _norm(_run(ROW_LOCAL_CHAIN, n))
        c = metrics.snapshot()["counters"]
        assert c.get("plan.chunked_segments", 0) >= 1
        config.set_flag("FAULTS", "")
        assert got == _norm(_run(ROW_LOCAL_CHAIN, n))

    def test_oom_on_global_segment_never_chunks(self):
        # sort is not row-local: degradation must NOT split the batch
        # (a chunked sort would be locally-sorted garbage); recovery
        # belongs to retry/the exact path and parity still holds
        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", "1")
        config.set_flag("RETRY_BASE_MS", "1")
        n = 1024
        before = metrics.snapshot()["counters"].get(
            "plan.chunked_segments", 0
        )
        config.set_flag("FAULTS", "seed=3,dispatch:oom:1:1")
        got = _norm(_run(GLOBAL_CHAIN, n))
        c = metrics.snapshot()["counters"]
        assert c.get("plan.chunked_segments", 0) == before
        config.set_flag("FAULTS", "")
        assert got == _norm(_run(GLOBAL_CHAIN, n))

    def test_permanent_fault_surfaces_typed(self):
        config.set_flag("BUCKETS", "")
        config.set_flag("FAULTS", "dispatch:permanent:1")
        with pytest.raises(faults.PermanentError):
            _run(ROW_LOCAL_CHAIN, 256)

    def test_injection_is_metered(self):
        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", "1")
        config.set_flag("RETRY_BASE_MS", "1")
        config.set_flag("FAULTS", "seed=5,serde:transient:1:1")
        _run(ROW_LOCAL_CHAIN, 512)
        c = metrics.snapshot()["counters"]
        assert c.get("faults.injected", 0) >= 1
        assert c.get("faults.injected.serde.transient", 0) >= 1
        assert c.get("retry.attempts", 0) >= 1


# ---------------------------------------------------------------------------
# at-most-once for donated work
# ---------------------------------------------------------------------------


def test_consumed_segment_is_never_retried(monkeypatch):
    # CPU jax never actually deletes donated buffers, so the consumed
    # state is simulated: _input_consumed answers True, exactly what a
    # donated executable that launched before dying leaves behind
    config.set_flag("BUCKETS", "")
    config.set_flag("METRICS", "1")
    calls = {"n": 0}

    def launch_then_die(seg_ops, table, donate=False, builds=()):
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE: device lost after launch")

    monkeypatch.setattr(plan_mod, "_run_fused", launch_then_die)
    monkeypatch.setattr(plan_mod, "_input_consumed", lambda t: True)
    before = metrics.snapshot()["counters"].get("retry.attempts", 0)
    # at-most-once: the transient failure must surface as-is — one
    # attempt, no retry, no per-op replay against buffers the device
    # already owns
    with pytest.raises(RuntimeError) as ei:
        _run(ROW_LOCAL_CHAIN, 1024)
    assert "device lost after launch" in str(ei.value)
    assert calls["n"] == 1
    c = metrics.snapshot()["counters"]
    assert c.get("retry.attempts", 0) == before


# ---------------------------------------------------------------------------
# deadlines + cancellation
# ---------------------------------------------------------------------------


class TestCancellation:
    def test_cancelled_token_aborts_with_clean_leak_report(self):
        config.set_flag("BUCKETS", "")
        tok = faults.CancelToken()
        tok.cancel("test says stop")
        with faults.scoped_token(tok):
            with pytest.raises(faults.Cancelled) as ei:
                _run(ROW_LOCAL_CHAIN, 1024)
        assert "test says stop" in str(ei.value)
        assert rb.leak_report() == []

    def test_expired_deadline_aborts_with_clean_leak_report(self):
        config.set_flag("BUCKETS", "")
        tok = faults.CancelToken(deadline_s=1e-6)
        time.sleep(0.005)
        with faults.scoped_token(tok):
            with pytest.raises(faults.DeadlineExceeded):
                _run(ROW_LOCAL_CHAIN, 1024)
        assert rb.leak_report() == []

    def test_expired_token_never_sleeps_in_backoff(self):
        config.set_flag("RETRY_BASE_MS", "10000")
        tok = faults.CancelToken(deadline_s=1e-6)
        time.sleep(0.005)
        with faults.scoped_token(tok):
            t0 = time.perf_counter()
            with pytest.raises(faults.DeadlineExceeded):
                faults.sleep_backoff(1, "t")
            assert time.perf_counter() - t0 < 1.0

    def test_token_scope_restores_previous(self):
        outer = faults.CancelToken()
        with faults.scoped_token(outer):
            with faults.scoped_token(faults.CancelToken()):
                assert faults.current_token() is not outer
            assert faults.current_token() is outer
        assert faults.current_token() is None

    def test_no_token_is_noop(self):
        faults.check_cancel()  # must not raise
        assert faults.current_token() is None


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _mk(self, threshold=3, interval=10.0):
        clock = {"t": 0.0}
        b = faults.CircuitBreaker(
            threshold=threshold, probe_interval_s=interval,
            clock=lambda: clock["t"], name="test",
        )
        return b, clock

    def test_opens_after_threshold_consecutive_transients(self):
        b, _ = self._mk(threshold=3)
        err = faults.TransientDeviceError("x")
        assert not b.note_failure(err)
        assert not b.note_failure(err)
        assert b.note_failure(err)  # third one trips
        assert b.state == faults.OPEN
        with pytest.raises(faults.Degraded) as ei:
            b.allow()
        assert "next probe" in str(ei.value)

    def test_success_resets_the_count(self):
        b, _ = self._mk(threshold=2)
        err = faults.TransientDeviceError("x")
        b.note_failure(err)
        b.note_success()
        assert not b.note_failure(err)  # count restarted
        assert b.state == faults.CLOSED

    def test_non_transient_failures_neither_count_nor_reset(self):
        b, _ = self._mk(threshold=2)
        b.note_failure(faults.TransientDeviceError("x"))
        b.note_failure(ValueError("bad request"))
        b.note_failure(faults.ResourceExhausted("oom"))
        assert b.state == faults.CLOSED
        # the next transient is the SECOND consecutive one: trips
        assert b.note_failure(faults.TransientDeviceError("x"))

    def test_half_open_probe_then_close(self):
        b, clock = self._mk(threshold=1, interval=5.0)
        b.note_failure(faults.TransientDeviceError("x"))
        assert b.state == faults.OPEN
        clock["t"] = 6.0
        assert b.allow() is True  # this caller is the probe
        assert b.state == faults.HALF_OPEN
        with pytest.raises(faults.Degraded):
            b.allow()  # everyone else sheds during the trial
        b.note_success()
        assert b.state == faults.CLOSED
        assert b.allow() is False

    def test_half_open_failure_reopens_and_rearms(self):
        b, clock = self._mk(threshold=1, interval=5.0)
        b.note_failure(faults.TransientDeviceError("x"))
        clock["t"] = 6.0
        assert b.allow() is True
        assert b.note_failure(faults.TransientDeviceError("y"))
        assert b.state == faults.OPEN
        clock["t"] = 10.0  # re-armed at t=6: not yet probe time
        with pytest.raises(faults.Degraded):
            b.allow()
        clock["t"] = 11.5
        assert b.allow() is True

    def test_to_doc_shape(self):
        b, _ = self._mk()
        doc = b.to_doc()
        assert doc["state"] == faults.CLOSED
        assert doc["threshold"] == 3
        assert doc["opens"] == 0


# ---------------------------------------------------------------------------
# serving integration: typed wire errors, breaker, hbm_admit site
# ---------------------------------------------------------------------------


def _wait_until(cond, timeout=30.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


def _small_batch(n=256):
    return (*_cols(n, seed=1), n)


class TestServingFaults:
    def test_breaker_opens_sheds_typed_and_recovers(self):
        config.set_flag("BUCKETS", "")
        config.set_flag("BREAKER_THRESHOLD", "2")
        config.set_flag("BREAKER_PROBE_S", "0.5")
        b = _small_batch()
        want = _norm(rb.table_plan_wire(json.dumps(ROW_LOCAL_CHAIN), *b))
        config.set_flag("FAULTS", "serve_accept:transient:1")
        with serving.serve() as srv:
            with serving.Client(srv.port, name="chaos") as c:
                for _ in range(2):  # trip the breaker
                    with pytest.raises(serving.ServingTransientError):
                        c.stream(ROW_LOCAL_CHAIN, [b])
                with pytest.raises(serving.ServingDegraded) as ei:
                    c.stream(ROW_LOCAL_CHAIN, [b])
                assert "circuit breaker" in str(ei.value)
                assert srv.stats()["breaker"]["state"] == faults.OPEN
                # device "recovers": the background probe must close
                # the breaker with no client traffic at all
                config.set_flag("FAULTS", "")
                assert _wait_until(
                    lambda: srv.breaker.state == faults.CLOSED,
                    timeout=30,
                )
                got = c.stream(ROW_LOCAL_CHAIN, [b])
                assert _norm(got[0]) == want

    def test_hbm_admit_fault_is_typed_then_recovers(self):
        config.set_flag("BUCKETS", "")
        config.set_flag("FAULTS", "hbm_admit:oom:1:1")
        b = _small_batch()
        want = _norm(rb.table_plan_wire(json.dumps(ROW_LOCAL_CHAIN), *b))
        with serving.serve() as srv:
            with serving.Client(srv.port, name="oomy") as c:
                with pytest.raises(serving.ServingResourceExhausted):
                    c.stream(ROW_LOCAL_CHAIN, [b])
                got = c.stream(ROW_LOCAL_CHAIN, [b])  # client retry
                assert _norm(got[0]) == want
        assert rb.leak_report() == []

    def test_stream_deadline_exceeded_is_typed(self):
        config.set_flag("BUCKETS", "")
        b = _small_batch()
        want = _norm(rb.table_plan_wire(json.dumps(ROW_LOCAL_CHAIN), *b))
        with serving.serve() as srv:
            with serving.Client(srv.port, name="late") as c:
                with pytest.raises(serving.ServingDeadlineExceeded):
                    c.stream(ROW_LOCAL_CHAIN, [b], deadline_s=1e-9)
                # no deadline: same session still works
                got = c.stream(ROW_LOCAL_CHAIN, [b])
                assert _norm(got[0]) == want
        assert rb.leak_report() == []


# ---------------------------------------------------------------------------
# disabled-path overhead: the metrics-gate class
# ---------------------------------------------------------------------------


class TestDisabledOverhead:
    def test_inject_disabled_cost_within_budget(self):
        assert not faults.active()
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            faults.inject("dispatch")
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6, f"disabled inject costs {per * 1e6:.2f}us"

    def test_check_cancel_disabled_cost_within_budget(self):
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            faults.check_cancel()
        per = (time.perf_counter() - t0) / n
        assert per < 5e-6, f"disabled check_cancel {per * 1e6:.2f}us"
