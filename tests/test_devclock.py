"""The completion clock (utils/devclock.py): when the device finished
what a span launched.

Every callable ``buckets.cached_jit`` hands out reports each launch to
one daemon thread, which waits for one device value of the result and
stamps its completion; the intervals and the gaps between them land in
the registry as ``device.*`` timers. The cases here drive it through the
plan runner on the CPU: what one launch leaves, that the sums telescope,
that a donated chain loses nothing, that nothing runs with ``METRICS``
off, and that no served program's lowered text depends on the clock.
The served requests' cases are in tests/test_request_anatomy.py.
"""

import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import (
    buckets, config, devclock, flight, metrics, tracing,
)

ROWS = 5_000
FLAGS = ("METRICS", "FLIGHT", "METRICS_DUMP", "PLANSTATS", "PLANSTATS_DIR")

# two fused segments and, between them, a plan-owned intermediate that
# the second one's executable is given to consume
CHAIN = [
    {"op": "filter", "mask": 2},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
    {"op": "sort_by", "keys": [{"column": 1, "ascending": False}]},
    {"op": "slice", "start": 0, "stop": 10},
]
# a build key that repeats: the join stays a boundary and materialises
JOIN_PLAN = [
    {"op": "filter", "mask": 2},
    {"op": "join", "on": [0]},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
]


def _fact(rows: int = ROWS, keys: int = 50) -> Table:
    i = jnp.arange(rows, dtype=jnp.int64)
    return Table([
        Column(i % keys, dt.INT64),
        Column(i % 7 + 1, dt.INT64),
        Column(i % 3 != 0, dt.BOOL8),
    ])


def _dim(keys: int = 50) -> Table:
    k = jnp.arange(2 * keys, dtype=jnp.int64) % keys  # every key twice
    return Table([Column(k, dt.INT64), Column(k * 3, dt.INT64)])


@pytest.fixture(autouse=True)
def _clean():
    for f in FLAGS:
        config.clear_flag(f)
    yield
    for f in FLAGS:
        config.clear_flag(f)
    flight.reset()
    metrics.reset()


@pytest.fixture
def live():
    config.set_flag("METRICS", True)
    config.set_flag("FLIGHT", True)
    metrics.reset()
    flight.reset()


def _device(snap):
    t = snap["timers"]
    progs = {k: v for k, v in t.items() if k.startswith("device.srt_")}
    segs = {k: v for k, v in t.items()
            if k.startswith("device.plan.segment.")}
    return t.get("device.busy"), t.get("device.idle"), progs, segs


def _intervals():
    return sorted((e for e in flight.tail_records() if e["ph"] == "X"),
                  key=lambda e: e["t_ns"])


def test_off_means_no_thread_and_no_record():
    # its own starting point, whatever ran before it in this worker: the
    # plane off by its own hand (`_clean` restores it), no clock thread,
    # and an empty registry: another file's `device.*` timers outlive
    # that file's flags
    config.set_flag("METRICS", False)
    devclock.shutdown()
    metrics.reset()
    fn = buckets.cached_jit(
        ("devclock.off",), lambda: (lambda x: x + 1), "srt_devclock_off")
    assert isinstance(fn, devclock.Launch)
    assert int(fn(jnp.arange(4))[3]) == 4
    plan_mod.run_plan(CHAIN, _fact())
    assert not any(t.name == devclock.THREAD_NAME
                   for t in threading.enumerate())
    snap = metrics.snapshot()
    assert not any(k.startswith("device.") for k in snap["timers"])
    assert devclock.stats_doc()["by_program"] == {}
    # the same callable starts reporting when the plane turns on
    config.set_flag("METRICS", True)
    fn(jnp.arange(4))
    assert metrics.snapshot()["timers"]["device.srt_devclock_off"]["count"] == 1
    assert any(t.name == devclock.THREAD_NAME and t.daemon
               for t in threading.enumerate())


def test_one_interval_a_launch_and_sums_telescope(live):
    for _ in range(3):
        out = plan_mod.run_plan(CHAIN, _fact(), donate_input=True)
        time.sleep(0.01)  # a host that enqueues nothing: an idle gap
    assert out.logical_row_count == 10
    busy, idle, progs, segs = _device(metrics.snapshot())
    assert {k: v["count"] for k, v in progs.items()} == {
        "device.srt_fused_plan": 6, "device.srt_groupby_reduce": 3}
    assert {k: v["count"] for k, v in segs.items()} == {
        "device.plan.segment.filter__groupby": 6,
        "device.plan.segment.sort_by__slice": 3}
    assert busy["count"] == 9
    assert sum(v["total_s"] for v in progs.values()) == pytest.approx(
        busy["total_s"], abs=1e-9)
    assert sum(v["total_s"] for v in segs.values()) == pytest.approx(
        busy["total_s"], abs=1e-9)
    recs = _intervals()
    assert len(recs) == 9
    for a, b in zip(recs, recs[1:]):
        assert a["t_ns"] <= a["arg"]["end_ns"] <= b["t_ns"]
    extent = (recs[-1]["arg"]["end_ns"] - recs[0]["t_ns"]) / 1e9
    assert idle["count"] >= 2 and idle["total_s"] >= 0.02
    assert busy["total_s"] + idle["total_s"] == pytest.approx(
        extent, abs=1e-6)


def test_idle_goes_to_the_span_that_covered_it(live):
    fn = buckets.cached_jit(
        ("devclock.idle",), lambda: (lambda x: x * 2), "srt_devclock_idle")
    x = jnp.arange(16)
    fn(x)
    # stamped before the span opens: else a late stamp of this launch
    # lies inside `serving.plan`, and the gap behind it is that span's
    metrics.snapshot()
    with metrics.span("serving.plan"):
        fn(x)
        metrics.snapshot()  # that launch is done: the gap starts here
        time.sleep(0.02)
        with metrics.span("plan"):  # opened inside the gap: not its owner
            fn(x)
    metrics.snapshot()
    time.sleep(0.02)
    fn(x)  # no span at all
    timers = metrics.snapshot()["timers"]
    assert timers["device.idle.serving.plan"]["count"] == 1
    assert timers["device.idle.serving.plan"]["total_s"] >= 0.02
    assert timers["device.idle.none"]["count"] >= 1
    assert "device.idle.plan" not in timers
    assert timers["device.idle"]["total_s"] == pytest.approx(
        sum(t["total_s"] for k, t in timers.items()
            if k.startswith("device.idle.")), abs=1e-9)


def test_donated_chain_fifty_times_loses_nothing(live):
    for _ in range(50):
        out = plan_mod.run_plan(CHAIN, _fact(), donate_input=True)
    assert out.logical_row_count == 10
    snap = metrics.snapshot()
    assert snap["counters"].get("device.lost", 0) == 0
    assert snap["counters"].get("plan.fallbacks", 0) == 0
    assert snap["bytes"]["hbm.donated_bytes"] > 0
    assert snap["timers"]["device.busy"]["count"] == 150


def test_two_sessions_side_by_side_lose_no_interval(live):
    plan_mod.run_plan(CHAIN, _fact())  # compiled once, not twice at once
    metrics.reset()
    flight.reset()
    errors = []

    def session():
        try:
            for _ in range(20):
                plan_mod.run_plan(CHAIN, _fact())
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=session) for _ in range(2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)  # hand-overs mid-launch, as often as may be
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    busy, idle, progs, _ = _device(metrics.snapshot())
    assert busy["count"] == 2 * 20 * 3
    assert progs["device.srt_fused_plan"]["count"] == 80
    assert progs["device.srt_groupby_reduce"]["count"] == 40
    recs = _intervals()
    assert len(recs) == 120
    for a, b in zip(recs, recs[1:]):
        assert a["arg"]["end_ns"] <= b["t_ns"]
    extent = (recs[-1]["arg"]["end_ns"] - recs[0]["t_ns"]) / 1e9
    assert busy["total_s"] + (idle or {"total_s": 0.0})["total_s"] == (
        pytest.approx(extent, abs=1e-6))


def test_snapshot_holds_every_launch_made_so_far(live):
    fn = buckets.cached_jit(
        ("devclock.drain",), lambda: (lambda x: jnp.sort(x)[::-1].cumsum()),
        "srt_devclock_drain")
    x = jnp.arange(1 << 18)
    fn(x)
    metrics.reset()
    for _ in range(8):
        fn(x)  # returns at enqueue
    assert metrics.snapshot()["timers"]["device.srt_devclock_drain"][
        "count"] == 8
    assert devclock.drain() is True


def test_a_value_that_cannot_be_waited_for_is_lost_not_raised(live):
    class Gone:
        ndim = 0

        def block_until_ready(self):
            raise RuntimeError("Array has been deleted")

    devclock.launched("srt_devclock_gone", (Gone(),))
    devclock.launched("srt_devclock_host", (3,))  # nothing to wait for
    snap = metrics.snapshot()
    assert snap["counters"]["device.lost"] == 1
    assert "device.srt_devclock_gone" not in snap["timers"]
    assert snap["timers"]["device.srt_devclock_host"]["count"] == 1


def test_a_scalar_result_is_waited_on_as_it_is():
    count = jnp.int32(7)
    table = Table([Column(jnp.arange(8), dt.INT64)])
    assert devclock._waitable((table, count)) is count
    one = devclock._waitable(table)
    assert one.shape == () and int(one) == 0
    assert one is not table.columns[0].data
    assert devclock._waitable(()) is None
    span = jnp.arange(4)  # a bare array is the host's to read: as it is
    assert devclock._waitable(span) is span


def test_a_span_asks_for_its_device_time(live):
    """``device.<span>`` is the innermost open span that asked
    (``device=True``); a span that did not ask gets no device timer,
    and one that ended before its launch completed still gets it."""
    fn = buckets.cached_jit(
        ("devclock.asked",), lambda: (lambda x: jnp.sort(x).cumsum()),
        "srt_devclock_asked")
    x = jnp.arange(1 << 16)
    fn(x)
    metrics.reset()
    with metrics.span("plan"):
        fn(x)  # nobody asked
        with metrics.span("plan.segment.outer", device=True):
            fn(x)
            with metrics.span("groupby.reduce"):  # did not ask: outer's
                fn(x)
            with metrics.span("plan.segment.inner", device=True):
                fn(x)
    timers = metrics.snapshot()["timers"]
    assert timers["device.srt_devclock_asked"]["count"] == 4
    assert timers["device.plan.segment.outer"]["count"] == 2
    assert timers["device.plan.segment.inner"]["count"] == 1
    assert "device.plan" not in timers
    assert "device.groupby.reduce" not in timers
    asked = sum(timers["device.plan.segment." + k]["total_s"]
                for k in ("outer", "inner"))
    assert asked <= timers["device.busy"]["total_s"] + 1e-9


class _Later:
    """A value that is ready at a time of the test's choosing."""

    ndim = 0

    def __init__(self):
        self.ready = threading.Event()

    def block_until_ready(self):
        assert self.ready.wait(30.0)


def test_reports_out_of_device_order_trade_an_interval_and_lose_none(live):
    """Two sessions that launch within microseconds of each other can
    report in the other order: the clock then waits for the LATER
    program first. Both intervals are recorded, none overlaps and the
    sums telescope; the earlier program reads next to nothing."""
    first, second = _Later(), _Later()
    devclock.launched("srt_devclock_second", (second,))  # reported first
    devclock.launched("srt_devclock_first", (first,))
    t0 = time.perf_counter()
    first.ready.set()  # the device's order: first, then second
    time.sleep(0.02)
    second.ready.set()
    snap = metrics.snapshot()
    timers = snap["timers"]
    assert snap["counters"].get("device.lost", 0) == 0
    assert timers["device.busy"]["count"] == 2
    assert timers["device.srt_devclock_second"]["total_s"] >= 0.02
    assert timers["device.srt_devclock_first"]["total_s"] < 0.02
    assert timers["device.busy"]["total_s"] == pytest.approx(
        timers["device.srt_devclock_first"]["total_s"]
        + timers["device.srt_devclock_second"]["total_s"], abs=1e-9)
    recs = _intervals()
    assert len(recs) == 2
    assert recs[0]["arg"]["end_ns"] <= recs[1]["t_ns"]
    extent = (recs[-1]["arg"]["end_ns"] - recs[0]["t_ns"]) / 1e9
    assert timers["device.busy"]["total_s"] == pytest.approx(
        extent, abs=1e-6)
    assert extent <= time.perf_counter() - t0 + 0.01


def test_a_scrape_does_not_wait_for_the_device(live):
    """``snapshot()`` drains the clock (a window's delta needs every
    launch); the Prometheus text and the daemon's ``stats`` do not wait:
    a launch in flight is in the next reading."""
    slow = _Later()
    devclock.launched("srt_devclock_slow", (slow,))
    t0 = time.perf_counter()
    text = metrics.prometheus_text()
    doc = devclock.stats_doc()
    assert time.perf_counter() - t0 < 0.5 * devclock.DRAIN_WAIT_S
    assert "srt_device_srt_devclock_slow" not in text
    assert doc["by_program"] == {} and doc["lost"] == 0
    slow.ready.set()
    assert devclock.drain() is True
    assert devclock.stats_doc()["by_program"]["srt_devclock_slow"][
        "count"] == 1


def test_the_kernel_tier_is_outside_the_clock(live):
    """The row kernels are jitted in the kernel tier, outside
    ``cached_jit``, with eager ops and two host reads around them: the
    clock files nothing for them rather than a bound (ROADMAP A8)."""
    config.set_flag("KERNELS", "on")
    try:
        rows = plan_mod.run_plan([{"op": "to_rows"}], _fact(64))
    finally:
        config.clear_flag("KERNELS")
    assert rows.logical_row_count == 64
    snap = metrics.snapshot()
    assert snap["counters"]["kernel.launches"] == 1
    assert snap["timers"]["kernel.row_pack"]["count"] == 1
    assert not [k for k in snap["timers"] if k.startswith("device.kernel")]
    assert "device.plan.segment.to_rows" not in snap["timers"]
    assert snap["counters"].get("device.lost", 0) == 0


def test_longest_gauges_name_the_program(live):
    small = buckets.cached_jit(
        ("devclock.small",), lambda: (lambda x: x + 1), "srt_devclock_small")
    large = buckets.cached_jit(
        ("devclock.large",), lambda: (lambda x: jnp.sort(x).cumsum()),
        "srt_devclock_large")
    small(jnp.arange(8))
    large(jnp.arange(1 << 20))
    metrics.reset()
    flight.reset()
    small(jnp.arange(8))
    metrics.snapshot()
    time.sleep(0.03)
    large(jnp.arange(1 << 20))
    small(jnp.arange(8))
    snap = metrics.snapshot()
    longest = snap["gauges"]["device.longest_ms"]["high_water"]
    assert longest == pytest.approx(
        1e3 * snap["timers"]["device.busy"]["max_s"])
    assert snap["gauges"]["device.idle.longest_ms"]["high_water"] >= 30.0
    named = [e["arg"] for e in flight.tail_records()
             if e["name"] == "device.longest"]
    assert named[-1].startswith("srt_devclock_large ")


def _lowered_by_program(monkeypatch, plan, table, rest):
    """Run ``plan`` and return {program: lowered text of each launch}."""
    texts = {}
    real = buckets.cached_jit

    def spy(key, build, name, donate_args=(), scope=None):
        fn = real(key, build, name, donate_args=donate_args, scope=scope)

        def call(*args):
            texts.setdefault(name, []).append(fn.lower(*args).as_text())
            return fn(*args)

        return call

    monkeypatch.setattr(buckets, "cached_jit", spy)
    buckets.cache_clear()
    out = plan_mod.run_plan(plan, table, rest)
    monkeypatch.setattr(buckets, "cached_jit", real)
    return texts, out.logical_row_count


@pytest.mark.parametrize("program", [
    "srt_fused_plan", "srt_groupby_reduce", "srt_bucketed_join_mat"])
def test_lowered_text_is_the_same_with_the_clock_on(monkeypatch, program):
    """The clock adds no output to a served program: byte-equal text."""
    plan = JOIN_PLAN if program == "srt_bucketed_join_mat" else CHAIN
    rest = [_dim()] if plan is JOIN_PLAN else []
    off, rows_off = _lowered_by_program(monkeypatch, plan, _fact(), rest)
    config.set_flag("METRICS", True)
    on, rows_on = _lowered_by_program(monkeypatch, plan, _fact(), rest)
    assert rows_on == rows_off and sorted(on) == sorted(off)
    assert off[program] and on[program] == off[program]
    assert metrics.snapshot()["timers"]["device." + program]["count"] == len(
        on[program])


def test_jax_build_is_positive_cold_and_zero_warm():
    config.set_flag("METRICS", True)
    metrics.reset()
    fn = buckets.cached_jit(
        ("devclock.build", time.time_ns()),
        lambda: (lambda x: (x * 5 - 2).sum()), "srt_devclock_build")
    x = jnp.arange(64)
    fn(x)
    cold = metrics.snapshot()
    build = cold["timers"]["jax.build"]
    assert build["count"] >= 3 and build["total_s"] > 0.0  # trace, lower, compile
    fn(x)
    warm = metrics.snapshot()
    assert warm["timers"]["jax.build"] == build
    assert warm["timers"]["device.srt_devclock_build"]["count"] == 2


def test_chrome_trace_shows_a_device_lane(live):
    ctx = tracing.new_context()
    with tracing.activate(ctx), metrics.span("plan"):
        plan_mod.run_plan(CHAIN, _fact())
    metrics.snapshot()
    events = flight.tail_records()
    json.dumps(events)  # the ring's records stay JSON-able
    doc = tracing.to_chrome_trace(events)
    lanes = {e["tid"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"
             and e["args"]["name"].startswith("device (")}
    assert len(lanes) == 1
    device = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("device.srt_")]
    assert len(device) == 3 and {e["tid"] for e in device} == lanes
    assert all(e["dur"] >= 0 and "/plan.segment." in e["args"]["span"]
               and e["args"]["traceparent"] for e in device)
    detail = tracing.trace_span_records(events, ctx.trace_id)
    assert sum(r["name"].startswith("device.srt_") for r in detail) == 3


def test_prometheus_text_carries_the_device_timers(live):
    plan_mod.run_plan(CHAIN, _fact())
    assert devclock.drain() is True
    text = metrics.prometheus_text()
    assert "srt_device_busy_seconds_total" in text
    assert "srt_device_srt_fused_plan_seconds_count 2" in text
    assert "srt_device_longest_ms_high_water" in text
