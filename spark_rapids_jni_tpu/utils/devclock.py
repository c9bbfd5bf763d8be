"""The completion clock — when the device finished what a span launched.

A ``metrics.span`` is a host span: it ends at a count read where its
runner has one and at enqueue where it has none, and whoever syncs next
is charged for the device time in between (a groupby's per-group half,
a join's materialise, ``sort_by``, ``project``). This module records the
other end. Every callable ``buckets.cached_jit`` hands out is a
:class:`Launch`, which reports each call here: program name, enqueue
stamp, the launching thread's open spans, the ambient trace id and ONE
device value of the result. One daemon thread (``srt-devclock``) waits
for each value in enqueue order — ``block_until_ready``, the GIL
released — and stamps its completion. No sync is added to the hot path
and no compiled program changes.

The device runs one queue in order, so a launch's **device interval**
is ``[max(its enqueue, the previous completion), its completion]`` and
an **idle gap** is ``[previous completion, next enqueue]`` where that is
positive. The intervals telescope: ``busy + idle`` is the last
completion minus the first enqueue, whatever wake-up latency two
neighbours trade.

What it records is the registry's (``metrics.timer_record``, so a
window's timer deltas read it as data):

* ``device.busy`` every interval, ``device.idle`` every gap;
* ``device.<program>`` — one timer a ``cached_jit`` name
  (``device.srt_fused_plan``);
* ``device.<span>`` — the interval goes to the innermost span open on
  the launching thread at enqueue that asked for it
  (``metrics.span(..., device=True)``: the plan's
  ``plan.segment.<sig>`` spans, hence ``device.plan.segment.<sig>``),
  though the span may have ended long before the launch completes;
* ``device.idle.<span>`` — the gap goes to the innermost span of the
  thread that ended it, provided that span was open when the gap began;
  ``device.idle.none`` where none was;
* gauges ``device.longest_ms`` / ``device.idle.longest_ms`` (with a
  ``device.longest`` flight instant naming the program): after a stall
  the dump says whether the device sat on queued work or the host
  enqueued nothing;
* counter ``device.lost``: a completion that could not be observed;
* with ``FLIGHT`` on, one ``"X"`` record an interval on the clock
  thread's lane (``tools/trace2chrome.py`` shows it beside the
  threads'): name, start (``t_ns``), ``end_ns``, the launching span's
  qualified name, the traceparent.

**Donation.** A plan-owned intermediate is donated to the next
segment's executable, so a value this clock holds may be deleted under
it. It therefore waits only on a value no later launch can donate: a
scalar the program returns (the fused plan's count, the probe's totals),
a result that is one bare array (the key span the host reads at once)
or, for a program that returns tables only, ONE element of one output
leaf, taken on the launching thread before the result leaves the
runner — one tiny eager dispatch, compiled once a shape. No served
program gains an output.

**What an interval is not.** It runs from the moment the device could
have begun the launch — its enqueue, or the previous completion — to its
completion. A launch enqueued while an input is still crossing to the
device (a streamed batch's ``device_put``) waits for the transfer inside
its interval: the clock sees launches, not transfers, so there
``device.busy`` reads "a launch in flight", an upper bound of the
device's compute, and ``device.idle`` (nothing in flight) stays exact.
On resident inputs the two are the same. Programs launched outside
``cached_jit`` (the mesh stage's, the kernel tier's row kernels and the
eager ops around them) are not seen at all.

**Order.** Reports are queued in the order the launching threads make
them, which is the device's order on one thread. Two threads that
launch within microseconds of each other can report in the other order:
the later completion is then stamped first and the earlier launch reads
(almost) nothing, so two PROGRAMS can trade one interval. No interval
is lost or counted twice and every sum telescopes as before; holding a
lock across launch and report would close the window and serialise a
cold compile against every other session, which is worse.

Gating: the clock runs under ``METRICS`` and has no flag of its own.
With ``METRICS`` off a launch costs the cached gate compare and no
thread exists. ``metrics.snapshot()`` drains the clock first (bounded),
so a window's delta holds every launch of the window.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Optional

import jax
from jax import lax

from . import flight
from . import metrics
from . import tracing

# how long snapshot() / reset() wait for the launches in flight
DRAIN_WAIT_S = 2.0

THREAD_NAME = "srt-devclock"

_Q: "queue.SimpleQueue" = queue.SimpleQueue()
_THREAD: Optional[threading.Thread] = None
_START_LOCK = threading.Lock()
# launches reported and not yet stamped: with none, a drain is a compare
_PENDING = 0
_PENDING_LOCK = threading.Lock()


@jax.jit
def _first_element(x):
    # the slice comes first: a reshape of the whole leaf may copy it
    return lax.reshape(lax.slice(x, (0,) * x.ndim, (1,) * x.ndim), ())


def _waitable(out):
    """The value whose readiness says the launch is done and that no
    later launch can donate: a result that is one bare array, a scalar
    among the results, else one element of the first non-empty leaf
    (None: nothing to wait for). One executable's outputs are ready
    together."""
    if isinstance(out, jax.Array):
        # a bare array is the host's to read (a join's key span, four
        # words), never a plan's flowing table: nothing donates it
        return out
    if type(out) is tuple:
        for x in out:  # the count beside the table: no need to flatten
            if getattr(x, "ndim", None) == 0:
                return x
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if getattr(x, "ndim", None) is not None]
    for leaf in leaves:
        if leaf.ndim == 0:
            return leaf
    for leaf in leaves:
        if leaf.size:
            return _first_element(leaf)
    return None


def launched(name: str, out) -> None:
    """Report one launch, on the launching thread, right after the call
    that enqueued it returned ``out``: that is its enqueue stamp. Never
    raises."""
    enq = time.perf_counter()
    try:
        value = _waitable(out)
    # srt: allow-broad-except(the clock observes a launch and must never fail it: an unobservable completion is counted)
    except Exception:
        metrics.counter_add("device.lost")
        return
    global _PENDING
    with _PENDING_LOCK:
        _PENDING += 1
    _Q.put((
        name, enq, metrics.open_spans(),
        tracing.current_traceparent() if flight.enabled() else None,
        value,
    ))
    if _THREAD is None:
        _start()


class Launch:
    """A jitted callable that reports each call to the clock. Everything
    else (``lower``, ``__name__``) is the jitted callable's."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self.__name__ = self.__qualname__ = name

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        if metrics.enabled():
            launched(self.__name__, out)
        return out

    def __getattr__(self, attr):
        if attr == "_fn":  # not yet set (a copy in the making): no loop
            raise AttributeError(attr)
        return getattr(self._fn, attr)


class _Fence:
    """A place in the queue: set once everything before it is stamped."""

    __slots__ = ("event", "reset", "stop")

    def __init__(self, reset: bool = False, stop: bool = False):
        self.event = threading.Event()
        self.reset = reset
        self.stop = stop


def _start() -> None:
    global _THREAD
    with _START_LOCK:
        if _THREAD is None:
            _THREAD = threading.Thread(
                target=_run, name=THREAD_NAME, daemon=True
            )
            _THREAD.start()


def _fence(**kw) -> bool:
    if _THREAD is None or threading.current_thread() is _THREAD:
        return True
    f = _Fence(**kw)
    _Q.put(f)
    return f.event.wait(DRAIN_WAIT_S)


def drain() -> bool:
    """Wait (bounded) until every launch reported so far is stamped."""
    return _PENDING == 0 or _fence()


def reset() -> None:
    """Drain, then forget the previous completion and the high-water
    marks: the next launch opens a timeline of its own."""
    _fence(reset=True)


def shutdown() -> None:
    """Stop the clock thread (test isolation); the next launch under
    ``METRICS`` starts a new one."""
    global _THREAD
    with _START_LOCK:
        t = _THREAD
        if t is None:
            return
        _fence(reset=True, stop=True)
        t.join(DRAIN_WAIT_S)
        _THREAD = None


def _covering(spans, t: float) -> str:
    """The innermost of ``spans`` that was open at ``t``."""
    for s in reversed(spans):
        if s.t0 <= t:
            return s.name
    return "none"


class _Timeline:
    """What the clock thread carries from one completion to the next."""

    __slots__ = ("prev", "longest", "idle_longest")

    def __init__(self):
        self.prev = None  # the previous completion
        self.longest = self.idle_longest = 0.0

    def stamp(self, name, enq, spans, tp, value) -> None:
        """Wait for one launch; record its interval and the gap before."""
        try:
            if value is not None:
                value.block_until_ready()
        except AttributeError:
            pass  # a host scalar: done when it was returned
        # srt: allow-broad-except(a deleted or failed value: the completion cannot be observed, it is counted and its time falls to the neighbours)
        except Exception:
            metrics.counter_add("device.lost")
            return
        done = time.perf_counter()
        start, prev = enq, self.prev
        if prev is not None:
            gap = enq - prev
            if gap > 0.0:
                metrics.timer_record("device.idle", gap)
                metrics.timer_record(
                    "device.idle." + _covering(spans, prev), gap
                )
                if gap > self.idle_longest:
                    self.idle_longest = gap
                    metrics.gauge_set("device.idle.longest_ms", gap * 1e3)
            else:
                start = prev
        busy = done - start
        self.prev = done
        metrics.timer_record("device.busy", busy)
        metrics.timer_record("device." + name, busy)
        for s in reversed(spans):
            if s.device:
                metrics.timer_record("device." + s.name, busy)
                break
        if busy > self.longest:
            self.longest = busy
            metrics.gauge_set("device.longest_ms", busy * 1e3)
            if flight.enabled():
                flight.record(
                    "I", "device.longest", f"{name} {busy * 1e3:.3f}ms"
                )
        if flight.enabled():
            flight.record(
                "X", "device." + name,
                {
                    "end_ns": int(done * 1e9),
                    "span": spans[-1].qualname if spans else None,
                    "tp": tp,
                },
                t_ns=int(start * 1e9),
            )


def _run() -> None:
    global _PENDING
    line = _Timeline()
    while True:
        item = _Q.get()
        if type(item) is _Fence:
            if item.reset:
                line = _Timeline()
            item.event.set()
            if item.stop:
                return
            continue
        line.stamp(*item)
        with _PENDING_LOCK:
            _PENDING -= 1


metrics.register_settler(drain, reset)


def stats_doc() -> dict:
    """The daemon's ``stats`` view: what the device did since the
    registry was last reset (empty with ``METRICS`` off). An operator's
    command never waits for the device: a launch in flight is in the
    next reading."""
    snap = metrics.snapshot(settle=False)
    timers, gauges = snap["timers"], snap["gauges"]

    def total(name):
        return timers.get(name, {}).get("total_s", 0.0)

    def high(name):
        return gauges.get(name, {}).get("high_water", 0.0)

    skip = ("device.busy", "device.idle", "device.plan.")
    return {
        "busy_s": total("device.busy"),
        "idle_s": total("device.idle"),
        "longest_ms": high("device.longest_ms"),
        "idle_longest_ms": high("device.idle.longest_ms"),
        "lost": snap["counters"].get("device.lost", 0),
        "by_program": {
            k[len("device."):]: {"count": t["count"], "total_s": t["total_s"]}
            for k, t in timers.items()
            if k.startswith("device.") and not k.startswith(skip)
        },
    }
