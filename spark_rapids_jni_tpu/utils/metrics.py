"""Op-level metrics registry + structured spans — the ``GpuMetric`` role.

The reference is observable end to end: per-operator ``GpuMetric``
counters (op time, rows, bytes) surface in Spark's SQL UI, and NVTX
ranges (reference pom.xml:85,200) mark the hot kernels in Nsight. This
module is both planes for the TPU runtime:

* a process-wide, thread-safe registry of named **counters**, **byte
  counters**, **wall-clock timers**, bounded **histograms**, and
  high-water **gauges** (the leak-report analog for resident handles);
* a ``span(name, **attrs)`` context manager — THE one way to open a
  layer-boundary span — that nests (thread-local stack, carried across
  a thread hop by :func:`adopt`), records its wall-clock duration into
  the timer registry — including on the exception path — always opens
  a ``jax.profiler.TraceAnnotation`` named ``"srt/" + qualname`` (so a
  profiler capture holds the program's spans on the device's clock),
  and emits one structured stderr line on the ``span`` channel when
  ``LOG_LEVEL`` admits TRACE.

Gating follows the ``log.enabled()`` discipline: :func:`enabled` is a
cheap check (``SPARK_RAPIDS_TPU_METRICS`` truthy, or a
``SPARK_RAPIDS_TPU_METRICS_DUMP`` path configured) and every mutator
no-ops when it is false, so instrumented hot paths cost a couple of
dict lookups when shipped disabled — the reference's ship-it-disabled
default. :func:`snapshot` returns a JSON-able dict; when a dump path is
configured the snapshot is also written there at interpreter exit
(atexit).
"""

from __future__ import annotations

import atexit
import bisect
import functools
import json
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax.monitoring
import jax.profiler

from . import config
from . import flight
from . import log
from . import tracing

# ---------------------------------------------------------------------------
# registry state — one lock guards every table; mutations are a few dict
# ops so contention stays negligible even under the concurrent-dispatch
# test tier (tests/test_metrics.py hammers it from many threads).
# RLock, not Lock: the package flushes its dumps at exit (atexit), and
# an embedder's signal handler that calls snapshot()/dump() runs on the
# MAIN thread — if the signal lands while that same thread is inside a
# mutator's critical section, a non-reentrant lock would self-deadlock.
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()
_COUNTERS: Dict[str, int] = {}
_BYTES: Dict[str, int] = {}
# name -> [count, total_s, min_s, max_s]
_TIMERS: Dict[str, List[float]] = {}
# name -> [value, high_water]
_GAUGES: Dict[str, List[float]] = {}
# name -> {"bounds": tuple, "counts": list, "count": int, "sum": float}
_HISTS: Dict[str, dict] = {}
# name -> [count, total_s] of span SELF time (duration minus enclosed
# child spans on the same thread); total time alone buries the hot
# leaf under its wrappers
_SELF: Dict[str, List[float]] = {}

# bounded histogram default: powers of 4 from 1 to ~10^9 (17 buckets
# incl. overflow) — sized for row counts and byte volumes
_DEFAULT_BOUNDS = tuple(4 ** i for i in range(16))

_TLS = threading.local()

_ANNOTATION = jax.profiler.TraceAnnotation  # a name of its own: tests swap it

# Gate cache, invalidated by config.generation(): a disabled
# instrumentation site costs one int compare + attribute read instead
# of re-reading os.environ per call (measured ~6us/span uncached vs
# ~0.2us cached — the difference between "near-zero" and 0.5% of a
# small dispatch). Flags flipped via config.set_flag/clear_flag are
# picked up immediately; raw mid-process os.environ writes are not
# (see config.generation()).
_GATE_GEN = -1
_GATE_ENABLED = False
_GATE_SPAN = False
_GATE_FLIGHT = False


# jax's own account of building a program, as one timer: every duration
# event of trace, lowering and backend compile (the persistent cache's
# retrieval is inside the last) goes to `jax.build`. The listener fires
# on trace and compile events only, so a warm path never reaches it;
# programs built outside `cached_jit` (the mesh stage's) are seen here
# and nowhere else.
_JAX_BUILD_PREFIX = "/jax/core/compile/"
_JAX_LISTENING = False


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    if event.startswith(_JAX_BUILD_PREFIX):
        timer_record("jax.build", duration_secs)


def _listen_to_jax() -> None:
    """Registered once, when the plane first turns on; with the plane
    off again the mutator no-ops."""
    global _JAX_LISTENING
    if not _JAX_LISTENING:
        _JAX_LISTENING = True
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _refresh_gate() -> None:
    global _GATE_GEN, _GATE_ENABLED, _GATE_SPAN, _GATE_FLIGHT
    _GATE_ENABLED = (
        bool(config.get_flag("METRICS"))
        or bool(config.get_flag("METRICS_DUMP"))
        # the plan-stats store diffs counters around every profile
        # session (utils/planstats.py) — stats with all-zero spill/
        # retry/shed columns would be silently wrong, so PLANSTATS
        # pulls the registry on with it
        or bool(config.get_flag("PLANSTATS"))
        or bool(str(config.get_flag("PLANSTATS_DIR") or ""))
    )
    _GATE_FLIGHT = flight.enabled()
    _GATE_SPAN = (
        _GATE_ENABLED
        or _GATE_FLIGHT
        or tracing.tracing_enabled()
        or log.enabled("TRACE", "span")
    )
    _GATE_GEN = config.generation()
    if _GATE_ENABLED:
        _listen_to_jax()


def enabled() -> bool:
    """True when the metrics plane is on — instrumentation sites guard
    expensive field construction with this (the log.enabled() pattern);
    a configured dump path implies collection."""
    if _GATE_GEN != config.generation():
        _refresh_gate()
    return _GATE_ENABLED


# ---------------------------------------------------------------------------
# mutators — every one no-ops when the plane is off, so un-guarded call
# sites stay near-zero too
# ---------------------------------------------------------------------------


def counter_add(name: str, n: int = 1) -> None:
    """Bump a named event counter (op calls, rows, retries, ...)."""
    if not enabled():
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def bytes_add(name: str, n: int) -> None:
    """Bump a named byte counter (wire traffic, planned HBM, ...)."""
    if not enabled():
        return
    with _LOCK:
        _BYTES[name] = _BYTES.get(name, 0) + int(n)


def counter_values(names: Sequence[str]) -> Dict[str, int]:
    """Point-in-time values of named counters/byte-counters (0 when a
    name was never ticked) — the cheap targeted read planstats diffs
    around each profile session, vs snapshot() which copies every
    table."""
    with _LOCK:
        return {
            n: int(_COUNTERS.get(n) or _BYTES.get(n) or 0) for n in names
        }


def timer_record(name: str, seconds: float) -> None:
    """Fold one wall-clock duration into a named timer."""
    if not enabled():
        return
    s = float(seconds)
    with _LOCK:
        t = _TIMERS.get(name)
        if t is None:
            _TIMERS[name] = [1, s, s, s]
        else:
            t[0] += 1
            t[1] += s
            if s < t[2]:
                t[2] = s
            if s > t[3]:
                t[3] = s


def gauge_set(name: str, value) -> None:
    """Set a gauge, tracking its high-water mark (resident handles,
    planned capacities)."""
    if not enabled():
        return
    v = float(value)
    with _LOCK:
        g = _GAUGES.get(name)
        if g is None:
            _GAUGES[name] = [v, v]
        else:
            g[0] = v
            if v > g[1]:
                g[1] = v


def self_time_record(name: str, seconds: float) -> None:
    """Fold one span SELF-time observation (duration minus child spans)
    into the ``span_self`` table."""
    if not enabled():
        return
    s = max(float(seconds), 0.0)
    with _LOCK:
        t = _SELF.get(name)
        if t is None:
            _SELF[name] = [1, s]
        else:
            t[0] += 1
            t[1] += s


def hist_observe(
    name: str, value, bounds: Optional[Sequence[float]] = None
) -> None:
    """Record one observation into a bounded histogram. ``bounds`` (used
    only on the first observation of ``name``) are inclusive upper bucket
    edges; one overflow bucket is appended."""
    if not enabled():
        return
    v = float(value)
    with _LOCK:
        h = _HISTS.get(name)
        if h is None:
            b = tuple(bounds) if bounds else _DEFAULT_BOUNDS
            h = _HISTS[name] = {
                "bounds": b,
                "counts": [0] * (len(b) + 1),
                "count": 0,
                "sum": 0.0,
            }
        h["counts"][bisect.bisect_left(h["bounds"], v)] += 1
        h["count"] += 1
        h["sum"] += v


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _NullSpan:
    """Shared no-op span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


# span-duration histogram edges in MILLISECONDS: ~x3 rungs from 10us to
# 30s + overflow — wide enough for a cold compile, fine enough that
# p50/p95 estimates are meaningful. Public: subsystem-owned duration
# histograms (pipeline.stall_ms / pipeline.overlap_ms) share these
# edges so percentiles line up across planes.
SPAN_MS_BOUNDS = (
    0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0,
    1000.0, 3000.0, 10000.0, 30000.0,
)


class _Span:
    __slots__ = ("name", "attrs", "qualname", "t0", "device",
                 "_trace_cm", "_child_s")

    def __init__(self, name: str, attrs: dict, device: bool = False):
        self.name = name
        self.attrs = attrs
        self.qualname = name
        self.t0 = 0.0  # perf_counter at __enter__
        self.device = device
        self._trace_cm = None
        self._child_s = 0.0

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        # nesting: the qualified name carries the enclosing span path so
        # the TRACE line / profiler range shows WHERE the op ran; the
        # timer aggregates under the plain name so repeated ops fold
        # into one stable registry row
        self.qualname = (
            stack[-1].qualname + "/" + self.name if stack else self.name
        )
        stack.append(self)
        # the shared clock: a profiler capture shows this span on its
        # thread's line of the host plane, on the device's clock. With
        # no capture running the annotation costs ~0.4 us.
        self._trace_cm = _ANNOTATION(
            tracing.ANNOTATION_PREFIX + self.qualname
        )
        self._trace_cm.__enter__()
        if _GATE_FLIGHT:
            # the ambient trace context rides the B arg (one contextvar
            # read; None outside a traced request, and flight omits
            # None args) — the join key tracequery/assign_trace_ids
            # merge per-process dumps on
            flight.record("B", self.qualname, tracing.current_traceparent())
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        # duration is recorded on the exception path too: a span that
        # dies mid-op is exactly the one the telemetry must explain
        dur = time.perf_counter() - self.t0
        if _GATE_FLIGHT:
            flight.record(
                "E", self.qualname,
                None if exc_type is None else exc_type.__name__,
            )
        stack = getattr(_TLS, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        if self._trace_cm is not None:
            self._trace_cm.__exit__(exc_type, exc, tb)
            self._trace_cm = None
        timer_record(self.name, dur)
        if _GATE_ENABLED:
            # self time: what THIS span spent outside its children —
            # the parent (still on the stack, same thread) absorbs our
            # whole duration into its child accumulator
            if stack:
                stack[-1]._child_s += dur
            self_time_record(self.name, dur - self._child_s)
            hist_observe(
                "span_ms." + self.name, dur * 1e3, bounds=SPAN_MS_BOUNDS
            )
        if exc_type is not None:
            counter_add("span." + self.name + ".errors")
        if log.enabled("TRACE", "span"):
            log.log(
                "TRACE", "span", self.qualname,
                dur_ms=round(dur * 1e3, 3),
                ok=exc_type is None,
                **self.attrs,
            )
        return False


def span(name: str, device: bool = False, **attrs):
    """Context manager: a named, nestable timed region — the one span
    API of the served path.

    Records duration into the timer registry under ``name`` (exception
    path included) plus self-time and a ``span_ms.*`` duration
    histogram, emits begin/end events (the B event carries the ambient
    traceparent) into the flight recorder when
    ``SPARK_RAPIDS_TPU_FLIGHT`` is on, opens a
    ``jax.profiler.TraceAnnotation`` named ``"srt/" + qualname``
    whenever it is live, and emits one ``[srt][span][TRACE]`` stderr
    line when the log level admits it. Returns a shared no-op object
    when every plane is off — the hot-path cost of a disabled span is
    one generation compare on the cached gate.

    ``device=True`` asks the completion clock (utils/devclock.py) for
    the span's device-ended time as well: every program launched on
    this thread while the span is the innermost such one leaves its
    device interval in the timer ``device.<name>``, though the span
    itself may have ended long before the program completes.
    """
    if _GATE_GEN != config.generation():
        _refresh_gate()
    if not _GATE_SPAN:
        return NULL_SPAN
    return _Span(name, attrs, device)


def traced(name: Optional[str] = None):
    """Decorator form of :func:`span`: wraps the function body in
    ``span(name or qualname)``."""

    def wrap(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(label):
                return fn(*args, **kwargs)

        return inner

    return wrap


def current_span():
    """The innermost span open on THIS thread (None outside any, or
    with every plane off) — what a thread hop captures at submit so
    the worker can :func:`adopt` it."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


class adopt:
    """Scope that makes ``parent`` — a span captured on ANOTHER thread
    with :func:`current_span` — the enclosing span of this thread's
    work: spans opened inside carry its qualified name as their path,
    and their time is credited to it as child time, so the parent's
    self time excludes the work it handed over and waited for.
    ``credit(seconds)`` adds time spent on the parent's behalf outside
    any span (the scheduler's queue wait). ``None`` = no-op scope."""

    __slots__ = ("_parent", "qualname", "_child_s")

    def __init__(self, parent):
        self._parent = parent
        self.qualname = "" if parent is None else parent.qualname
        self._child_s = 0.0

    def credit(self, seconds: float) -> None:
        self._child_s += float(seconds)

    def __enter__(self):
        if self._parent is not None:
            stack = getattr(_TLS, "stack", None)
            if stack is None:
                stack = _TLS.stack = []
            stack.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._parent is not None:
            stack = getattr(_TLS, "stack", None)
            if stack and stack[-1] is self:
                stack.pop()
            # several workers may settle into one parent at once
            with _LOCK:
                self._parent._child_s += self._child_s
        return False


def span_depth() -> int:
    """Current nesting depth on this thread (test/introspection aid)."""
    stack = getattr(_TLS, "stack", None)
    return len(stack) if stack else 0


def open_spans() -> tuple:
    """The spans open on THIS thread, outermost first; where the work
    crossed a thread hop, the span it was adopted from stands for the
    ``adopt`` scope. What the completion clock keeps of a launch
    (utils/devclock.py): it reads ``name``, ``qualname``, ``t0`` and
    ``device`` of each."""
    stack = getattr(_TLS, "stack", None)
    if not stack:
        return ()
    return tuple(
        s._parent if type(s) is adopt else s for s in stack
    )


def span_stack() -> tuple:
    """Qualified names of the spans open on THIS thread, outermost
    first — the allocation provenance the resident-table leak report
    attaches to each handle."""
    stack = getattr(_TLS, "stack", None)
    return tuple(s.qualname for s in stack) if stack else ()


# ---------------------------------------------------------------------------
# export plane
# ---------------------------------------------------------------------------


# What records into the registry from a thread of its own (the completion
# clock, utils/devclock.py) registers here how to settle — record what it
# still holds, within a bound — and how to start over, so that this
# module imports nothing above it.
_SETTLERS: List[tuple] = []


def register_settler(settle, start_over) -> None:
    _SETTLERS.append((settle, start_over))


def snapshot(settle: bool = True) -> dict:
    """One JSON-able dict of everything measured so far. With ``settle``
    the completion clock is drained first (a bounded wait for the
    launches in flight, outside the lock its thread records under), so
    a window's delta holds every launch of the window; a scrape passes
    False and never waits for the device."""
    if settle:
        for fn, _ in _SETTLERS:
            fn()
    with _LOCK:
        return {
            "counters": dict(_COUNTERS),
            "bytes": dict(_BYTES),
            "timers": {
                k: {
                    "count": int(t[0]),
                    "total_s": float(t[1]),
                    "min_s": float(t[2]),
                    "max_s": float(t[3]),
                }
                for k, t in _TIMERS.items()
            },
            "gauges": {
                k: {"value": g[0], "high_water": g[1]}
                for k, g in _GAUGES.items()
            },
            "histograms": {
                k: {
                    "bounds": list(h["bounds"]),
                    "counts": list(h["counts"]),
                    "count": int(h["count"]),
                    "sum": float(h["sum"]),
                }
                for k, h in _HISTS.items()
            },
            "span_self": {
                k: {"count": int(t[0]), "self_s": float(t[1])}
                for k, t in _SELF.items()
            },
        }


def _prom_name(name: str) -> str:
    """Registry name -> Prometheus metric name: ``srt_`` prefix, dots
    and every other non-[a-zA-Z0-9_] character become underscores."""
    return "srt_" + "".join(
        c if (c.isalnum() or c == "_") else "_" for c in name
    )


def prometheus_text(snap: Optional[dict] = None) -> str:
    """Prometheus text-exposition rendering of the metrics snapshot —
    the serving daemon's ``trace`` command returns this alongside the
    slow-request log so one scrape-shaped payload carries the whole
    registry. Counters/bytes render as ``counter``, gauges as ``gauge``
    (plus a ``_high_water`` series), timers as a summary-shaped
    ``_count``/``_total_seconds`` pair, histograms as a classic
    cumulative ``_bucket{le=...}`` family. A scrape never waits for
    the device: a launch still in flight is in the next one."""
    if snap is None:
        snap = snapshot(settle=False)
    lines: List[str] = []

    def emit(name: str, kind: str, series) -> None:
        lines.append(f"# TYPE {name} {kind}")
        for labels, value in series:
            lines.append(f"{name}{labels} {value}")

    for k in sorted(snap.get("counters", {})):
        emit(_prom_name(k) + "_total", "counter",
             [("", snap["counters"][k])])
    for k in sorted(snap.get("bytes", {})):
        emit(_prom_name(k) + "_bytes_total", "counter",
             [("", snap["bytes"][k])])
    for k in sorted(snap.get("gauges", {})):
        g = snap["gauges"][k]
        emit(_prom_name(k), "gauge", [("", g["value"])])
        emit(_prom_name(k) + "_high_water", "gauge",
             [("", g["high_water"])])
    for k in sorted(snap.get("timers", {})):
        t = snap["timers"][k]
        base = _prom_name(k) + "_seconds"
        emit(base + "_count", "counter", [("", t["count"])])
        emit(base + "_total", "counter", [("", t["total_s"])])
    for k in sorted(snap.get("histograms", {})):
        h = snap["histograms"][k]
        base = _prom_name(k)
        lines.append(f"# TYPE {base} histogram")
        cum = 0
        for bound, n in zip(h["bounds"], h["counts"]):
            cum += n
            lines.append(f'{base}_bucket{{le="{bound}"}} {cum}')
        cum += h["counts"][len(h["bounds"])] if (
            len(h["counts"]) > len(h["bounds"])
        ) else 0
        lines.append(f'{base}_bucket{{le="+Inf"}} {cum}')
        lines.append(f"{base}_count {h['count']}")
        lines.append(f"{base}_sum {h['sum']}")
    for k in sorted(snap.get("span_self", {})):
        s = snap["span_self"][k]
        base = _prom_name(k) + "_self_seconds"
        emit(base + "_count", "counter", [("", s["count"])])
        emit(base + "_total", "counter", [("", s["self_s"])])
    return "\n".join(lines) + "\n" if lines else ""


def reset() -> None:
    """Clear the registry (test isolation; bench per-config blocks).
    The completion clock starts a timeline of its own."""
    for _, fn in _SETTLERS:
        fn()
    with _LOCK:
        _COUNTERS.clear()
        _BYTES.clear()
        _TIMERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _SELF.clear()


def dump(path: Optional[str] = None) -> Optional[str]:
    """Write the snapshot as JSON to ``path`` (default: the
    ``SPARK_RAPIDS_TPU_METRICS_DUMP`` flag). Returns the path written,
    or None when no path is configured. Failures WARN on stderr instead
    of raising — a broken dump path must not take the process down at
    exit."""
    path = path or str(config.get_flag("METRICS_DUMP") or "")
    if not path:
        return None
    try:
        with open(path, "w") as f:
            json.dump(snapshot(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path
    except OSError as e:
        print(
            f"[srt][metrics][WARN] metrics dump to {path!r} failed: {e}",
            file=sys.stderr,
            flush=True,
        )
        return None


def _dump_at_exit() -> None:  # pragma: no cover - exercised via subprocess
    dump()


atexit.register(_dump_at_exit)
