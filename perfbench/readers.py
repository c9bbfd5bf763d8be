"""Reader kinds of the per-layer metrics.

A metric is a file ``layer_metrics/<name>.json`` naming one of these
kinds (or ``plugins/reader_<kind>.py``) and what to read. A reader that
finds nothing to read returns None and the metric is left out of the
line. ``ctx`` is what one run gathered: counter and timer deltas over
the window, the server's ``stats``, bench-side clocks, memory stats, the
reduced device trace (``--trace 1`` on a chip only) and the peaks of the
device kind.
"""

from __future__ import annotations

import re

from . import counts, plugins


def _counter_delta(spec, ctx):
    return float(sum(ctx["counters"].get(n, 0) for n in spec["counters"]))


def _timer_mean_ms(spec, ctx):
    t = ctx["timers"].get(spec["timer"])
    if not t or not ctx["requests"]:
        return None
    return 1e3 * t["total_s"] / ctx["requests"]


def _stats_percentile(spec, ctx):
    vals = [s[spec["field"]][spec["key"]] for s in ctx["stats"].get("sessions", [])
            if spec["field"] in s]
    return float(max(vals)) if vals else None


def _clock_mean_ms(spec, ctx):
    if not ctx["requests"]:
        return None
    return 1e3 * ctx["clocks"][spec["clock"]] / ctx["requests"]


def _memory_stat(spec, ctx):
    v = ctx["memory"].get(spec["stat"])
    return None if v is None else float(v) * float(spec.get("scale", 1.0))


def _op_seconds(trace, pattern):
    """Per device: seconds of the ops whose printed name matches."""
    rx = re.compile(pattern)
    return [sum(s for n, s in d["ops"].items() if rx.search(n))
            for d in trace["devices"]]


def _trace_time_share(spec, ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    busy = sum(d["busy_s"] for d in trace["devices"])
    if busy <= 0:
        return None
    return 100.0 * sum(_op_seconds(trace, spec["ops"])) / busy


def _trace_time_ms(spec, ctx):
    trace = ctx["trace"]
    if not trace or not trace["requests"]:
        return None
    secs = _op_seconds(trace, spec["ops"])
    if not secs or max(secs) <= 0:
        return None
    return 1e3 * max(secs) / trace["requests"]


def _trace_roofline_share(spec, ctx):
    trace = ctx["trace"]
    if not trace or not trace["requests"]:
        return None
    secs = max(_op_seconds(trace, spec["ops"]), default=0.0)
    if secs <= 0:
        return None
    moved = counts.find(spec["count"])(ctx["config"], ctx["traffic"], ctx["rows_in"])
    least_s = moved * trace["requests"] / (ctx["peaks"][spec["peak"]] * 1e9)
    return 100.0 * least_s / secs


READERS = {
    "counter_delta": _counter_delta, "timer_mean_ms": _timer_mean_ms,
    "stats_percentile": _stats_percentile, "clock_mean_ms": _clock_mean_ms,
    "memory_stat": _memory_stat, "trace_time_share": _trace_time_share,
    "trace_time_ms": _trace_time_ms,
    "trace_roofline_share": _trace_roofline_share,
}


def read(spec: dict, ctx: dict):
    kind = spec["reader"]
    fn = READERS.get(kind) or plugins.find("reader", kind, "read")
    return fn(spec, ctx)
