"""HBM footprint planning — the RMM-pool role, TPU-shaped.

The reference leans on RMM pools, streams and allocator statistics
(row_conversion.hpp:30-31; RMM_LOGGING_LEVEL, reference pom.xml:82) to
keep kernels inside device memory. Under XLA the allocator belongs to
the runtime and the PJRT client exposes no live pool state, so
this module plans ANTE-HOC instead: conservative per-op byte estimates
against a configurable per-chip budget, used to size batch/chunk
parameters so the batched/capped APIs never assemble a resident set
past the chip (round-3's 32M-join worker crash was discovered by
crashing; round-4 VERDICT item 7 asks for it to be planned for).

Budget plane: ``SPARK_RAPIDS_TPU_HBM_BUDGET_GB`` (utils/config.py) —
default 16 GiB (v5e per chip) scaled by a fixed reserve fraction that
covers XLA's own workspace, fusion temporaries and the framework's
transient double-buffering, which the estimates below deliberately do
not enumerate.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional, Sequence

import numpy as np

from . import config
from . import flight
from . import lockcheck
from . import log
from . import metrics
from . import profiler

GIB = 1 << 30

# fraction of the budget left to XLA workspace/temporaries; estimates
# here count steady-state buffers only
RESERVE_FRACTION = 0.35

# CPU: pretend a v5e so planning behaves identically under the test
# suite's forced-CPU backend (shapes, not host RAM, are what the plans
# must exercise)
_CPU_PRETEND_HBM_GB = 16.0


@functools.lru_cache(maxsize=1)
def _tpu_bytes_limit() -> float:
    """The runtime's own limit for the default device, asked once per
    process: spill.note_put reads the budget under its registry lock on
    every put, and the limit does not change while the process lives."""
    import jax

    return float(jax.devices()[0].memory_stats()["bytes_limit"])


def backend_hbm_gb(platform: Optional[str] = None) -> float:
    """Device memory in GiB: the limit the runtime reports on TPU, the
    pretend-v5e figure on CPU; any other device is an error, not a
    default."""
    import jax

    dev = jax.devices()[0]
    platform = platform or dev.platform
    if platform == "cpu":
        return _CPU_PRETEND_HBM_GB
    if platform == "tpu" and dev.platform == "tpu":
        return _tpu_bytes_limit() / GIB
    raise ValueError(
        f"no HBM size known for platform {platform!r} (default device: "
        f"{dev.platform} {dev.device_kind!r}); set "
        "SPARK_RAPIDS_TPU_HBM_BUDGET_GB"
    )


def budget_bytes(platform: Optional[str] = None) -> int:
    """Usable device bytes for steady-state buffers."""
    gb = config.get_flag("HBM_BUDGET_GB")
    if not gb:
        gb = backend_hbm_gb(platform)
    return int(float(gb) * GIB * (1.0 - RESERVE_FRACTION))


def column_bytes(col) -> int:
    """Resident bytes of one device column (data + validity + lengths)."""
    total = col.data.size * col.data.dtype.itemsize
    if col.validity is not None:
        total += col.validity.size * col.validity.dtype.itemsize
    if col.lengths is not None:
        total += col.lengths.size * col.lengths.dtype.itemsize
    return int(total)


def table_bytes(table) -> int:
    return sum(column_bytes(c) for c in table.columns)


def row_bytes(table) -> int:
    """Per-row resident bytes (ceil) — sizing unit for join output."""
    n = max(table.row_count, 1)
    return -(-table_bytes(table) // n)


def key_word_count(cols: Sequence) -> int:
    """u64 order words per row for a key column list (ops/keys.py):
    strings cost pad/8 + 1 words, DECIMAL128 two, the rest one, plus a
    validity word per nullable column."""
    words = 0
    for c in cols:
        if c.dtype.is_string:
            words += c.data.shape[1] // 8 + 1
        elif getattr(c.dtype, "id", None) is not None and c.data.ndim == 2:
            words += c.data.shape[1]
        else:
            words += 1
        if c.validity is not None:
            words += 1
    return words


# cumulative donated bytes for the flight counter track (the
# bucket.pad_waste_bytes discipline: kept locally so the track survives
# flight-only mode and per-config metrics resets)
_DONATED_LOCK = lockcheck.make_lock("hbm.donated")
_DONATED_TOTAL = 0

# Donation listeners: the serving tier registers one so a tenant whose
# plan donated its buffers gets the bytes credited back against its
# per-session budget (serving/session.py). Listeners must be cheap and
# must not raise — they run on the hot donate path, unconditionally
# (budget credits can't depend on a telemetry flag).
_DONATION_LISTENERS: list = []


def register_donation_listener(fn) -> None:
    """Register ``fn(nbytes)`` to observe every buffer donation."""
    if fn not in _DONATION_LISTENERS:
        _DONATION_LISTENERS.append(fn)


def note_donation(nbytes: int) -> None:
    """Record one buffer donation: ``nbytes`` of input HBM the chained
    executable updated IN PLACE instead of allocating fresh output
    buffers next to. The plan-vs-budget picture reads this as peak
    relief — a fused chain that donates never holds input + output of
    a segment simultaneously, so the steady-state estimates above are
    conservative by exactly the donated volume."""
    global _DONATED_TOTAL
    profiler.note_donation(int(nbytes))
    for fn in tuple(_DONATION_LISTENERS):
        fn(int(nbytes))
    if not (metrics.enabled() or flight.enabled()):
        return
    metrics.counter_add("hbm.donations")
    metrics.bytes_add("hbm.donated_bytes", int(nbytes))
    if flight.enabled():
        # cumulative donated bytes as a counter track: the Chrome trace
        # shows WHEN in-place chaining kicked in alongside resident.live
        with _DONATED_LOCK:
            _DONATED_TOTAL += int(nbytes)
            total = _DONATED_TOTAL
        flight.record("C", "hbm.donated_bytes", total)


# Pressure listeners: the spill tier (utils/spill.py) registers one so
# a plan that does NOT fit the budget frees the deficit (coldest
# resident tables demote to host/disk) BEFORE the launch OOMs. Fired
# unconditionally — eviction can't depend on a telemetry flag — with
# the byte deficit; listeners gate themselves and must not raise.
_PRESSURE_LISTENERS: list = []


def register_pressure_listener(fn) -> None:
    """Register ``fn(deficit_bytes)`` to observe every over-budget plan."""
    if fn not in _PRESSURE_LISTENERS:
        _PRESSURE_LISTENERS.append(fn)


def _record_plan(kind: str, plan: dict, planned_bytes: int) -> None:
    """Plan-vs-budget decisions on the metrics plane: how many plans ran,
    how many bytes they committed, and how often a shape failed to fit
    (the spill/chunk trigger)."""
    if not plan["fits"]:
        deficit = max(planned_bytes - plan["budget_bytes"], 1)
        for fn in tuple(_PRESSURE_LISTENERS):
            fn(deficit)
    if not metrics.enabled():
        return
    metrics.counter_add("hbm.plan." + kind)
    metrics.bytes_add("hbm.planned_bytes", planned_bytes)
    metrics.gauge_set("hbm.budget_bytes", plan["budget_bytes"])
    if not plan["fits"]:
        metrics.counter_add("hbm.plan_over_budget")


def join_plan(
    left,
    right,
    left_on: Sequence,
    right_on: Sequence,
    platform: Optional[str] = None,
) -> dict:
    """Steady-state byte plan of a batched join: what is resident
    across one probe-chunk iteration, and the probe_rows that fits.

    Resident set per iteration (ops/join.py inner_join_batched):
      inputs        both tables
      build         sorted key words (W_r + 1 occupancy) * 8 B * m
                    + the permutation (4 B * m)
      probe chunk   chunk slice of left + lo/counts/lvalid (9 B/row)
      output        capacity * output row bytes (pow2 of the chunk's
                    matches; planned at 1x expansion and ENFORCED at
                    run time by re-splitting oversized chunks, since
                    fan-out is data-dependent)
    """
    lcols = [left.column(c) for c in left_on]
    rcols = [right.column(c) for c in right_on]
    m = right.row_count
    budget = budget_bytes(platform)
    fixed = (
        table_bytes(left)
        + table_bytes(right)
        + (key_word_count(rcols) + 1) * 8 * m
        + 4 * m
    )
    out_row = row_bytes(left) + row_bytes(right)
    per_probe_row = (
        row_bytes(left)            # the chunk slice
        + 9                        # lo (4) + counts (4) + lvalid (1)
        + 2 * out_row              # pow2 capacity overshoot at 1x fan-out
    )
    avail = budget - fixed
    probe_rows = max(1024, avail // max(per_probe_row, 1))
    plan = {
        "budget_bytes": budget,
        "fixed_bytes": int(fixed),
        "per_probe_row_bytes": int(per_probe_row),
        "output_row_bytes": int(out_row),
        "probe_rows": int(probe_rows),
        "fits": avail > 0,
    }
    log.log("INFO", "hbm", "join_plan", **plan)
    _record_plan("join", plan, int(fixed))
    if metrics.enabled() and probe_rows < left.row_count:
        # the plan decided the probe side must be chunked
        metrics.counter_add("hbm.join_chunk_decisions")
    return plan


def sort_plan(table, n_key_words: int, platform: Optional[str] = None) -> dict:
    """Variadic payload sort: operands (keys + iota + every 1-D buffer)
    live twice (input + output) during the sort."""
    n = table.row_count
    operand = n_key_words * 8 * n + 4 * n + table_bytes(table)
    total = 2 * operand
    plan = {
        "budget_bytes": budget_bytes(platform),
        "total_bytes": int(total),
        "fits": total <= budget_bytes(platform),
    }
    log.log("INFO", "hbm", "sort_plan", rows=n, **plan)
    _record_plan("sort", plan, int(total))
    return plan


def groupby_plan(
    table,
    by: Sequence,
    num_segments: int,
    platform: Optional[str] = None,
) -> dict:
    """Single-pass capped groupby: the variadic sort (keys + payload,
    doubled) plus the num_segments-sized output/bounds."""
    key_cols = [table.column(c) for c in by]
    n = table.row_count
    words = key_word_count(key_cols) + 1  # + occupancy/iota word
    sort_bytes = 2 * (words * 8 * n + 4 * n + table_bytes(table))
    seg_bytes = num_segments * (8 + 2 * 4) + num_segments * row_bytes(table)
    total = sort_bytes + seg_bytes
    plan = {
        "budget_bytes": budget_bytes(platform),
        "total_bytes": int(total),
        "fits": total <= budget_bytes(platform),
    }
    log.log("INFO", "hbm", "groupby_plan", rows=n, segments=num_segments,
            **plan)
    _record_plan("groupby", plan, int(total))
    return plan
