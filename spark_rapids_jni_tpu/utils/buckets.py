"""Shape buckets + the compiled-executable cache — the anti-recompile plane.

Spark executors stream thousands of ``ColumnarBatch``es with ONE schema
but ragged row counts. Row counts are static shape metadata here
(column.py), so under XLA every distinct batch size would recompile
every op in the chain — a recompile storm on the measured hot path
(round-5 put the winning groupby at 0.17% of HBM peak largely on
dispatch/compile overhead). The standard TPU serving fix is applied
centrally in this module:

* **Bucket policy** — a small geometric ladder of row-count buckets
  (default ×2 from a 1024 floor, capped at 2^23 rows), env-tunable via
  ``SPARK_RAPIDS_TPU_BUCKETS``. A ragged stream of N sizes maps onto
  O(log) buckets, so the op plane compiles O(#buckets) executables
  instead of O(N) — the compiled-shape analog of the reference's one
  central two-phase 2 GB batch splitter (row_conversion.cu:505-511).
* **Pad-to-bucket** — ``pad_table`` zero-pads every column buffer to the
  bucket and records the LOGICAL row count on the Table
  (``Table.logical_rows``); op semantics are preserved by validity-aware
  tail masking in the bucketed runners (``bucketed.py``): padded rows are
  dead for filters, sorts, groupbys, joins and distinct via the existing
  ``row_valid`` occupancy machinery of the capped ops.
* **Executable cache** — ``cached_jit`` keys a jitted callable on
  ``(op, schema signature, bucket)``; a hit means the XLA executable is
  reused outright. ``compile_cache.hit``/``compile_cache.miss`` counters,
  the ``bucket.pad_waste_bytes`` counter and per-bucket histograms feed
  the metrics registry.

Debugging: ``SPARK_RAPIDS_TPU_BUCKETS=off`` disables the whole plane —
every dispatch then runs the exact-shape path, which remains the
semantic reference (the bucketed runners fall back to it on any error).
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple

from . import config
from . import devclock
from . import flight
from . import lockcheck
from . import log
from . import metrics
from . import profiler
from . import tracing

# default ladder: 1024, 2048, ... 2^23 (8.4M rows). The cap keeps the
# fused join graphs the bucketed runners build below the TPU worker
# fault threshold (ops/join.py FUSED_PROBE_MAX_ROWS = 16M) and bounds
# pad waste on huge batches; sizes above it dispatch exact.
DEFAULT_FLOOR = 1024
DEFAULT_GROWTH = 2
DEFAULT_CAP = 1 << 23

_OFF_VALUES = frozenset({"off", "none", "false", "disabled", "0"})


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    enabled: bool
    floor: int = DEFAULT_FLOOR
    growth: int = DEFAULT_GROWTH
    cap: int = DEFAULT_CAP
    explicit: Optional[Tuple[int, ...]] = None

    def buckets_upto(self, n: int) -> Tuple[int, ...]:
        """Every bucket the ladder can produce for sizes <= n (test and
        introspection aid; the recompile-regression test sizes its
        compile budget with this)."""
        if not self.enabled:
            return ()
        if self.explicit is not None:
            return tuple(b for b in self.explicit if b <= max(n, self.explicit[0]))
        out = []
        b = self.floor
        while b <= self.cap:
            out.append(b)
            if b >= n:
                break
            b *= self.growth
        return tuple(out)


_OFF = BucketPolicy(enabled=False)


def _parse_spec(raw: str) -> BucketPolicy:
    got = raw.strip().lower()
    if not got:
        return BucketPolicy(enabled=True)
    if got in _OFF_VALUES:
        return _OFF
    try:
        if "," in got:
            sizes = sorted({int(p) for p in got.split(",") if p.strip()})
            if not sizes or sizes[0] <= 0:
                raise ValueError
            return BucketPolicy(
                enabled=True, floor=sizes[0], cap=sizes[-1],
                explicit=tuple(sizes),
            )
        parts = [int(p) for p in got.split(":")]
        if len(parts) == 1:
            floor, growth, cap = parts[0], DEFAULT_GROWTH, DEFAULT_CAP
        elif len(parts) == 2:
            floor, growth, cap = parts[0], parts[1], DEFAULT_CAP
        elif len(parts) == 3:
            floor, growth, cap = parts
        else:
            raise ValueError
        if floor <= 0 or growth < 2 or cap < floor:
            raise ValueError
        return BucketPolicy(enabled=True, floor=floor, growth=growth, cap=cap)
    except ValueError:
        # a typo'd bucket spec must fail loudly, not silently measure /
        # serve with the default ladder under the wrong label
        raise ValueError(
            f"SPARK_RAPIDS_TPU_BUCKETS must be 'floor:growth[:cap]', an "
            f"explicit 'a,b,c' list, or off|none|0 — got {raw!r}"
        ) from None


# policy cache, invalidated by config.generation() (the metrics-gate
# pattern: a dispatch-path check costs an int compare, not an environ
# read per call)
_POLICY: BucketPolicy = _OFF
_POLICY_GEN = -1
_POLICY_LOCK = lockcheck.make_lock("buckets.policy")


def policy() -> BucketPolicy:
    global _POLICY, _POLICY_GEN
    gen = config.generation()
    if _POLICY_GEN != gen:
        with _POLICY_LOCK:
            if _POLICY_GEN != gen:
                _POLICY = _parse_spec(str(config.get_flag("BUCKETS")))
                _POLICY_GEN = gen
    return _POLICY


def enabled() -> bool:
    """True when pad-to-bucket batching is on for the dispatch plane."""
    return policy().enabled


def bucket_for(n: int) -> Optional[int]:
    """Smallest bucket >= ``n``, or None when ``n`` has no bucket
    (bucketing disabled, empty input, or past the ladder cap — those
    dispatch on the exact-shape path)."""
    p = policy()
    if not p.enabled or n <= 0:
        return None
    if p.explicit is not None:
        for b in p.explicit:
            if b >= n:
                return b
        return None
    if n > p.cap:
        return None
    b = p.floor
    while b < n:
        b *= p.growth
    return b if b <= p.cap else None


# ---------------------------------------------------------------------------
# pad / unpad: the Table-level bucket transforms
# ---------------------------------------------------------------------------


def tail_valid(physical: int, n):
    """Row-occupancy mask for a padded buffer: True for the first ``n``
    of ``physical`` rows. ``n`` is a device scalar so one compiled
    executable serves every logical count within a bucket."""
    import jax.numpy as jnp

    return jnp.arange(physical, dtype=jnp.int32) < n


def pad_column(col, target: int):
    """Zero-pad one column's buffers to ``target`` rows (tail validity
    False, tail lengths 0)."""
    import jax.numpy as jnp

    from ..column import Column

    n = col.row_count
    if n == target:
        return col
    if n > target:
        raise ValueError(f"cannot pad {n} rows down to {target}")
    extra = target - n
    data = jnp.concatenate(
        [col.data, jnp.zeros((extra,) + col.data.shape[1:], col.data.dtype)]
    )
    validity = (
        None
        if col.validity is None
        else jnp.concatenate(
            [col.validity, jnp.zeros((extra,), col.validity.dtype)]
        )
    )
    lengths = (
        None
        if col.lengths is None
        else jnp.concatenate(
            [col.lengths, jnp.zeros((extra,), col.lengths.dtype)]
        )
    )
    return Column(data, col.dtype, validity, lengths)


# running pad-waste total for the flight counter track: kept locally so
# the track survives flight-only mode (metrics off => bytes_add no-ops)
# and isn't zeroed by the bench's per-config metrics.reset()
_PAD_WASTE_LOCK = lockcheck.make_lock("buckets.pad_waste")
_PAD_WASTE_TOTAL = 0


def _record_pad_metrics(table, target: int, logical: int) -> None:
    """Pad-waste accounting shared by the device-side ``pad_table`` and
    the host-side wire upload padding (runtime_bridge)."""
    global _PAD_WASTE_TOTAL
    if not (metrics.enabled() or flight.enabled()
            or profiler.session_active()):
        return
    from . import hbm

    extra = target - logical
    if extra > 0 and table.columns:
        # per-row bytes from the logical region (the padded buffers
        # would skew the denominator)
        per_row = -(-hbm.table_bytes(table) // max(table.row_count, 1))
        waste = extra * per_row
        metrics.bytes_add("bucket.pad_waste_bytes", waste)
        profiler.note_pad(extra, waste)
        if flight.enabled():
            # cumulative waste as a flight counter track: the Chrome
            # trace shows WHEN padding cost spiked, not just how much
            with _PAD_WASTE_LOCK:
                _PAD_WASTE_TOTAL += waste
                total = _PAD_WASTE_TOTAL
            flight.record("C", "bucket.pad_waste_bytes", total)
    metrics.counter_add("bucket.pad_tables")
    metrics.hist_observe("bucket.size", target)
    metrics.hist_observe("bucket.pad_rows", max(extra, 0))


def note_padded(table) -> None:
    """Record pad metrics for a table that was padded elsewhere (the
    wire path pads host-side before upload)."""
    if table.logical_rows is not None:
        _record_pad_metrics(table, table.row_count, table.logical_rows)


def pad_table(table, target: Optional[int] = None):
    """Pad every column to ``target`` rows (default: the table's bucket)
    and carry the logical row count on the result. Returns the input
    unchanged when no bucket applies."""
    from ..column import Table

    n = table.logical_row_count
    if target is None:
        target = bucket_for(n)
        if target is None:
            return table
    if table.logical_rows is not None and table.row_count >= target:
        # already padded to a bucket at or above the target (e.g. a
        # capped-filter output kept at its input's bucket): the
        # invariant physical >= bucket >= logical holds — pass through
        # instead of trying to pad DOWN
        return table
    _record_pad_metrics(table, target, n)
    return Table(
        [pad_column(c, target) for c in table.columns],
        table.names,
        logical_rows=n,
    )


def head_table(table, rows: int):
    """The first ``rows`` physical rows of every buffer (device slices);
    names kept, logical-row metadata dropped."""
    from ..column import Column, Table

    cols = [
        Column(
            c.data[:rows],
            c.dtype,
            None if c.validity is None else c.validity[:rows],
            None if c.lengths is None else c.lengths[:rows],
        )
        for c in table.columns
    ]
    return Table(cols, table.names)


def unpad_table(table):
    """Exact-shape view of a possibly padded table (device slice to the
    logical row count; identity for exact tables)."""
    from ..column import Table

    lr = table.logical_rows
    if lr is None:
        return table
    if lr == table.row_count:
        return Table(table.columns, table.names)
    return head_table(table, lr)


def table_signature(table) -> tuple:
    """Cache-key signature of a table: per-column (type id, scale,
    matrix width, validity/lengths presence) plus names — everything
    that changes the traced program besides the bucketed row count."""
    cols = tuple(
        (
            int(c.dtype.id.value),
            int(c.dtype.scale),
            int(c.data.shape[1]) if c.data.ndim > 1 else 0,
            c.validity is not None,
            c.lengths is not None,
        )
        for c in table.columns
    )
    return (cols, table.names)


def cache_key(kind: str, payload, tables, extra: tuple = ()) -> tuple:
    """Canonical compiled-executable cache key: ``(kind, canonical
    payload JSON, per-table schema signatures, per-table physical row
    counts, extra)``. Shared by the per-op bucketed runners (payload =
    one op dict) and the plan compiler (payload = a fused segment's op
    LIST — the plan signature), so every cached executable is keyed the
    same way and each key sees exactly one input shape signature."""
    import json

    return (
        kind,
        json.dumps(payload, sort_keys=True),
        tuple(table_signature(t) for t in tables),
        tuple(t.row_count for t in tables),
        extra,
    )


# ---------------------------------------------------------------------------
# compiled-executable cache
# ---------------------------------------------------------------------------

# LRU of jitted callables keyed on (op, schema signature, bucket). Each
# key sees exactly ONE input shape signature by construction (buckets
# are part of the key), so a cache hit means the XLA executable is
# reused — hit/miss counters are honest compile counters.
CACHE_CAPACITY = 256

_CACHE_LOCK = lockcheck.make_lock("buckets.cache")
_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()


def cached_jit(
    key: tuple, build: Callable[[], Callable], name: str,
    donate_args: tuple = (), scope: Optional[str] = None,
):
    """Jitted callable for ``key``; ``build`` constructs the python fn
    on a miss. ``name`` becomes the callable's __name__ so compile-log
    lines (jax.log_compiles) are attributable to the bucket plane —
    the recompile-regression test greps for it.

    ``scope`` traces the function under ``jax.named_scope(scope)``
    (``"srt." + op``): trace-time only, every HLO op it emits carries
    the name in its ``op_name`` metadata (which is not in the
    compile-cache key), so a device trace can say which plan op a
    fusion belongs to. The scope has to open INSIDE the jitted
    function; one around the call does not reach a jit traced beneath.

    ``donate_args`` (jax ``donate_argnums``) marks positional arguments
    whose buffers the executable may consume IN PLACE — resident chains
    and fused plan segments pass their padded input table here when its
    table id is consumed, so an N-op chain updates HBM instead of
    doubling peak. Donation is part of the executable (XLA aliases
    output to input buffers), so it is folded into the cache key: a
    donated and a non-donated call of the same op compile separately
    and never serve each other. Callers must never reuse a donated
    argument's buffers after the call.

    What comes back is a ``devclock.Launch`` around the jitted callable:
    under ``METRICS`` each call leaves its device interval in
    ``device.<name>`` (utils/devclock.py); ``lower`` and every other
    attribute are the jitted callable's."""
    if donate_args:
        key = key + (("donate", tuple(donate_args)),)
    with _CACHE_LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _CACHE.move_to_end(key)
    if fn is not None:
        metrics.counter_add("compile_cache.hit")
        profiler.note_cache(True)
        return fn
    from . import faults

    faults.inject("compile")
    import jax

    raw = build()
    if scope:
        inner = raw

        def raw(*args):
            with jax.named_scope(scope):
                return inner(*args)

    raw.__name__ = name
    raw.__qualname__ = name
    # every launch of it reports to the completion clock: here, at the
    # one choke point, so that no launch site can forget to
    jfn = devclock.Launch(
        jax.jit(raw, donate_argnums=tuple(donate_args)), name
    )
    with _CACHE_LOCK:
        cur = _CACHE.setdefault(key, jfn)
        won = cur is jfn
        if won:
            while len(_CACHE) > CACHE_CAPACITY:
                _CACHE.popitem(last=False)
        size = len(_CACHE)
    if won:
        metrics.counter_add("compile_cache.miss")
        profiler.note_cache(False)
        metrics.gauge_set("compile_cache.size", size)
        if flight.enabled():
            # a miss on the hot path means an XLA compile is coming —
            # the timeline explains the latency spike right after it
            flight.record("I", "compile_cache.miss", name)
        if log.enabled("DEBUG", "buckets"):
            log.log("DEBUG", "buckets", "compile_cache_miss", name=name,
                    size=size)
        if profiler.session_active() or tracing.context_enabled():
            # jax.jit compiles lazily at the FIRST call: hand this
            # caller (the miss winner — the launch about to pay the
            # compile) a transient wrapper that times that call and
            # attributes it as compile_s to the active segment. The
            # cache keeps the raw jfn, so steady state is untouched.
            # The wrapper also opens the trace-tagged `compile.jit`
            # span, so a traced request shows its compile wall even
            # without an active profile session.
            cur = profiler.time_first_call(cur, name)
    else:
        # another thread built the same key first; use theirs
        metrics.counter_add("compile_cache.hit")
        profiler.note_cache(True)
    return cur


def cache_stats() -> dict:
    with _CACHE_LOCK:
        return {"size": len(_CACHE), "capacity": CACHE_CAPACITY}


def cache_clear() -> None:
    """Drop every cached executable (test isolation)."""
    with _CACHE_LOCK:
        _CACHE.clear()
