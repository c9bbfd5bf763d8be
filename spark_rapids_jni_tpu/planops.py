"""The plan ops: ONE declaration per op, and the one-op dispatcher.

``OPS`` is the only place that knows the list of plan ops. Each entry
(:class:`OpSpec`) says what the op is to every layer that asks:

* ``infer`` — the static inference rule ``plancheck`` walks a plan with
  (``planrules``);
* ``exact(op, table, rest) -> Table`` — the exact-shape path, the
  semantic reference every other path must match byte for byte;
* ``traced(op, t, n, rv) -> (t, n)`` — the ONE traced body of the op:
  a fused segment (``plan._run_segment_traced``), the mesh stage
  (``parallel/planmesh``) and the generic one-op runner
  (``bucketed.run_one_op``) all run it. ``t`` is the padded table,
  ``n`` the device logical count, ``rv`` the occupancy mask;
* ``select(op, t, rv) -> (t, rv)`` — a selecting op's body up to its
  choice of rows: the table it hands on, rows where they are, and the
  occupancy ANDed with its selection. Its ``traced`` compacts by that
  (:func:`_compacting`); a fused segment whose occupancy may stay a
  mask (:func:`deferred_from`) hands it to the next op as it is. A
  ``join`` has this body alone (``select(op, t, rv, build)``): inside a
  segment it can only select, which an inner join on a unique build key
  is, and ``plancheck.predict_segments`` lets no other join in;
* ``fusable`` / ``bucketable`` — may the op ride a fused segment, has
  it a one-op bucketed program (a constant, or a predicate over the op
  for the cases its JSON decides);
* ``row_local`` — its output over a row range depends only on those
  rows (the OOM half-batch split and the mesh chains may chunk it);
  ``exchange`` — it is a mesh exchange boundary;
  ``behind_exchange(op, part, schema, names) -> (keys, None) |
  (None, reason)`` — may the op run a device at a time directly behind
  that exchange on the mesh, the union of the devices' results being
  its result over the whole table, and where among its output columns
  the exchange's keys then lie (``planrules.groupby_behind_exchange``:
  a groupby whose keys hold the exchange's). Every other op behind or
  in front of an exchange there has to be ``row_local``;
* ``counts`` — the traced body changes the row count, so the one-op
  program returns the new count and the runner reads it;
  ``program`` — the name the one-op runner compiles under;
* ``runner`` — for ``join`` and ``groupby``, whose one-op runners are
  two launches with a host read between: their own runner.

Adding an op is one entry here, its ``ops/`` code and its tests.

Below the table sits the one-op dispatcher (:func:`dispatch`: kernel
tier -> bucketed -> exact). Imports point one way: ``ops``, ``rows``,
``kernels``, ``bucketed``, ``planrules`` <- this module <- ``plancheck``
<- ``plan`` <- ``runtime_bridge`` <- ``serving``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from . import bucketed, dtype as dt, ops, planrules as rules, rows as rows_mod
from .column import Column, Table
from .kernels import registry as kernel_registry
from .ops import join as join_mod
from .ops import partition as partition_mod
from .ops import regex as regex_mod
from .ops import strings as strings_mod
from .ops.compaction import distinct_capped
from .ops.filter import filter_table_capped, selection_mask
from .ops.groupby import _COLLECT_OPS, GroupbyAgg, groupby_sort
from .ops.project import project_table
from .utils import buckets, faults, lockcheck, metrics


@dataclasses.dataclass(frozen=True)
class OpSpec:
    infer: Callable
    exact: Callable
    traced: Optional[Callable] = None
    select: Optional[Callable] = None
    fusable: Union[bool, Callable[[dict], bool]] = False
    bucketable: Union[bool, Callable[[dict], bool]] = False
    row_local: bool = False
    exchange: bool = False
    behind_exchange: Optional[Callable] = None
    counts: bool = False
    program: Optional[str] = None
    runner: Optional[Callable] = None


# ---------------------------------------------------------------------------
# what an op's JSON says, parsed once for every path
# ---------------------------------------------------------------------------


def _cast_target(op: dict) -> dt.DType:
    return dt.DType(dt.TypeId(op["type_id"]), op.get("scale", 0))


def _cast_column(src: Column, target: dt.DType) -> Column:
    if src.dtype.is_string or target.is_string:
        return strings_mod.cast(src, target)
    return ops.cast(src, target)


def _sort_keys(op: dict) -> list:
    return [
        ops.SortKey(k["column"], ascending=k.get("ascending", True))
        for k in op["keys"]
    ]


# ---------------------------------------------------------------------------
# exact: (op, table, rest) -> Table at the exact shape
# ---------------------------------------------------------------------------

_JOINS = {
    "inner": ops.inner_join,
    "left": ops.left_join,
    "right": ops.right_join,
    "full": ops.full_join,
    "semi": ops.semi_join,
    "anti": ops.anti_join,
}


def _x_join(op, table, rest):
    how = op.get("how", "inner")
    fn = _JOINS.get(how)
    if fn is None:
        raise ValueError(f"unknown join how={how!r}")
    if not rest:
        raise ValueError("join needs two input tables")
    return fn(table, rest[0], op["on"])


def _x_concat(op, table, rest):
    return ops.concatenate([table, *rest])


def _x_groupby(op, table, rest):
    aggs = [GroupbyAgg(a["column"], a["agg"]) for a in op["aggs"]]
    return ops.groupby_aggregate(table, op["by"], aggs)


def _x_sort_by(op, table, rest):
    return ops.sort_table(table, _sort_keys(op))


def _x_filter(op, table, rest):
    mask_idx = op["mask"]
    keep = [c for i, c in enumerate(table.columns) if i != mask_idx]
    return ops.filter_table(Table(keep), table.columns[mask_idx])


def _x_distinct(op, table, rest):
    return ops.distinct(table, op.get("keys"))


def _x_cast(op, table, rest):
    out = list(table.columns)
    out[op["column"]] = _cast_column(
        table.columns[op["column"]], _cast_target(op)
    )
    return Table(out, table.names)


def _x_project(op, table, rest):
    # Spark's ProjectExec: one output column per expression tree
    # (ops/project.py has the grammar); counted at launch, here as in
    # note_launched for the traced programs
    out = project_table(table, op["exprs"])
    metrics.counter_add("project.calls")
    return out


def _x_explode(op, table, rest):
    return ops.explode(table, op["column"])


def _x_rlike(op, table, rest):
    # filter rows whose string column matches the pattern (the Spark
    # `WHERE col RLIKE pat` scan shape)
    mask = regex_mod.contains_re(table.columns[op["column"]], op["pattern"])
    return ops.filter_table(table, mask)


def _x_cross_join(op, table, rest):
    if not rest:
        raise ValueError("cross_join needs two input tables")
    return ops.cross_join(table, rest[0])


def _x_slice(op, table, rest):
    n = table.row_count
    start = int(op.get("start", 0))
    stop = int(op.get("stop", n))
    if start < 0 or stop < 0:
        raise ValueError(
            f"slice: negative bounds not supported (start={start}, "
            f"stop={stop})"
        )
    start = min(start, n)
    stop = max(start, min(stop, n))
    return ops.slice_rows(table, start, stop)


def _x_repeat(op, table, rest):
    return ops.repeat(table, int(op["count"]))


def _x_sample(op, table, rest):
    return ops.sample(
        table, int(op["n"]), seed=int(op.get("seed", 0)),
        replacement=bool(op.get("replacement", False)),
    )


def _x_partition(op, table, rest):
    # Spark's ShuffleExchangeExec partitioning step as a table op: rows
    # reordered partition-contiguously by Pmod(Murmur3, num) (hash) or
    # sampled key-range splitters (range). The exchange itself is the
    # mesh path's job (planmesh); on the exact path the stable reorder
    # IS the observable result, which is what the mesh path must match
    # byte-for-byte after its all-to-all.
    kind = op.get("kind", "hash")
    num = int(op["num"])
    if num < 1:
        raise ValueError(f"partition: num must be >= 1, got {num}")
    keys = list(op.get("keys", []))
    if kind == "hash":
        out, _ = partition_mod.hash_partition(table, keys or None, num)
    elif kind == "range":
        if not keys:
            raise ValueError("partition: range kind needs keys")
        out, _ = partition_mod.range_partition(table, keys, num)
    else:
        raise ValueError(f"unknown partition kind {kind!r}")
    if metrics.enabled():
        metrics.counter_add("partition.exact")
    return out


def _x_to_rows(op, table, rest):
    # device row transpose; result = a true LIST<UINT8> column (the
    # reference's output type, row_conversion.cu:389-406)
    return Table([rows_mod.to_rows_list(table)])


def _x_from_rows(op, table, rest):
    schema = [
        dt.DType(dt.TypeId(t), s)
        for t, s in zip(op["type_ids"], op["scales"])
    ]
    src = table.columns[0]
    if src.dtype.id == dt.TypeId.LIST:
        return rows_mod.from_rows_list(src, schema)
    # legacy flat-UINT8 input: one column of num_rows*row_size bytes
    layout = rows_mod.compute_fixed_width_layout(schema)
    n = int(op["num_rows"])
    raw = np.asarray(src.data).reshape(n, layout.row_size)
    pr = rows_mod.PackedRows(jnp.asarray(raw), layout)
    return rows_mod.from_rows(pr, schema)


# ---------------------------------------------------------------------------
# traced: (op, padded table, device logical count, row_valid occupancy)
# -> (table at the same physical shape, new device count), INSIDE a
# traced program. Where the count flows, the occupancy mask is
# recomputed per step from it, so a filter's clone-padded tail is dead
# for everything downstream; where a fused segment lets the occupancy
# flow as a mask (deferred_from), rv is that mask and n is not read.
# ---------------------------------------------------------------------------


def _gated(mask: Column, rv) -> Column:
    """The occupancy gate: padding tails can hold arbitrary garbage
    (an upstream capped filter clones kept rows; padding strings are
    zero-length, which a pattern may match), so a selection is ANDed
    with the occupancy mask explicitly."""
    return Column(jnp.logical_and(mask.data, rv), mask.dtype, mask.validity)


def _t_cast(op, t, n, rv):
    ci = int(op["column"])
    cols = list(t.columns)
    cols[ci] = _cast_column(t.columns[ci], _cast_target(op))
    return Table(cols, t.names), n


def _t_project(op, t, n, rv):
    # elementwise: what it computes over the padding tail stays behind
    # the flowing count, like a cast's
    return project_table(t, op["exprs"]), n


def _s_filter(op, t, rv):
    mi = int(op["mask"])
    kept = Table(
        [c for i, c in enumerate(t.columns) if i != mi]
    )  # names dropped exactly like the exact path
    return kept, selection_mask(_gated(t.columns[mi], rv))


def _s_rlike(op, t, rv):
    mask = regex_mod.contains_re(t.columns[int(op["column"])], op["pattern"])
    return t, selection_mask(_gated(mask, rv))


def _s_join(op, t, rv, build):
    # an inner join whose build key repeats no value and is addressed
    # directly (bucketed.selecting_table_size read it from ``build``'s
    # table): each probe row has at most one build row, so the join is
    # a selection plus a row-local lookup and moves no row
    r, rn, table_size = build
    on = list(op["on"])
    if not join_mod.direct_key(
        [t.column(c) for c in on], [r.column(c) for c in on]
    ):
        # the build side's key was read; the probe side's is known only
        # here, where the segment is traced: the per-op path owns it
        raise bucketed._Decline
    matched, out = join_mod.lookup_unique(
        t, r, on, on, rv, buckets.tail_valid(r.row_count, rn), table_size
    )
    return out, jnp.logical_and(rv, matched)


def _compacting(select):
    """The traced body of a selecting op: its selection, then the
    stable compaction that turns the occupancy back into a prefix."""

    def traced(op, t, n, rv):
        kept, keep = select(op, t, rv)
        return filter_table_capped(
            kept, Column(keep, dt.BOOL8, None), capacity=t.row_count
        )

    return traced


def _t_distinct(op, t, n, rv):
    return distinct_capped(
        t, op.get("keys"), capacity=t.row_count, row_valid=rv
    )


def _t_sort_by(op, t, n, rv):
    return ops.sort_table(t, _sort_keys(op), row_valid=rv), n


def _t_slice(op, t, n, rv):
    # exact-path semantics (start/stop clamped to the LOGICAL count)
    # expressed against the device scalar: keep rows [s, e) of the
    # first n, compacted to the front at the same physical shape.
    # Host-side clamp to the physical row count first: n <= row_count,
    # so the clamp is semantics-free and keeps a giant (>= 2^31) but
    # valid bound from overflowing the int32 conversion
    cap = t.row_count
    s = jnp.minimum(jnp.int32(min(int(op.get("start", 0)), cap)), n)
    stop = op.get("stop")
    e = (
        n
        if stop is None
        else jnp.minimum(jnp.int32(min(int(stop), cap)), n)
    )
    e = jnp.maximum(s, e)
    iota = jnp.arange(t.row_count, dtype=jnp.int32)
    keep = jnp.logical_and(iota >= s, iota < e)
    return filter_table_capped(
        t, Column(keep, dt.BOOL8, None), capacity=t.row_count
    )


def _t_groupby(op, t, n, rv):
    # the sort half only -> (sorted state, group count): the launcher
    # runs the per-group half once it has read the count
    # (bucketed._reduce_groups)
    return groupby_sort(
        t, list(op["by"]), bucketed._groupby_aggs(op), row_valid=rv
    )


# ---------------------------------------------------------------------------
# the cases an op's JSON decides
# ---------------------------------------------------------------------------


def _static_slice(op: dict) -> bool:
    # negative bounds raise in the exact path; keep that error surfacing
    # there, not from inside a traced segment
    try:
        start = int(op.get("start", 0))
        stop = op.get("stop")
        return start >= 0 and (stop is None or int(stop) >= 0)
    except (TypeError, ValueError):
        return False


def _no_collect(op: dict) -> bool:
    # collect_* needs a data-dependent list-capacity pre-pass the exact
    # path owns: such a groupby neither fuses nor pays a padded upload
    return not any(
        a.get("agg") in _COLLECT_OPS
        for a in op.get("aggs", ())
        if isinstance(a, dict)
    )


def _bucketed_how(op: dict) -> bool:
    return op.get("how", "inner") in bucketed.JOIN_HOWS


# ---------------------------------------------------------------------------
# the table. A groupby is fusable tail-only: it closes the run it ends
# (plancheck.predict_segments). slice has a traced body for fused
# segments and NO one-op program.
# ---------------------------------------------------------------------------

OPS: Dict[str, OpSpec] = {
    "cast": OpSpec(
        rules._r_cast, _x_cast, _t_cast, fusable=True, bucketable=True,
        row_local=True, program="srt_bucketed_cast",
    ),
    "project": OpSpec(
        rules._r_project, _x_project, _t_project, fusable=True,
        bucketable=True, row_local=True, program="srt_bucketed_project",
    ),
    "filter": OpSpec(
        rules._r_filter, _x_filter, _compacting(_s_filter), _s_filter,
        fusable=True, bucketable=True, row_local=True, counts=True,
        program="srt_bucketed_filter",
    ),
    "rlike": OpSpec(
        rules._r_rlike, _x_rlike, _compacting(_s_rlike), _s_rlike,
        fusable=True, bucketable=True, row_local=True, counts=True,
        program="srt_bucketed_rlike",
    ),
    "distinct": OpSpec(
        rules._r_distinct, _x_distinct, _t_distinct, fusable=True,
        bucketable=True, counts=True, program="srt_bucketed_distinct",
    ),
    "sort_by": OpSpec(
        rules._r_sort_by, _x_sort_by, _t_sort_by, fusable=True,
        bucketable=True, program="srt_bucketed_sort",
    ),
    "slice": OpSpec(
        rules._r_slice, _x_slice, _t_slice, fusable=_static_slice,
        counts=True,
    ),
    "groupby": OpSpec(
        rules._r_groupby, _x_groupby, _t_groupby, fusable=_no_collect,
        bucketable=_no_collect, runner=bucketed._r_groupby,
        behind_exchange=rules.groupby_behind_exchange,
    ),
    "join": OpSpec(
        rules._r_join, _x_join, select=_s_join, bucketable=_bucketed_how,
        runner=bucketed._r_join,
    ),
    "partition": OpSpec(rules._r_partition, _x_partition, exchange=True),
    "concat": OpSpec(rules._r_concat, _x_concat),
    "cross_join": OpSpec(rules._r_cross_join, _x_cross_join),
    "explode": OpSpec(rules._r_explode, _x_explode),
    "repeat": OpSpec(rules._r_repeat, _x_repeat),
    "sample": OpSpec(rules._r_sample, _x_sample),
    "to_rows": OpSpec(rules._r_to_rows, _x_to_rows),
    "from_rows": OpSpec(rules._r_from_rows, _x_from_rows),
}


def _spec_says(op, field: str) -> bool:
    spec = OPS.get(op.get("op")) if isinstance(op, dict) else None
    if spec is None:
        return False  # malformed entries fail loudly in run_plan
    flag = getattr(spec, field)
    return flag(op) if callable(flag) else flag


def op_fusable(op) -> bool:
    """Could this op ride inside a fused segment?"""
    return _spec_says(op, "fusable")


def op_bucketable(op) -> bool:
    """Could this op take the one-op bucketed path at all? The wire
    layer uses it to skip host-side padding (and the extra upload bytes
    it costs) for ops that would immediately unpad."""
    return _spec_says(op, "bucketable")


def deferred_from(seg_ops: Sequence[dict]) -> int:
    """The index from which a traced segment's occupancy may flow as a
    MASK instead of a prefix: a selecting op at or behind it keeps its
    rows where they are and ANDs its selection into the occupancy
    (``plan._run_segment_traced``). That holds when every op from there
    to the tail is row-local (none reads the count or looks across
    rows) or a join — inside a segment a join only selects among its
    probe rows (:func:`_s_join`), though it is not ``row_local``: it
    reads a second table, which no chunked or sharded chain has — and
    the tail is a groupby, whose sort puts ANY mask's rows last
    (``ops.groupby._key_words``). ``len(seg_ops)`` where nothing may
    defer: the segment's result then needs the prefix."""
    last = len(seg_ops) - 1
    if last < 0 or seg_ops[last]["op"] != "groupby":
        return len(seg_ops)
    i = last
    while i > 0 and keeps_rows(seg_ops[i - 1]):
        i -= 1
    return i


def keeps_rows(op: dict) -> bool:
    """May a segment's occupancy flow through this op as a mask?"""
    return OPS[op["op"]].row_local or op["op"] == "join"


def note_launched(seg_ops: Sequence[dict]) -> None:
    """The counters of a traced program that has just been launched —
    one op alone, a fused segment, a mesh stage: ``project.calls`` for
    its ``project`` ops, for each selecting op ``filter.deferred`` or
    ``filter.compacted``, the way its occupancy went, and for each join
    ``join.deferred`` (inside a segment: it selected, by the direct
    probe) or ``join.materialised`` (its own runner moved the rows).
    Counted on the host at launch, not at trace (the executable is
    cached), so the counters say what the daemon evaluated."""
    k = sum(1 for o in seg_ops if o.get("op") == "project")
    if k:
        metrics.counter_add("project.calls", k)
    start = deferred_from(seg_ops)
    for i, o in enumerate(seg_ops):
        if o["op"] == "join":
            if len(seg_ops) > 1:
                metrics.counter_add("join.deferred")
                metrics.counter_add("join.probe.direct")
            else:
                metrics.counter_add("join.materialised")
        elif OPS[o["op"]].select is not None:
            metrics.counter_add(
                "filter.deferred" if i >= start else "filter.compacted"
            )


# ---------------------------------------------------------------------------
# the one-op dispatcher
# ---------------------------------------------------------------------------


def dispatch(op: dict, table: Table, rest: Sequence[Table] = ()) -> Table:
    """Run one op on device; returns the result Table.

    ``rest`` carries additional input tables for multi-table ops
    (``join`` takes the probe side as ``table`` and the build side as
    ``rest[0]``; ``concat`` appends every table in ``rest``).

    With shape bucketing on (the default; ``SPARK_RAPIDS_TPU_BUCKETS``),
    ops with a one-op runner go through ``bucketed.dispatch_bucketed``:
    inputs padded to row-count buckets, one compiled executable per
    ``(op, schema, bucket)`` from the central cache, results padded with
    ``Table.logical_rows`` carrying the real count. Other ops (and the
    ``=off`` debug mode) take the exact-shape path — padded inputs are
    unpadded first so exact ops never see garbage tails.

    Every op runs inside a ``metrics.span`` and feeds the per-op
    call/row counters — the ``GpuMetric`` plane of the dispatch layer.
    The disabled path costs one string concat and the span's cheap
    gate checks. Row counters count LOGICAL rows (padding is an
    implementation detail; its cost shows up in ``bucket.*`` instead).

    This is also a fault boundary (utils/faults.py): the ``dispatch``
    injection site is armed here, transient-classified failures retry
    with backoff (safe: nothing on this path donates its inputs — the
    consumed single-op flavor is ``plan.run_donated``, gated by its
    caller), and permanent-classified errors surface unchanged.
    """
    name = op["op"]

    def attempt():
        faults.inject("dispatch")
        return _dispatch_once(op, table, rest, name)

    return faults.run_with_retry(attempt, "dispatch." + name)


def _one_op_runner(spec: Optional[OpSpec], name: str):
    """The op's one-op bucketed runner: its own, else the generic one
    over its traced body; None where it has no one-op program."""
    if spec is None or spec.bucketable is False:
        return None
    if spec.runner is not None:
        return spec.runner

    def runner(op, table, rest):
        return bucketed.run_one_op(
            op, table, name, spec.traced, spec.program, spec.counts
        )

    return runner


def _dispatch_once(
    op: dict, table: Table, rest: Sequence[Table], name: str
) -> Table:
    # a tracked lock held across a device launch serializes every other
    # dispatcher behind the chip — the lockcheck shim reports it
    lockcheck.note_blocking("device_dispatch")
    spec = OPS.get(name)
    with metrics.span("dispatch." + name):
        # the kernel tier (kernels/registry.py) is consulted FIRST:
        # hand-written Pallas runners under SPARK_RAPIDS_TPU_KERNELS,
        # byte-identical over the logical rows, declining/falling back
        # to the bucketed/exact chain below. The flag-off path is one
        # generation check (<5 µs contract, test_kernel_tier.py).
        out = kernel_registry.dispatch_kernel(op, table, rest, name)
        runner = _one_op_runner(spec, name) if buckets.enabled() else None
        if out is None and runner is not None:
            out = bucketed.dispatch_bucketed(runner, op, table, rest, name)
            if out is not None:
                note_launched((op,))
        if out is None:
            if spec is None:
                raise ValueError(f"unknown table op {name!r}")
            out = spec.exact(
                op,
                buckets.unpad_table(table),
                [buckets.unpad_table(t) for t in rest],
            )
    if metrics.enabled():
        rows_in = int(table.logical_row_count) + sum(
            int(t.logical_row_count) for t in rest
        )
        metrics.counter_add("op." + name + ".calls")
        metrics.counter_add("op." + name + ".rows_in", rows_in)
        metrics.counter_add(
            "op." + name + ".rows_out", int(out.logical_row_count)
        )
        metrics.hist_observe("dispatch.rows_in", rows_in)
    return out
