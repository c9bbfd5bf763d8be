"""Bucketed op runners: the dispatch plane's pad-to-bucket fast path.

``planops.dispatch`` routes every op that has a one-op runner through
:func:`dispatch_bucketed` before falling back to the op's exact-shape
function. This module is the mechanism only — padding, cache keys, the
count read, the join's and the groupby's two-launch runners and the
generic one-op runner — and knows no list of ops: ``planops.OPS`` hands
it each op's traced body and program name. A runner:

1. pads its input tables to their row-count buckets
   (``utils/buckets.pad_table``; wire uploads arrive pre-padded on the
   host side, so this is usually a no-op),
2. fetches the op's compiled executable from the
   ``(op, schema signature, bucket)`` cache (``utils/buckets.cached_jit``)
   — a ragged stream of N batch sizes costs O(#buckets) compiles
   instead of O(N),
3. runs the op at the BUCKET shape with the logical row count passed as
   a device scalar; padded rows are dead via validity-aware tail
   masking: the ``row_valid`` occupancy machinery the capped two-phase
   ops already grew for shuffle padding (ops/groupby.py
   ``groupby_aggregate_capped(row_valid=...)``, ops/join.py
   ``left_valid``/``right_valid``, ops/sort.py ``row_valid``,
   ops/compaction.py ``_first_of_run_mask(row_valid=...)``),
4. returns a PADDED result carrying ``Table.logical_rows`` — the wire
   boundary slices host-side (zero extra compiles) and a downstream
   bucketed op consumes the padding directly. A groupby's result is
   born at the bucket of its group count (``_reduce_groups``), so the
   per-group half of the aggregation, and what follows it, run at the
   aggregate's size.

Semantics contract: for the first ``logical_rows`` rows the result is
bit-identical to the exact path (``tests/test_buckets.py`` pins this at
bucket-boundary row counts). Any runner failure falls back to the exact
path, which remains the semantic reference — bucketing can change
performance, never results.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from . import dtype as dt
from .column import Column, Table
from .utils import buckets, log, metrics, profiler


class _Decline(Exception):
    """Internal: this op/shape opts out of bucketing (exact path runs)."""


_WARNED_OPS = set()


def dispatch_bucketed(
    runner, op: dict, table: Table, rest: Sequence[Table], name: str
) -> Optional[Table]:
    """Run one op through the bucket plane with its one-op ``runner``
    (``(op, table, rest) -> Table``). Returns the (possibly padded)
    result Table, or None when the op/shape isn't bucketable — the
    caller then unpads the inputs and runs the exact path."""
    # the span makes the bucket plane its own flight-recorder/trace
    # track (nested inside dispatch.<op>); declines and fallbacks are
    # handled INSIDE it so they exit the span cleanly instead of
    # counting as span errors
    with metrics.span("bucketed." + name):
        try:
            out = runner(op, table, tuple(rest))
        except _Decline:
            metrics.counter_add("bucket.declined")
            return None
        # srt: allow-broad-except(semantics-preserving fallback: the exact path re-runs the op and raises the real error)
        except Exception as e:
            # bucketing must never change semantics: any runner failure
            # falls back to the exact path, which raises the real error
            # if the op itself is at fault
            metrics.counter_add("bucket.fallback_errors")
            profiler.note_fallback("bucketed")
            if name not in _WARNED_OPS:
                _WARNED_OPS.add(name)
                log.log(
                    "WARN", "buckets", "bucketed_runner_failed", op=name,
                    error=f"{type(e).__name__}: {str(e)[:200]}",
                )
            return None
    metrics.counter_add("bucket.dispatched")
    return out


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------


def _padded_input(t: Table) -> Table:
    """The bucketed view of an input table: pre-padded tables pass
    through (their physical size keys the cache), exact tables pad to
    their bucket; shapes with no bucket decline."""
    b = padded_rows(t)
    return t if t.logical_rows is not None else buckets.pad_table(t, b)


def padded_rows(t: Table) -> int:
    """`_padded_input`'s physical row count, before anything is padded."""
    n = t.logical_row_count
    b = t.row_count if t.logical_rows is not None else buckets.bucket_for(n)
    if n <= 0 or b is None:
        raise _Decline
    return b


def _strip(t: Table) -> Table:
    """Drop the logical-row metadata before a jit call: the count
    travels as a device scalar instead, so every logical size within a
    bucket shares ONE traced program (pytree aux must not vary)."""
    return Table(t.columns, t.names)


def _n_dev(t: Table):
    return jnp.asarray(t.logical_row_count, jnp.int32)


def _finish(padded_out: Table, logical) -> Table:
    return Table(
        padded_out.columns, padded_out.names, logical_rows=int(logical)
    )


def _key(kind: str, op: dict, *tables: Table, extra: tuple = ()) -> tuple:
    return buckets.cache_key(kind, op, tables, extra)


def _groupby_aggs(op: dict) -> list:
    """The op's aggregations; collect_* decline (a data-dependent list
    capacity pre-pass the exact path owns)."""
    from .ops.groupby import _COLLECT_OPS, GroupbyAgg

    aggs = [GroupbyAgg(a["column"], a["agg"]) for a in op["aggs"]]
    if any(a.op in _COLLECT_OPS for a in aggs):
        raise _Decline
    return aggs


# A served groupby is two launches with a host read between them, and
# the device runs launches in the order they arrive. With two tenants
# the other's sort half, enqueued during that read, ran BETWEEN this
# groupby's halves: both sorts, both reduces, then both tenants on the
# host at once and the device idle — or not, as the host's timing fell,
# so that a request's time depended on when the other tenant's arrived
# (stream-c2's runs spread by 3% on one tree; PERF.md, PR 27). A turn
# keeps the halves together: the next tenant's first half is enqueued
# behind this one's second, and its host work hides behind the device's.
_GROUPBY_TURN = threading.Lock()
# a turn orders launches, it never withholds service: a tenant that has
# waited this long (a cold compile, a 2^23-row sort ahead of it) goes
# unordered, as every launch did before
_TURN_WAIT_S = 0.5


@contextlib.contextmanager
def groupby_turn():
    """Held from a groupby's first launch until its second is enqueued."""
    held = _GROUPBY_TURN.acquire(timeout=_TURN_WAIT_S)
    try:
        yield
    finally:
        if held:
            _GROUPBY_TURN.release()


def group_bucket(groups: int, rows: int) -> int:
    """The width a groupby's per-group half runs at: the bucket of its
    group count, at least the smallest bucket (zero groups still return
    the exact schema) and at most the ``rows`` its first half sorted
    (every row its own group)."""
    k = buckets.bucket_for(max(groups, 1))
    return rows if k is None or k > rows else k


def _reduce_groups(state, num_groups) -> Table:
    """Second half of a served groupby, launched at the bucket of the
    group count.

    The first half (``ops.groupby.groupby_sort``, inside the per-op
    runner's or the fused segment's executable) has sorted the input's
    N rows and counted the groups; everything per group — the segment
    searches, the key gather, each aggregate's gathers — runs here at
    ``K = bucket_for(num_groups)``: 2^13 wide for 6,666 groups of 2^23
    rows, where one trace has to run it 2^23 wide. The same shape as the
    inner join's probe -> count read -> materialise. ``K`` is at least
    the smallest bucket (zero groups still return the exact schema) and
    at most N (every row its own group: today's work, nothing lost).
    One executable per (schema, aggs, N, K); the launch returns at
    enqueue."""
    from .ops.groupby import groupby_reduce

    n = int(state.perm.shape[0])
    # srt: allow-host-sync(bucketed-runner boundary: the first half's launch is done; one count read sizes the second half and the logical rows of its result)
    g = int(num_groups)
    k = group_bucket(g, n)

    def build():
        def fn(st, ng):
            return groupby_reduce(st, ng, k)

        return fn

    key = (
        "groupby.reduce", state.slots,
        buckets.table_signature(state.keys), n, k,
    )
    fn = buckets.cached_jit(
        key, build, "srt_groupby_reduce", scope="srt.groupby"
    )
    metrics.counter_add("groupby.input_rows", n)
    metrics.counter_add("groupby.reduce_rows", k)
    with metrics.span("groupby.reduce", rows=k):
        out = fn(state, num_groups)
    return _finish(out, g)


# ---------------------------------------------------------------------------
# runners
# ---------------------------------------------------------------------------


def run_one_op(
    op: dict, table: Table, name: str, traced, program: str, counts: bool
) -> Table:
    """The one-op runner of every simple op: pad, compile ``fn(t, n)``
    from the op's ONE traced body (``planops.OPS[name].traced``, the
    body a fused segment runs) under ``program`` and the
    ``(name, op, schema, bucket)`` key, launch. ``counts`` says the body
    changes the row count: then the program returns the new count and
    one read of it sizes the logical rows of the padded result."""
    pt = _padded_input(table)

    def build():
        def fn(t, n):
            out, n = traced(op, t, n, buckets.tail_valid(t.row_count, n))
            return (out, n) if counts else out

        return fn

    fn = buckets.cached_jit(
        _key(name, op, pt), build, program, scope="srt." + name
    )
    if not counts:
        return _finish(fn(_strip(pt), _n_dev(pt)), pt.logical_row_count)
    out, count = fn(_strip(pt), _n_dev(pt))
    # srt: allow-host-sync(bucketed-runner boundary: the compiled launch is done; one count read sizes the logical rows of the padded result)
    return _finish(out, int(count))


def _r_groupby(op: dict, table: Table, rest) -> Table:
    from .ops.groupby import groupby_sort

    aggs = _groupby_aggs(op)
    pt = _padded_input(table)
    by = list(op["by"])

    def build():
        def fn(t, n):
            rv = buckets.tail_valid(t.row_count, n)
            return groupby_sort(t, by, aggs, row_valid=rv)

        return fn

    fn = buckets.cached_jit(
        _key("groupby", op, pt), build, "srt_bucketed_groupby",
        scope="srt.groupby",
    )
    with groupby_turn():
        state, num_groups = fn(_strip(pt), _n_dev(pt))
        return _reduce_groups(state, num_groups)


# the hows _r_join has code for; planops' join entry reads this for
# its ``bucketable``
JOIN_HOWS = frozenset({"inner", "left", "semi", "anti"})

# logical rows of the two sides and of the result of the calling
# thread's last served join and the bucket its output ran at, all host
# integers `_r_join` holds anyway: the serving tier's work item moves
# them onto its session with take_join() (planmesh.take_exchange's
# sibling)
_LAST_JOIN = threading.local()


def take_join():
    """``(probe_rows, build_rows, output_rows, cap)`` of this thread's
    last served join, once (None when none ran since): ``cap`` the
    output's bucket, the rows its materialise (a semi / anti join: its
    compaction) ran at."""
    plan, _LAST_JOIN.plan = getattr(_LAST_JOIN, "plan", None), None
    return plan


def _note_join(lt: Table, rt: Table, total: int, cap: int) -> None:
    _LAST_JOIN.plan = (
        lt.logical_row_count, rt.logical_row_count, total, cap
    )


def _build_key_facts(rt: Table, on: list) -> tuple:
    """What a `direct_key` join's build side shows, as host integers:
    ``(kmin, kmax, valid_rows, repeats)`` (`ops.join.build_key_span`).
    One tiny program at the build side's width and its read, before the
    launch it chooses."""
    from .ops import join as join_mod

    def build():
        def fn(r, rn):
            rv = buckets.tail_valid(r.row_count, rn)
            return join_mod.build_key_span(r, on, rv)

        return fn

    fn = buckets.cached_jit(
        _key("join.span", {"on": on}, rt), build,
        "srt_bucketed_join_span", scope="srt.join",
    )
    span = fn(_strip(rt), _n_dev(rt))
    # srt: allow-host-sync(bucketed-runner boundary: one read of four words from the build side chooses the probe before its launch)
    return tuple(int(v) for v in np.asarray(span))


def _probe_choice(lt: Table, rt: Table, on: list) -> tuple:
    """The one choice of a served join's probe, from what the build side
    shows: ``(table_size, narrow, unique)``. ``table_size`` is the
    direct probe's when the key is one integer-family column and a
    table as wide as its valid build keys' span, however sparse, fits
    the device's share (``ops.join.direct_table_size``), None for the
    search; ``unique`` says that no valid build key repeats, so that
    the table holds a row and no count; ``narrow`` says that the search
    can run over one u32 word a side: the valid build keys span under
    2^32 values (``ops.join.offsets_fit``), and only the table's bytes
    were in the way. A key of another kind is decided from the schema
    and searched over all its words; an integer one costs
    `_build_key_facts`."""
    from .ops import join as join_mod

    if not join_mod.direct_key(
        [lt.column(c) for c in on], [rt.column(c) for c in on]
    ):
        return None, False, False
    kmin, kmax, valid_rows, repeats = _build_key_facts(rt, on)
    table_size = join_mod.direct_table_size(kmin, kmax, valid_rows)
    if table_size is not None:
        return table_size, False, not repeats
    return None, join_mod.offsets_fit(kmin, kmax, valid_rows), False


def selecting_table_size(
    op: dict, rt: Table, probe_rows: int
) -> Optional[int]:
    """The direct probe's table size when this join only SELECTS among
    its probe rows, so that a fused segment can run it without moving
    one (``ops.join.lookup_unique``): an ``inner`` join on one
    integer-family column whose valid build keys are dense and repeat
    no value. None for every other join, which stays `_r_join`'s. Read
    from the (padded) build side ``rt`` alone, before the plan is
    segmented; the probe side's key is held to `direct_key` where the
    segment is traced.

    Dense, here, is the table no wider than twice the build side nor
    than the probe side: narrower than `_r_join`'s own probe asks
    (``ops.join.direct_table_size``), because riding the segment is
    priced differently: one more probe-wide gather a 32-bit word of
    every build column read, and the groupby's sort at the probe's
    width. A sparse unique key may well win there too; it has not been
    measured (PERF.md §7)."""
    from .ops import join as join_mod

    on = op.get("on")
    if op.get("how", "inner") != "inner" or not isinstance(on, list):
        return None
    try:
        if not join_mod.addressable_key([rt.column(c) for c in on]):
            return None
    except (IndexError, KeyError, TypeError, ValueError):
        return None  # no such column: the per-op path says so
    kmin, kmax, valid_rows, repeats = _build_key_facts(rt, on)
    if repeats:
        return None
    size = join_mod.direct_table_size(kmin, kmax, valid_rows)
    if size is None or size > 2 * rt.row_count or size > probe_rows:
        return None
    return size


def join_probe_program(
    on: list, table_size: Optional[int], narrow: bool, unique: bool
):
    """Phase 1 of a served inner / left join, as `_r_join` compiles it
    (and `tests/test_chip_compile.py`, at a cell's buckets): the match
    ranges of every probe row and both totals."""
    def fn(l, r, ln, rn):
        from .ops.join import _left_emit, _match_ranges

        lv = buckets.tail_valid(l.row_count, ln)
        rv = buckets.tail_valid(r.row_count, rn)
        perm_r, lo, counts, _ = _match_ranges(
            l, r, on, on, lv, rv, table_size=table_size, narrow=narrow,
            unique=unique,
        )
        return (
            perm_r, lo, counts,
            jnp.sum(counts),
            jnp.sum(_left_emit(counts, lv)),
        )

    return fn


def join_mat_program(on: list, cap: int, left_outer: bool):
    """Phase 2: the joined rows at the output's bucket ``cap``, from
    phase 1's ranges."""
    def fn(l, r, perm_r, lo, counts, ln):
        from .ops.join import _expand, _join_output, _left_emit

        if left_outer:
            lv = buckets.tail_valid(l.row_count, ln)
            emit = _left_emit(counts, lv)
            left_idx, right_idx, matched, _ = _expand(
                perm_r, lo, counts, cap, left_outer=True, emit=emit
            )
            return _join_output(
                l, r, on, left_idx, right_idx, matched, None
            )
        left_idx, right_idx, _, _ = _expand(
            perm_r, lo, counts, cap, left_outer=False
        )
        # no matched/row_valid masks, matching the exact-path
        # inner_join output schema; rows past ``total`` are garbage
        # behind the logical row count
        return _join_output(l, r, on, left_idx, right_idx, None, None)

    return fn


def _r_join(op: dict, table: Table, rest) -> Table:
    from .ops.join import mat_spreads

    how = op.get("how", "inner")
    if how not in JOIN_HOWS or not rest:
        # right/full build on the exact outer machinery; argument
        # errors surface from the exact path
        raise _Decline
    lt = _padded_input(table)
    rt = _padded_input(rest[0])
    on = list(op["on"])
    probe = table_size, narrow, unique = _probe_choice(lt, rt, on)
    metrics.counter_add(
        "join.probe.search" if table_size is None else "join.probe.direct"
    )
    if narrow:
        metrics.counter_add("join.probe.narrow")
    if table_size is not None and not unique:
        # the span read showed a valid build key twice: the table holds
        # a run's head and its length (a search never asks)
        metrics.counter_add("join.build.repeats")
    # logical rows of the two sides and (below) of the result, a served
    # join, and the entries of the table it probed (none: a search):
    # beside groupby.input_rows / groupby.reduce_rows
    metrics.counter_add("join.probe_rows", lt.logical_row_count)
    metrics.counter_add("join.build_rows", rt.logical_row_count)
    metrics.counter_add("join.table_entries", table_size or 0)

    if how in ("semi", "anti"):
        anti = how == "anti"

        def build_sa():
            def fn(l, r, ln, rn):
                from .ops.filter import filter_table_capped
                from .ops.join import _match_ranges

                lv = buckets.tail_valid(l.row_count, ln)
                rv = buckets.tail_valid(r.row_count, rn)
                _, _, counts, lvalid = _match_ranges(
                    l, r, on, on, lv, rv,
                    table_size=table_size, narrow=narrow, unique=unique,
                )
                has = jnp.logical_and(counts > 0, lvalid)
                if anti:
                    # null-key rows match nothing -> kept by ANTI;
                    # padding rows (lv False) emit nothing
                    keep = jnp.logical_and(jnp.logical_not(has), lv)
                else:
                    keep = has
                return filter_table_capped(
                    l, Column(keep, dt.BOOL8, None), capacity=l.row_count
                )

            return fn

        fn = buckets.cached_jit(
            _key("join." + how, op, lt, rt, extra=probe), build_sa,
            "srt_bucketed_join_" + how, scope="srt.join",
        )
        out, count = fn(_strip(lt), _strip(rt), _n_dev(lt), _n_dev(rt))
        # srt: allow-host-sync(bucketed-runner boundary: the compiled launch is done; one count read sizes the logical rows of the padded result)
        total = int(count)
        metrics.counter_add("join.output_rows", total)
        _note_join(lt, rt, total, lt.row_count)
        return _finish(out, total)

    # inner/left: two-phase sizing. Phase 1 (probe) compiles per input
    # bucket pair; phase 2 (materialize) per OUTPUT capacity bucket —
    # the output size is bucketed too, so both phases cost O(#buckets)
    # executables across a ragged stream.
    p1 = buckets.cached_jit(
        _key("join.ranges", {"on": on}, lt, rt, extra=probe),
        lambda: join_probe_program(on, table_size, narrow, unique),
        "srt_bucketed_join_probe", scope="srt.join",
    )
    perm_r, lo, counts, inner_total, left_total = p1(
        _strip(lt), _strip(rt), _n_dev(lt), _n_dev(rt)
    )
    # srt: allow-host-sync(bucketed-runner boundary: the compiled launch is done; one count read sizes the logical rows of the padded result)
    total = int(left_total if how == "left" else inner_total)
    metrics.counter_add("join.output_rows", total)
    cap = buckets.bucket_for(total)
    if cap is None:
        # no output bucket (empty result, or a fan-out past the ladder
        # cap): materializing at the exact total would compile one
        # executable per distinct size AND build the oversized fused
        # graphs the cap exists to avoid — the exact path (with its
        # fenced batched-probe routing) owns those shapes
        raise _Decline
    _note_join(lt, rt, total, cap)
    metrics.counter_add("join.mat.cap_rows", cap)
    if mat_spreads(cap, lt.row_count):
        # the form the materialise compiles to below, by the same
        # predicate: an output wider than its probe side spreads the
        # probe side's values and gathers only the build side's
        metrics.counter_add("join.mat.spread")
    p2 = buckets.cached_jit(
        _key("join.mat." + how, {"on": on}, lt, rt, extra=(cap,)),
        lambda: join_mat_program(on, cap, how == "left"),
        "srt_bucketed_join_mat", scope="srt.join",
    )
    out = p2(_strip(lt), _strip(rt), perm_r, lo, counts, _n_dev(lt))
    return _finish(out, total)
