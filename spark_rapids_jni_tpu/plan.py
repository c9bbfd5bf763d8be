"""Plan compiler: whole op chains fused into single cached executables.

``runtime_bridge.table_plan_wire``/``table_plan_resident`` accept a JSON
*list* of ops instead of a single op. This module segments the list into
maximal runs of fusable single-table bucketable ops and compiles each
run into ONE jitted callable cached under a ``(plan signature, schema
signature, bucket)`` key via the same ``utils/buckets.cached_jit`` the
per-op bucketed runners use. Intermediates inside a segment stay traced
values: they never materialize as resident tables, never re-enter
Python, and the whole segment costs one executable launch — the
Weld/Photon-style lazy-fusion step layered on PR 2's shape buckets.

Fusable ops (single-table, bucketable, ``row_valid``-maskable):
``cast``, ``project``, ``filter``, ``rlike``, ``distinct``, ``sort_by``,
``slice`` (non-negative bounds), and a non-collect ``groupby`` TAIL
(what ``planops.OPS`` marks ``fusable``) — a groupby may close a fused
run but not continue it: the segment's executable
ends with the groupby's sort half, its per-group half is a second
launch at the bucket of the group count (``bucketed._reduce_groups``),
and the following ops re-enter the compiler on that result. A filter
from which only row-local ops lead to that groupby moves no row: its
selection joins the occupancy mask the groupby's sort already carries
(``_run_segment_traced``). So does an ``inner`` join in that place
whose build side shows a unique, directly addressable key (read from
the data before the plan is segmented, ``_selecting_joins``): its match
bit joins the mask, its build columns are a row-local lookup, and the
build table is one more argument of the segment's executable.
Everything else (any other join, concat, explode, to_rows/from_rows, ...) is a
segment boundary dispatched through the one-op ``planops.dispatch``
path — bucketed runner or exact fallback — with ``Table.logical_rows`` carried through unchanged so padding
semantics survive the boundary.

Semantics contract: byte-identical to the per-op path (which is itself
byte-identical to the exact path — tests/test_buckets.py). ANY failure
inside a fused segment falls back to per-op replay of that segment, so
op errors surface from the exact path with their real messages —
fusion can change launch counts, never results
(tests/test_plan.py pins both).

Telemetry (``plan.*``, through the metrics registry + flight recorder):
``plan.calls``/``plan.segments``/``plan.fused_segments``/
``plan.fused_ops``/``plan.exact_ops``/``plan.fallbacks``/
``plan.declined`` counters (plus ``plan.mesh_segments``/
``plan.mesh_declined``/``plan.mesh_fallbacks`` when a mesh runner is
offered — see ``parallel/planmesh.py``, whose stage counts what it
sized beside its ``mesh.pack``/``mesh.counts``/``mesh.exchange``/
``mesh.groupby``/``mesh.gather`` spans: ``mesh.exchange.slot_rows``/
``.recv_rows``, ``mesh.gather.rows_read``/``.rows_kept`` and, where a
groupby rides the exchange, ``mesh.groupby.stages``/``.rows_in`` (the
rows the devices counted behind the exchange)/``.groups``/``.slot_rows``
(devices x the group bucket)), a ``plan`` span wrapping each run with one
``plan.segment.<sig>`` span per segment (its device-ended time is the
completion clock's ``device.plan.segment.<sig>``, utils/devclock.py),
``plan.fallback`` flight instants,
and the ``compile_cache.miss`` instants ``cached_jit`` already emits
(fused executables are named ``srt_fused_plan`` so ``jax.log_compiles``
lines are attributable).
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import bucketed, plancheck, planops
from .column import Table
from .utils import buckets, faults, flight, hbm, log, metrics, profiler

# fused-segment failures are replayed per-op; warn once per op-chain
# shape (the bucketed._WARNED_OPS discipline), not per call
_WARNED_SIGS = set()

# Donated segments EXPECT partial aliasing: a filter drops its mask
# column and a cast changes a dtype, so some input buffers have no
# same-shaped output to alias and XLA warns per compile. The donation
# of the (dominant) same-schema buffers still lands; the warning is
# noise for this plane and is filtered narrowly. Re-armed per donated
# launch (idempotent: skipped when an equivalent filter is already
# live) because the process filter list is freely reset by embedders
# and per-test by pytest — a one-shot module flag would leak the
# warning everywhere after the first such reset.
_DONATE_WARNING_MSG = "Some donated buffers were not usable"


def _filter_partial_donation_warning() -> None:
    import warnings

    for f in warnings.filters:
        if (
            f[0] == "ignore"
            and f[1] is not None
            and f[1].pattern == _DONATE_WARNING_MSG
        ):
            return
    warnings.filterwarnings("ignore", message=_DONATE_WARNING_MSG)


def segment_plan(ops: Sequence[dict]) -> List[Tuple[str, list]]:
    """Split a plan into ``[(kind, ops)]`` segments, ``"fused"`` or
    ``"exact"``: ``plancheck.predict_segments`` over the ops themselves."""
    return [
        (kind, [ops[i] for i in idxs])
        for kind, idxs in plancheck.predict_segments(ops)
    ]


def _run_segment_traced(seg_ops: Sequence[dict], t: Table, n, builds=()):
    """The traced body of one fused segment: thread (table, occupancy)
    through every op at the segment's one physical shape. A groupby
    tail leaves its sorted state in the table's place. ``builds`` holds
    ``(build table, its device count, table size)`` for each join of
    the segment, in order.

    Occupancy flows as the count ``n`` (a prefix, turned into a mask
    for each op) until a selecting op at or behind
    ``planops.deferred_from``: that one keeps its rows where they are
    and ANDs its selection into the mask, which then flows on as it is
    — the row-local ops behind it never read the count, and the groupby
    tail's sort puts the rows of any mask last. Everywhere else a
    selecting op compacts, because what follows (an op that reads the
    count, the segment's caller) needs the prefix."""
    masked_from = planops.deferred_from(seg_ops)
    builds = iter(builds)
    mask = None
    for i, op in enumerate(seg_ops):
        spec = planops.OPS[op["op"]]
        # trace-time only: every HLO op of this plan op carries its
        # name in its op_name metadata, so a device trace can give a
        # fusion to filter, join, groupby or sort_by
        with jax.named_scope("srt." + op["op"]):
            rv = buckets.tail_valid(t.row_count, n) if mask is None else mask
            if spec.select is not None and i >= masked_from:
                build = (next(builds),) if op["op"] == "join" else ()
                t, mask = spec.select(op, t, rv, *build)
                continue
            t, n = spec.traced(op, t, n, rv)
            if hasattr(n, "astype"):
                n = n.astype(jnp.int32)
    return t, n


def _run_fused(
    seg_ops: Sequence[dict], table: Table, donate: bool = False,
    builds: Sequence[Tuple[Table, int]] = (),
) -> Table:
    """One fused segment -> one cached executable -> one launch (and,
    behind a groupby tail, the launch of its per-group half).

    ``builds`` is ``(build table, table size)`` for each join of the
    segment, in order (``_selecting_joins``): further arguments of the
    executable, whose key holds their schema, bucket and table size.
    They are the caller's and are never donated.

    ``donate=True`` marks the segment's input table as CONSUMED: its
    padded buffers are donated to the executable
    (``buckets.cached_jit(donate_args=(0,))``) so XLA updates HBM in
    place instead of holding input + output simultaneously — the
    resident-chain peak-halving of ISSUE 5. The caller guarantees
    nothing else references the input's buffers (plan-owned
    intermediates, consumed resident ids, freshly decoded wire
    tables). After the call the input arrays are deleted; the
    ``run_plan`` fallback checks for that before attempting a per-op
    replay."""
    pt = bucketed._padded_input(table)  # _Decline when unbucketable
    rts = [bucketed._padded_input(b) for b, _ in builds]
    sizes = tuple(size for _, size in builds)
    key = buckets.cache_key("plan", list(seg_ops), (pt, *rts), sizes)

    def build():
        def fn(t, n, *rest):
            return _run_segment_traced(
                seg_ops, t, n, [(*rn, size) for rn, size in zip(rest, sizes)]
            )

        return fn

    donate_args = (0,) if donate else ()
    if donate:
        _filter_partial_donation_warning()
    fn = buckets.cached_jit(
        key, build, "srt_fused_plan", donate_args=donate_args
    )
    donated = hbm.table_bytes(pt) if donate else 0
    groupby_tail = seg_ops[-1]["op"] == "groupby"
    # a groupby tail's two launches stay together on the device
    with bucketed.groupby_turn() if groupby_tail else contextlib.nullcontext():
        out, count = fn(
            bucketed._strip(pt), bucketed._n_dev(pt),
            *((bucketed._strip(r), bucketed._n_dev(r)) for r in rts),
        )
        planops.note_launched(seg_ops)
        if donated:
            # counted AFTER the launch: a trace/compile failure falls back
            # to per-op replay with the input intact — nothing was donated
            hbm.note_donation(donated)
        if groupby_tail:
            # the per-op runner's second half, so both paths hand the next
            # op the same physical shape
            return bucketed._reduce_groups(out, count)
    # srt: allow-host-sync(segment boundary: the fused launch is done; the count read is the one sync that sizes the unpadded result)
    return bucketed._finish(out, int(count))


def run_donated(op: dict, table: Table, name: str) -> Optional[Table]:
    """Run ONE op whose input table is CONSUMED (the caller released
    its resident id) with the padded input donated to the executable —
    the single-op flavor of plan-segment donation: a one-op segment
    through :func:`_run_fused`, so the donated executable shares its
    cache keying. Returns None when the op/shape can't take the donated
    path (the caller then runs the normal dispatch on the still-intact
    input); raises only when the donated launch failed AFTER consuming
    its buffers."""
    if not buckets.enabled() or not planops.op_fusable(op):
        return None
    with metrics.span("bucketed.donated." + name):
        try:
            return _run_fused([op], table, donate=True)
        except bucketed._Decline:
            metrics.counter_add("bucket.declined")
            return None
        except Exception as e:
            if _input_consumed(table):
                raise
            metrics.counter_add("bucket.fallback_errors")
            profiler.note_fallback("bucketed")
            if name not in bucketed._WARNED_OPS:
                bucketed._WARNED_OPS.add(name)
                log.log(
                    "WARN", "buckets", "donated_runner_failed", op=name,
                    error=f"{type(e).__name__}: {str(e)[:200]}",
                )
            return None


def _run_chunked(seg_ops: Sequence[dict], table: Table) -> Table:
    """The graceful-degradation path for a ResourceExhausted fused
    segment: split the input at half the rows, run each half through
    the same fused machinery (smaller bucket -> smaller working set),
    and concatenate — parity-safe because every op in the segment is
    row-local (caller-gated on the specs' ``row_local``: sort_by,
    distinct, groupby and slice are global and fall back to the exact
    path instead). Returns the exact (unpadded) result table; raises
    faults.ResourceExhausted when the input is too small to split."""
    from .ops.copying import concatenate, slice_rows

    t = buckets.unpad_table(table)
    n = int(t.row_count)
    if n < 2:
        raise faults.ResourceExhausted(
            f"segment OOM at {n} row(s): nothing left to split"
        )
    halves = []
    # a _Decline at the half shape propagates: the exact per-op path
    # is the smaller-footprint fallback the caller owns
    for lo, hi in ((0, n // 2), (n // 2, n)):
        part = slice_rows(t, lo, hi)
        halves.append(buckets.unpad_table(_run_fused(seg_ops, part)))
    metrics.counter_add("plan.chunked_segments")
    if flight.enabled():
        flight.record(
            "I", "plan.oom_chunked",
            ",".join(str(o.get("op", "?")) for o in seg_ops),
        )
    return concatenate(halves)


def _run_fused_tolerant(
    seg_ops: Sequence[dict], table: Table, donate: bool,
    builds: Sequence[Tuple[Table, int]] = (),
) -> Table:
    """One fused segment with the fault-tolerance contract applied at
    segment granularity:

    * a donated launch that already CONSUMED its input is at-most-once
      (PR 5's doomed-replay rule): its error surfaces as-is, no retry;
    * a ResourceExhausted-classified failure with the input intact
      first asks the spill tier for headroom (utils/spill.py: the
      coldest resident tables demote to host/disk) and retries the
      SAME launch — degrade by moving cold data, not by splitting hot
      work; only when nothing could spill does it retry at half-batch
      chunks (row-local segments only);
    * a transient-classified failure retries the whole segment with
      backoff up to RETRY_MAX (the injection fires BEFORE the launch
      consumes anything, so an injected retry is always safe);
    * anything else propagates to run_plan's per-op replay fallback.
    """
    attempt = 0
    spill_tried = False
    while True:
        faults.check_cancel()
        try:
            faults.inject("dispatch")
            return _run_fused(seg_ops, table, donate=donate, builds=builds)
        except bucketed._Decline:
            raise
        except (faults.Cancelled, faults.DeadlineExceeded):
            raise
        except Exception as e:
            if _input_consumed(table):
                # donated executable failed AFTER consuming its input:
                # retrying (or replaying) would dereference deleted
                # buffers — the worker error is authoritative
                raise
            cls = faults.classify(e)
            if cls is faults.ResourceExhausted and not spill_tried:
                # OOM ladder rung 1: free headroom by spilling cold
                # resident tables, then retry the SAME shape. 2x the
                # input sizes the launch's input + output residency.
                spill_tried = True
                from .utils import spill

                freed = spill.request_headroom(
                    2 * hbm.table_bytes(table), reason="oom"
                )
                if freed:
                    metrics.counter_add("plan.oom_spill_retries")
                    if flight.enabled():
                        flight.record("I", "plan.oom_spill_retry", freed)
                    continue
            if cls is faults.ResourceExhausted and all(
                planops.OPS[o["op"]].row_local for o in seg_ops
            ):
                try:
                    return _run_chunked(seg_ops, table)
                # srt: allow-broad-except(chunked-fallback failure defers to the exact path, which owns the original typed error)
                except Exception:
                    raise e  # exact-path fallback owns it from here
            if (
                faults.retryable_class(cls)
                and attempt < faults.retry_max()
            ):
                attempt += 1
                faults.sleep_backoff(
                    attempt, "plan.segment." + segment_sig(seg_ops),
                    error=e,
                )
                continue
            raise


def _take_rest(op: dict, orig_rest: tuple, queue: list) -> list:
    """Extra input tables for a multi-table fallback op: an explicit
    ``"rest"`` field names indices into the plan call's extra-table
    list; otherwise join/cross_join consume the next unconsumed extra
    table and concat consumes everything left."""
    idxs = op.get("rest")
    if idxs is not None:
        return [orig_rest[int(i)] for i in idxs]
    name = op.get("op")
    if name in ("join", "cross_join"):
        return [queue.pop(0)] if queue else []
    if name == "concat":
        out = list(queue)
        queue.clear()
        return out
    return []


def run_plan(
    ops: Sequence[dict],
    table: Table,
    rest: Sequence[Table] = (),
    donate_input: bool = False,
    mesh_runner=None,
) -> Table:
    """Execute a plan (a list of op dicts) over ``table``; returns the
    final (possibly padded) Table. The chain's flowing table is always
    the FIRST input of every op; ``rest`` supplies extra tables for
    multi-table segment-boundary ops (see ``_take_rest``).

    ``mesh_runner`` (a ``parallel.tolerant.MeshRunner``) offers the
    plan to the mesh data-parallel path first: row-local plans run
    sharded over the runner's mesh with fault-tolerant replay
    (``parallel/planmesh.py``). A plan with no mesh path falls through
    here silently; a mesh whose degradation ladder hits its device
    floor falls back to this single-device exact path (metered as
    ``plan.mesh_fallbacks`` — the serving tier's keep-the-tenant
    guarantee), which then runs ``planmesh.exact_ops``: the same plan
    in the mesh stage's row order. The mesh path never consumes
    ``table``, so both fallbacks are safe even with
    ``donate_input=True``.

    ``donate_input=True`` declares ``table`` consumed by this plan —
    nothing else holds its buffers (a wire upload, a resident id the
    caller released) — allowing the FIRST fused segment to donate it.
    Later segments may donate too: the flowing table between segments
    is plan-owned. Because an exact boundary segment's output CAN
    alias its input buffers (a single-table concat returns them
    outright), every donation is additionally gated on the flowing
    table's buffers being disjoint from everything the caller can
    still observe (the undonated input and every ``rest`` table)."""
    if not isinstance(ops, (list, tuple)):
        raise TypeError("plan must be a JSON list of op objects")
    if not ops:
        return table
    for op in ops:
        if not isinstance(op, dict) or "op" not in op:
            raise ValueError(f"plan entries must be op objects, got {op!r}")
    # one span for the whole plan, the mesh offer included: the mesh
    # path is a plan like any other to whoever reads the `plan` timer
    with metrics.span("plan", ops=len(ops)):
        if mesh_runner is not None:
            out, ops = _offer_mesh(ops, table, rest, mesh_runner)
            if out is not None:
                return out
        return _run_segments(ops, table, rest, donate_input)


def segment_sig(seg_ops: Sequence[dict]) -> str:
    """A segment's op names joined by ``__`` (``filter__groupby``): the
    suffix of its ``plan.segment.<sig>`` span. Op names are lowercase
    identifiers, so the timer name keeps the metric-name grammar."""
    return "__".join(str(o.get("op", "op")) for o in seg_ops)


def _offer_mesh(ops, table: Table, rest, mesh_runner):
    """Offer the plan to the mesh data-parallel path -> ``(result,
    ops)``: the result None where the single-device path below has to
    answer, with the ops it then runs. A declined plan runs as written;
    one the mesh stage took and gave up (``faults.Degraded``) runs
    ``planmesh.exact_ops``, which keeps the stage's row order: an
    aggregate behind an exchange comes back by (partition id, key) from
    one device as from four."""
    from .parallel import planmesh

    # the mesh path runs the whole plan as ONE sharded stage, so it
    # gets one whole-plan "mesh" segment for attribution — the
    # plan-stats record of a mesh run carries rows/bytes like the
    # segment loop does for the exact path
    pseg = profiler.segment_begin(
        0, "mesh", ops, rows_in=int(table.logical_row_count)
    )
    try:
        with metrics.span("plan.segment.mesh", device=True, index=0,
                          kind="mesh", ops=len(ops)):
            try:
                out = planmesh.run_plan_mesh(
                    ops, table, mesh_runner, rest
                )
            except planmesh.MeshUnsupported:
                # not a failure (and no span error): this plan has no
                # mesh path
                out = None
        if out is None:
            metrics.counter_add("plan.mesh_declined")
            profiler.segment_end(pseg)
            pseg = None
            return None, ops
        metrics.counter_add("plan.mesh_segments")
        planops.note_launched(ops)
        profiler.segment_end(
            pseg, rows_out=int(out.logical_row_count),
            out_bytes=hbm.table_bytes(out),
        )
        pseg = None
        return out, ops
    except faults.Degraded as e:
        # collective failures persisted down to the runner's device
        # floor: the single-device exact path IS the degradation
        # target — the mesh path never consumed the input, so the
        # replay lineage is intact
        metrics.counter_add("plan.mesh_fallbacks")
        faults.note_error_class(e, "plan.mesh")
        if flight.enabled():
            flight.record("I", "plan.mesh_fallback", str(e)[:160])
        log.log(
            "WARN", "plan", "mesh_degraded_to_exact",
            error=f"{type(e).__name__}: {str(e)[:200]}",
        )
        profiler.segment_end(pseg, fallback=True)
        pseg = None
    finally:
        # an unexpected exception propagates: close the segment so
        # the thread-local binding never leaks past this plan
        if pseg is not None:
            profiler.segment_end(pseg)
    return None, planmesh.exact_ops(ops, table, rest)


def _selecting_joins(ops, table: Table, orig_rest: tuple):
    """What the segmenter may know of the plan's joins, read from the
    data once, before the plan is segmented: ``(join_selects, builds)``.
    ``join_selects(i, op)`` (``plancheck.predict_segments`` asks it of
    the joins that could ride a run) reads the build side of join ``i``
    (``bucketed.selecting_table_size``) and, when the join only
    selects, leaves ``(padded build table, table size)`` in
    ``builds[i]``. The probe side is taken to be as wide as the plan's
    input: its width bounds the table, and is a matter of cost alone.
    ``(None, {})`` for a plan without a join."""
    build_of: dict = {}
    queue = list(orig_rest)
    try:
        for i, op in enumerate(ops):
            got = _take_rest(op, orig_rest, queue)
            if op["op"] == "join" and got:
                build_of[i] = got[0]
    except (IndexError, TypeError, ValueError):
        pass  # a bad ``rest`` field: the op's own dispatch says so
    if not build_of:
        return None, {}
    builds: dict = {}

    def join_selects(i: int, op: dict) -> bool:
        try:
            rt = bucketed._padded_input(build_of[i])
            size = bucketed.selecting_table_size(
                op, rt, bucketed.padded_rows(table)
            )
        except (faults.Cancelled, faults.DeadlineExceeded):
            raise
        # srt: allow-broad-except(no build table, no bucket, a read that failed: the join stays the boundary it was and its own runner surfaces the real error)
        except Exception:
            return False
        if size is None:
            return False
        builds[i] = (rt, size)
        return True

    return join_selects, builds


def _run_segments(ops, table: Table, rest, donate_input: bool) -> Table:
    """The single-device path: the plan's segments in order, each under
    its ``plan.segment.<sig>`` span."""
    orig_rest = tuple(rest)
    queue = list(orig_rest)
    builds: dict = {}
    if buckets.enabled():
        join_selects, builds = _selecting_joins(ops, table, orig_rest)
        segs = plancheck.predict_segments(ops, join_selects)
    else:
        # debugging mode: the whole plan runs per-op on the exact path
        segs = [("exact", [i]) for i in range(len(ops))]
    metrics.counter_add("plan.calls")
    metrics.counter_add("plan.segments", len(segs))
    owned = bool(donate_input)
    # buffers the CALLER can still observe: a donated segment must
    # never consume these. Ownership flips True after the first
    # segment, but an exact segment's output can ALIAS its input
    # (a single-table concat returns the input buffers outright;
    # unpad_table at the exact row count keeps the same columns), so
    # every donation is additionally gated on buffer disjointness
    # against this set.
    protected: set = set()
    if not donate_input:
        protected.update(_buffer_ids(table))
    for t in orig_rest:
        protected.update(_buffer_ids(t))
    for i, (kind, idxs) in enumerate(segs):
        seg_ops = [ops[j] for j in idxs]
        faults.check_cancel()  # between-segment checkpoint
        with metrics.span(
            "plan.segment." + segment_sig(seg_ops), device=True,
            index=i, kind=kind, ops=len(seg_ops),
        ):
            pseg = profiler.segment_begin(
                i, kind, seg_ops,
                rows_in=int(table.logical_row_count),
            )
            fell_back = False
            try:
                replay = seg_ops
                if kind == "fused":
                    donate = owned and protected.isdisjoint(
                        _buffer_ids(table)
                    )
                    riding = [j for j in idxs if j in builds]
                    try:
                        table = _run_fused_tolerant(
                            seg_ops, table, donate=donate,
                            builds=[builds[j] for j in riding],
                        )
                        # a riding join takes its build table from the
                        # queue as the per-op replay would, once the
                        # launch is through: a fallback replay below
                        # finds the queue as it was
                        for j in riding:
                            _take_rest(ops[j], orig_rest, queue)
                        metrics.counter_add("plan.fused_segments")
                        metrics.counter_add(
                            "plan.fused_ops", len(seg_ops)
                        )
                        replay = ()
                    except bucketed._Decline:
                        # not a failure: no bucket for this shape —
                        # the per-op path owns it
                        metrics.counter_add("plan.declined")
                    except (
                        faults.Cancelled, faults.DeadlineExceeded
                    ):
                        # cooperative aborts are not segment
                        # failures: never replayed, never wrapped
                        raise
                    except Exception as e:
                        if _input_consumed(table):
                            # the donated executable failed AFTER
                            # consuming its input: a per-op replay
                            # would dereference deleted buffers —
                            # surface the real error instead
                            raise
                        # fusion must never change semantics: replay
                        # per-op; the exact path raises the real
                        # error if an op itself is at fault
                        fell_back = True
                        metrics.counter_add("plan.fallbacks")
                        names = ",".join(
                            str(o.get("op", "?")) for o in seg_ops
                        )
                        if flight.enabled():
                            flight.record("I", "plan.fallback", names)
                        if names not in _WARNED_SIGS:
                            _WARNED_SIGS.add(names)
                            log.log(
                                "WARN", "plan",
                                "fused_segment_failed",
                                ops=names,
                                error=(
                                    f"{type(e).__name__}: "
                                    f"{str(e)[:200]}"
                                ),
                            )
                for op in replay:
                    table = planops.dispatch(
                        op, table, _take_rest(op, orig_rest, queue)
                    )
                    metrics.counter_add("plan.exact_ops")
            finally:
                if pseg is not None:
                    try:
                        ro = int(table.logical_row_count)
                        ob = int(hbm.table_bytes(table))
                    # srt: allow-broad-except(donated-and-failed input has no sizeable buffers; profiling must not mask the real error)
                    except Exception:  # donated-and-failed input
                        ro, ob = 0, 0
                    profiler.segment_end(
                        pseg, rows_out=ro, out_bytes=ob,
                        fallback=fell_back,
                    )
        # every segment output is a fresh plan-owned intermediate:
        # the NEXT fused segment may donate it
        owned = True
    return table


def _buffer_ids(table: Table) -> set:
    """Identities of every device buffer a table holds (aliasing
    check for donation safety)."""
    out = set()
    for c in table.columns:
        out.add(id(c.data))
        if c.validity is not None:
            out.add(id(c.validity))
        if c.lengths is not None:
            out.add(id(c.lengths))
    return out


def _input_consumed(table: Table) -> bool:
    """True when a donated executable already deleted this table's
    buffers (replaying it is impossible)."""
    try:
        return bool(table.columns) and table.columns[0].data.is_deleted()
    # srt: allow-broad-except(backends without is_deleted assume replayable — the conservative donation answer)
    except Exception:
        return False
