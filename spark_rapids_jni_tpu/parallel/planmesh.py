"""Mesh data-parallel plan execution: row-local chains, one exchange,
one aggregate behind it.

``run_plan_mesh`` runs a plan whose every op is row-local (``cast``,
``project``, ``filter``, ``rlike`` — the ops ``planops.OPS`` marks
``row_local``) as ONE shard_map stage over a
:class:`~.tolerant.MeshRunner`: rows split into contiguous
blocks (one per device), each shard runs the same fused segment body
the single-device path compiles (``plan._run_segment_traced``), and the
host gathers each shard's valid prefix back in mesh order.

Shuffle as a plan op (ISSUE 17): a plan may additionally carry ONE
``partition`` op (the spec's ``exchange``) anywhere in the chain. It is
the mesh segment boundary: the scan-side row-local chain, a two-phase
counts pass, a ragged all-to-all exchange, a device-local stable sort
back into partition order, and the merge-side row-local chain all run
as one planned pipeline under the same ``MeshRunner`` stage. The
exchange launches are ``shuffle``-site replay boundaries inside the
stage, so seeded shuffle faults replay losslessly from the host-side
lineage and persistent failure walks the degradation ladder like any
other stage.

An aggregate behind the exchange (ISSUE 46): directly behind a HASH
``partition`` the plan may carry ONE op whose ``OpSpec`` has a
``behind_exchange`` rule that admits it — a ``groupby`` whose ``by``
columns hold every partition key and whose aggregates are exact in any
row layout (``planrules.groupby_behind_exchange``). Equal keys hash
alike, so every group lies whole on one device: each device aggregates
the rows it received and the union of the devices' results is the
aggregate of the whole table, every row counted once. A row-local chain
may follow it (a ``project`` and a ``filter``: the HAVING). The groupby
is two launches here as everywhere (``bucketed._reduce_groups``): the
exchange program ends with its sort half, the stage reads every
device's group count in the one read that fetches the exchange's
overflow, and a second sharded program (``srt_mesh_groupby``) runs the
per-group half at the bucket of the LARGEST device's groups, then the
chain behind it.

Parity contract: row-local ops neither reorder rows nor look across
them, so block-sharded execution followed by an in-order prefix gather
is byte-identical to the single-device result — at ANY mesh size. The
partition boundary preserves this: the exact path's ``partition`` is a
stable reorder by partition id, and the mesh path maps the contiguous
pid range ``[d*num//size, (d+1)*num//size)`` to device ``d`` (monotonic
in pid), exchanges rows in stable (src, in-src) order, and stable-sorts
each device's received prefix by recomputed pid — so device ``d`` holds
exactly the ``d``-th contiguous slice of the exact path's reordered
table and the in-order gather is byte-identical, again at ANY mesh
size. That mesh-size independence is what makes the degradation ladder
safe here: when the runner remeshes to fewer devices mid-incident and
replays, the stage re-derives shard layout, counts, and capacities from
the captured host-side lineage (the undonated input table + ops) at the
new size and the bytes do not change.

The order contract of an aggregate behind the exchange: its groups come
back ordered by PARTITION ID, THEN KEY (the groupby's own key order
inside one partition). A device's groupby leaves its groups in key
order; where a device holds one partition (``num <= size``) that is the
contract's order already, and where it holds several (the ladder's
smaller meshes) the reduce program stable-sorts its groups by
recomputed pid. The single-device path that answers the same plan after
``faults.Degraded`` runs :func:`exact_ops`, the plan with the groups
re-partitioned by the same hash behind the groupby — a stable reorder
of key order by pid — so four devices, two and one return the same
bytes. (A session WITHOUT a mesh runs the plan as written and gets key
order: same rows, same values.)

Built once, launched many times: a stage's device work is one of four
cached, jitted ``shard_map`` programs (``_stage_program``:
``srt_mesh_rowlocal``, ``srt_mesh_counts``, ``srt_mesh_exchange``,
``srt_mesh_groupby``) through ``buckets.cached_jit``, keyed by what its
shape depends on — the op lists, the packed table's schema and bucketed
shard width, the mesh's devices, the exchange's rounded capacities, the
group bucket. Whatever is data (a range partition's splitters, the
planned counts, the group counts) is an argument, never a constant of
the closure, so a request of a shape seen before traces, lowers and
compiles nothing, and the completion clock (``utils/devclock.py``) sees
every launch.

Anything else — multi-table rest inputs, any other op that is not
row-local (a join, a second groupby, a groupby whose keys lack a
partition key or that sits behind a range partition), more than one
partition boundary, padded inputs — raises :class:`MeshUnsupported` and
the caller falls through to the ordinary single-device plan path.

``run_plan_mesh_stream`` drives a SEQUENCE of batches through the same
plan with exchange/compute overlap: batch N+1's scan-side counts pass
and host-side pack are staged on the pipeline workers
(``pipeline.stage_ahead``) while batch N's exchange launch runs on the
caller thread — the overlap shows up as ``pipeline.overlap_ms``.
"""

from __future__ import annotations

import threading
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import bucketed, plancheck, planops
from ..column import Column, Table
from ..utils import buckets, metrics
from .mesh import SHUFFLE_AXIS, shard_map
from .tolerant import MeshRunner, run_collective


# planned receive rows per device of the calling thread's last exchange
# stage (the stage reads them to the host to size it) and the two
# capacities it rounded from them, then the same for the groups of a
# groupby behind it: the serving tier's work item moves them onto its
# session with take_exchange()
_LAST_EXCHANGE = threading.local()


def take_exchange():
    """``(planned receive rows per device, cap, pair_cap)`` of this
    thread's last exchange stage, once (None when no exchange ran
    since): ``cap`` the rows a device the exchange program was built
    for, ``pair_cap`` the hottest (src, dst) pair's rounded rows. Where
    a groupby rode the exchange, ``groups per device`` and ``group_cap``
    follow: the candidate groups a device the reduce program was built
    for, the bucket of the largest device's groups."""
    plan, _LAST_EXCHANGE.plan = getattr(_LAST_EXCHANGE, "plan", None), None
    return plan


class MeshUnsupported(Exception):
    """This plan/input shape has no mesh path; use the exact path."""


def _split_at_exchange(ops: Sequence[dict]):
    """``(pre_ops, partition_op | None, post_ops)`` — the plan split at
    its (single) exchange boundary."""
    exchange = {n for n, s in planops.OPS.items() if s.exchange}
    idx = [i for i, o in enumerate(ops) if o.get("op") in exchange]
    if not idx:
        return list(ops), None, []
    if len(idx) > 1:
        raise MeshUnsupported(
            "mesh path handles one partition boundary per plan; "
            f"got {len(idx)}"
        )
    i = idx[0]
    return list(ops[:i]), ops[i], list(ops[i + 1:])


class _Behind(NamedTuple):
    """What runs a device at a time directly behind the exchange: the
    ``op`` (a groupby) and ``again``, the exchange's partition restated
    over the op's OUTPUT columns (the rule that admitted the op resolved
    its keys there): what puts the groups in (pid, key) order."""

    op: dict
    again: dict


def _check_supported(ops: Sequence[dict], table: Table,
                     rest: Sequence[Table]):
    """``(pre, part, group, tail)``: the scan-side chain, the exchange,
    what runs a device at a time directly behind it (:class:`_Behind`:
    a groupby on the exchange's keys) and the chain behind that;
    ``part`` and ``group`` None where the plan has none. Raises
    :class:`MeshUnsupported` for every other plan."""
    if rest:
        raise MeshUnsupported("mesh plan path takes no rest tables")
    if not ops:
        raise MeshUnsupported("empty plan")
    if not table.columns or table.logical_row_count == 0:
        raise MeshUnsupported("empty table")
    pre, part, tail = _split_at_exchange(ops)
    group = None
    head = planops.OPS.get(tail[0].get("op")) if part and tail else None
    if head is not None and head.behind_exchange is not None:
        op, tail = tail[0], tail[1:]
        flowing = plancheck.schema_behind(
            pre, plancheck.schema_of_table(table), table.names
        )
        keys, why = (
            (None, "the scan-side chain's schema cannot be inferred")
            if flowing is None
            else head.behind_exchange(op, part, *flowing)
        )
        if why:
            raise MeshUnsupported(
                f"op {op['op']!r} cannot run behind this exchange: {why}"
            )
        group = _Behind(op, {"op": "partition", "kind": "hash",
                             "keys": keys, "num": part["num"]})
    row_local = sorted(n for n, s in planops.OPS.items() if s.row_local)
    for op in (*pre, *tail):
        name = op.get("op")
        if name not in row_local:
            raise MeshUnsupported(
                f"op {name!r} is not row-local; mesh path handles "
                f"{row_local} chains around one optional partition "
                "boundary, and one groupby on the partition's keys "
                "directly behind a hash partition"
            )
    if part is not None and part.get("kind", "hash") == "range" and pre:
        # range splitters are sampled from the exchange INPUT; with a
        # scan-side chain that input only exists per shard mid-stage,
        # so the deterministic full-table sample the exact path draws
        # is unavailable — decline rather than break byte parity
        raise MeshUnsupported(
            "range partition needs an empty scan-side chain: splitters "
            "are sampled from the full exchange input"
        )
    return pre, part, group, tail


def exact_ops(ops: Sequence[dict], table: Table,
              rest: Sequence[Table] = ()) -> list:
    """The plan the single-device path runs in a mesh session's stead
    after ``faults.Degraded``: ``ops``, with the groups of a groupby the
    mesh stage ran behind its exchange re-partitioned by the same hash.
    A stable reorder of the groupby's key order by partition id is
    (pid, key): the order contract, whatever answers."""
    pre, part, group, tail = _check_supported(ops, table, rest)
    if group is None:
        return list(ops)
    return [*pre, part, group.op, group.again, *tail]


def _pack_sharded(table: Table, mesh, axis: str, n: int):
    """(padded sharded table, per-shard valid counts) for a contiguous
    row-block layout — the host-side pack step.

    A shard's physical width is the bucket (``buckets.bucket_for``) of
    ``ceil(n / size)``, so a stream of unequal batches packs to one
    shape a bucket and the stage programs keyed on it recur. Rows fill
    the shards in order, ``per`` to a shard; the last real shard is
    short and ``cnt`` carries every shard's real rows, so the in-order
    prefix gather is the same bytes whatever the width."""
    size = int(mesh.shape[axis])
    per = -(-n // size)  # ceil: contiguous row blocks, one per dev
    per = buckets.bucket_for(per) or per
    pad = per * size - n

    def padleaf(x):
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return jax.device_put(
            x, NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
        )

    pt = jax.tree_util.tree_map(padleaf, table)
    counts = np.clip(n - np.arange(size) * per, 0, per).astype(np.int32)
    cnt = jax.device_put(
        jnp.asarray(counts), NamedSharding(mesh, P(axis))
    )
    return pt, cnt


def _gather_prefix(out_t: Table, out_c, size: int) -> Table:
    """Host-side gather: each shard's valid prefix, in mesh order —
    exactly the single-device result for row-local segments.

    The result is HOST-backed: every leaf is a ``numpy`` array in the
    device storage dtype (FLOAT64 as its uint64 bits), filled shard by
    shard into one buffer of the kept rows. The stage's output is read
    from the chips here, once; the wire serialises these buffers as
    they are and a consumer that computes on them uploads at first use.
    """
    # srt: allow-host-sync(result materialization: the stage's output IS these host bytes)
    got = np.asarray(jax.device_get(out_c))
    per_out = out_t.row_count // size
    if metrics.enabled():
        # every shard is read whole; the prefixes are cut on the host
        metrics.counter_add("mesh.gather.rows_read", size * per_out)
        metrics.counter_add("mesh.gather.rows_kept", int(got.sum()))
    ends = np.cumsum(got)
    # every shard's transfer of every column starts before the first
    # read waits for one
    for x in jax.tree_util.tree_leaves(out_t):
        for s in x.addressable_shards:
            s.data.copy_to_host_async()

    def take(x):
        if x is None:
            return None
        out = np.empty((int(ends[-1]),) + x.shape[1:], x.dtype)
        for s in x.addressable_shards:
            # placed by the shard's own rows, so mesh order holds
            # whatever order the shards are listed in
            i = (s.index[0].start or 0) // per_out
            # srt: allow-host-sync(result materialization: gathering the sharded output to host)
            out[ends[i] - got[i]:ends[i]] = np.asarray(s.data)[:got[i]]
        return out

    return Table(
        [
            Column(take(c.data), c.dtype, take(c.validity), take(c.lengths))
            for c in out_t.columns
        ],
        names=out_t.names,
    )


def _stage_program(which: str, mesh, axis: str, pt, pre,
                   part: Optional[dict] = None, post=(),
                   cap: Optional[int] = None,
                   pair_cap: Optional[int] = None):
    """One mesh stage launch as a cached, jitted ``shard_map`` program
    (``buckets.cached_jit``): built on the first request of its shape,
    launched on every one after. ``which`` names it:

    * ``rowlocal`` ``(pt, cnt)`` -> ``(table, rows a shard)``: the
      whole row-local chain ``pre``;
    * ``counts`` ``(pt, cnt, splitters)`` -> ``(counts, recv, pair)``:
      the scan-side chain and the planned (src, dst-device) send
      counts, with what the host sizes the exchange from beside them
      (rows a destination receives, the hottest pair's rows) so that
      one read fetches both;
    * ``exchange`` ``(pt, cnt, counts, splitters)`` -> ``(table, rows a
      shard, worst overflow, rows a device holds behind the exchange as
      it counted them)``: scan-side chain, ragged exchange into
      ``cap`` rows a device (``pair_cap`` a pair, where the
      implementation shapes a buffer by it), stable pid sort,
      merge-side chain ``post``. Where ``post`` is the groupby that
      rides the exchange, its sort half: the table is then its sorted
      state (``ops.groupby.SortedGroups``) and the rows a shard its
      groups, as a fused segment with a groupby tail leaves them;
    * ``groupby`` ``(state, groups a shard)`` -> ``(table, rows a shard,
      rows kept)``: ``pt`` is that sorted state, ``pre`` the groupby,
      ``part`` the exchange's partition over the groupby's output
      (``_Behind.again``); its per-group half at ``cap`` candidate
      groups a device, the groups put in (pid, key) order where a
      device holds several of ``part``'s partitions, then the chain
      ``post``.

    The key holds everything static the body closes over: the op lists,
    ``pt``'s schema and physical rows, the mesh (axis, size, platform
    and device ids in mesh order: a degraded mesh is another program),
    the exchange implementation and the two capacities. Data never is:
    a range partition's ``splitters`` are sampled from the table and are
    an ARGUMENT (a tuple, empty for a hash partition), as ``counts`` is.
    Nothing is donated: the un-donated input is the stage's replay
    lineage."""
    from .shuffle import _ragged_impl, exchange_ragged

    size = int(mesh.shape[axis])
    impl = _ragged_impl(None) if which == "exchange" else None
    if impl == "ragged":
        # only the dense form shapes a buffer by the hottest pair
        pair_cap = None
    if which == "groupby":
        # a sorted state is no table: what ``bucketed._reduce_groups``
        # keys its half by stands for its schema and rows
        tables, shape = (), (
            pt.slots, buckets.table_signature(pt.keys),
            int(pt.perm.shape[0]),
        )
    else:
        tables, shape = (pt,), ()
    key = buckets.cache_key(
        "mesh." + which,
        {"pre": list(pre), "part": part, "post": list(post)},
        tables,
        (
            axis, size, mesh.devices.flat[0].platform,
            tuple(int(d.id) for d in mesh.devices.flat),
            impl, cap, pair_cap,
        ) + shape,
    )

    def build():
        from .. import plan as plan_mod
        from ..ops import partition as partition_mod

        def sharded(body, replicated=0):
            """``body(local table, its rows, *replicated)`` over the
            mesh; every result is sharded."""
            return shard_map(
                body, mesh=mesh,
                in_specs=(P(axis), P(axis)) + (P(),) * replicated,
                out_specs=P(axis), check_vma=False,
            )

        def rows(n):
            return jnp.reshape(n, (1,)).astype(jnp.int32)

        if which == "rowlocal":
            def rowlocal_body(local, c):
                t2, n2 = plan_mod._run_segment_traced(pre, local, c[0])
                return t2, rows(n2)

            return sharded(rowlocal_body)

        num = int(part["num"])
        keys = list(part.get("keys", []))
        hashed = part.get("kind", "hash") == "hash"

        def pids_of(local: Table, splitters):
            if hashed:
                return partition_mod.partition_ids_hash(
                    local, keys or None, num
                )
            return partition_mod.partition_ids_range(
                local, keys, splitters
            )

        def by_pid(t: Table, occ, splitters=()):
            """``t``'s occupied rows first, in stable partition-id
            order (padding keyed past every real pid)."""
            skey = jnp.where(
                occ, pids_of(t, splitters).astype(jnp.int32), num
            )
            perm = jnp.argsort(skey, stable=True).astype(jnp.int32)
            return jax.tree_util.tree_map(
                lambda x: None if x is None else x[perm], t
            )

        if which == "groupby":
            from ..ops.groupby import groupby_reduce

            def groupby_body(state, g):
                with jax.named_scope("srt.groupby"):
                    t = groupby_reduce(state, g[0], cap)
                if num > size:
                    # several partitions on this device: the groupby's
                    # key order -> (pid, key), the order contract
                    with jax.named_scope("srt.partition"):
                        t = by_pid(t, buckets.tail_valid(cap, g[0]))
                t3, n3 = plan_mod._run_segment_traced(post, t, g[0])
                return t3, rows(n3)

            reduce = sharded(groupby_body)

            def groupby_program(state, groups):
                t3, n3 = reduce(state, groups)
                return t3, n3, jnp.sum(n3)

            return groupby_program

        def scan(local, c, splitters):
            """Scan-side chain -> (table, which rows are real, the
            device each goes to)."""
            t2, n2 = plan_mod._run_segment_traced(pre, local, c[0])
            rv = jnp.arange(t2.row_count, dtype=jnp.int32) < n2
            dd = (pids_of(t2, splitters) * size) // num
            return t2, rv, dd.astype(jnp.int32)

        if which == "counts":
            def count_body(local, c, splitters):
                with jax.named_scope("srt.partition"):
                    _, rv, dd = scan(local, c, splitters)
                    dd = jnp.where(rv, dd, size)
                    return jnp.bincount(dd, length=size + 1)[:size].astype(
                        jnp.int32
                    )[None, :]

            count = sharded(count_body, replicated=1)

            def counts_program(packed, cnt, splitters):
                counts = count(packed, cnt, splitters)
                return counts, jnp.sum(counts, axis=0), jnp.max(counts)

            return counts_program

        def exchange_body(local, c, C, splitters):
            t2, rv, dd = scan(local, c, splitters)
            with jax.named_scope("srt.partition"):
                out, occ, overflow = exchange_ragged(
                    t2, dd, C, cap, axis, impl, row_valid=rv,
                    pair_capacity=pair_cap,
                )
                # restore the exact path's order: received rows arrive
                # in stable (src, in-src) order; a stable sort by
                # recomputed pid makes this device hold its contiguous
                # slice of the globally pid-sorted table
                sorted_t = by_pid(out, occ, splitters)
                n_recv = jnp.sum(occ.astype(jnp.int32))
            t3, n3 = plan_mod._run_segment_traced(post, sorted_t, n_recv)
            return t3, rows(n3), rows(overflow), rows(n_recv)

        exchange = sharded(exchange_body, replicated=2)

        def exchange_program(packed, cnt, counts, splitters):
            t3, n3, overflow, held = exchange(packed, cnt, counts, splitters)
            return t3, n3, jnp.max(overflow), held

        return exchange_program

    return buckets.cached_jit(key, build, "srt_mesh_" + which)


def _splitters_of(part: dict, table: Table) -> tuple:
    """A range partition's splitters, from the full host-side exchange
    input — the same deterministic sample the exact path draws, so
    partition ids agree byte-for-byte (the scan-side chain is empty,
    per ``_check_supported``); ``()`` for a hash partition."""
    if part.get("kind", "hash") != "range":
        return ()
    from ..ops import partition as partition_mod

    return tuple(partition_mod.range_splitters(
        table, list(part.get("keys", [])), int(part["num"])
    ))


def _counts_pass(pre, part, spl: tuple, mesh, axis: str, pt, cnt):
    """Scan-side chain + per-(src, dst-device) planned send counts —
    the two-phase sizing pass, a shuffle-site replay boundary. Returns
    the program's ``(counts, recv, pair)``, all still on the device."""
    fn = _stage_program("counts", mesh, axis, pt, pre, part)
    return run_collective(
        "plan.partition_counts", lambda: fn(pt, cnt, spl), site="shuffle"
    )


def _rowlocal_stage(seg_ops, table: Table, n: int, axis: str):
    """Stage closure for a pure row-local plan (no exchange boundary)."""

    def stage(mesh):
        # re-derived per replay: a smaller surviving mesh re-plans the
        # shard layout + per-shard valid counts from the same lineage
        size = int(mesh.shape[axis])
        with metrics.span("mesh.pack"):
            pt, cnt = _pack_sharded(table, mesh, axis, n)
        out_t, out_c = _stage_program(
            "rowlocal", mesh, axis, pt, seg_ops
        )(pt, cnt)
        # no read between the launch and the gather: the gather's first
        # device_get waits for the stage's device work
        with metrics.span("mesh.gather"):
            return _gather_prefix(out_t, out_c, size)

    return stage


def _partition_stage(pre, part, group, post, table: Table, n: int,
                     axis: str, prepared: Optional[dict] = None):
    """Stage closure for a plan with one partition boundary: scan-side
    chain -> counts pass -> ragged exchange -> stable pid sort ->
    merge-side chain, all re-derivable from the host-side lineage.
    With a ``group`` op behind the exchange (:func:`_check_supported`)
    the exchange program ends with its sort half and a second program
    reduces every device's groups and runs the chain ``post``
    (:func:`_groupby_stage`).

    ``prepared`` (from :func:`prepare_exchange`) carries a pack + counts
    pass already run for a specific mesh — reused only when the stage
    executes on that same mesh; any replay on a degraded mesh
    re-derives both.
    """
    from ..utils import config, planstats
    from .shuffle import (
        _round_capacity,
        check_overflow_compact,
        recv_capacity,
    )

    # data, so an argument of the programs; the same on any mesh
    spl = (
        prepared["splitters"] if prepared is not None
        else _splitters_of(part, table)
    )

    def stage(mesh):
        size = int(mesh.shape[axis])
        if (
            prepared is not None
            and prepared.get("mesh") is mesh
            and prepared.get("size") == size
        ):
            pt, cnt = prepared["pt"], prepared["cnt"]
            planned = prepared["counts"]
        else:
            with metrics.span("mesh.pack"):
                pt, cnt = _pack_sharded(table, mesh, axis, n)
            planned = None
        # the counts pass through its one read-back: device-ended
        with metrics.span("mesh.counts"):
            if planned is None:
                planned = _counts_pass(pre, part, spl, mesh, axis, pt, cnt)
            counts, *sizing = planned
            # observe (not split: a pure redistribution has no agg to
            # make salting lossless) planned recv skew across
            # destinations — the planstats drift surface for
            # partition-op plans, and the serving session's mesh_recv
            # srt: allow-host-sync(two-phase sizing: the planning pass exists to produce these host capacities and the skew observation)
            recv, hottest_pair = jax.device_get(sizing)
            cap = recv_capacity(int(recv.max()))
            pair_cap = _round_capacity(int(hottest_pair.max()))
        _LAST_EXCHANGE.plan = (recv, cap, pair_cap)
        if metrics.enabled():
            # what the program was built for against what it carries:
            # every device runs at `cap` rows, whatever it receives
            metrics.counter_add("mesh.exchange.slot_rows", size * cap)
            metrics.counter_add("mesh.exchange.recv_rows", int(recv.sum()))
        mean = float(recv.mean()) if recv.size else 0.0
        factor = float(config.get_flag("SKEW_SPLIT_FACTOR"))
        if mean > 0 and float(recv.max()) > factor * mean:
            metrics.counter_add("mesh.skew_observed")
            planstats.note_skew({
                "site": "plan.partition",
                "action": "observed",
                "max_recv": int(recv.max()),
                "mean_recv": mean,
                "ratio": float(recv.max()) / mean,
                "factor": factor,
                "devices": size,
            })

        fn = _stage_program(
            "exchange", mesh, axis, pt, pre, part,
            post if group is None else [group.op], cap, pair_cap,
        )
        # the exchange launch through the overflow read: device-ended
        with metrics.span("mesh.exchange"):
            out_t, out_c, out_ov, held = run_collective(
                "plan.partition_exchange",
                lambda: fn(pt, cnt, counts, spl),
                site="shuffle",
            )
            if group is not None:
                # the groupby's count read rides the overflow read, and
                # with it the rows every device's sort half was handed
                # srt: allow-host-sync(two-launch groupby: every device's group count sizes the per-group half, read once with the exchange's overflow)
                out_ov, groups, held = jax.device_get((out_ov, out_c, held))
            # capacity came from the real counts, so overflow means a
            # bug — surface it loudly rather than gathering a truncated
            # result
            check_overflow_compact(out_ov, cap, "plan partition")
        if metrics.enabled():
            metrics.counter_add("partition.mesh_segments")
            metrics.counter_add("partition.rows_exchanged", n)
        if group is not None:
            out_t, out_c = _groupby_stage(
                mesh, axis, group, post, out_t, out_c, groups,
                int(held.sum()),
            )
        with metrics.span("mesh.gather"):
            return _gather_prefix(out_t, out_c, size)

    return stage


def _groupby_stage(mesh, axis: str, group: _Behind, post, state, count,
                   groups, rows_in: int):
    """The per-group half of the groupby behind the exchange, then the
    chain ``post``, as one sharded launch -> (sharded table, rows a
    shard on the host). ``state`` is the exchange program's sorted
    state, ``count`` every device's group count as it left it and
    ``groups`` the same on the host, read already; ``rows_in`` the rows
    the devices counted behind the exchange (what the aggregate was
    handed, not what the counts pass planned). The program is built for
    the bucket of the LARGEST (at most the rows a device holds, as
    ``bucketed._reduce_groups`` sizes its half), so one program serves
    every device whatever it received."""
    size = int(mesh.shape[axis])
    k = bucketed.group_bucket(
        int(groups.max()), int(state.perm.shape[0]) // size
    )
    _LAST_EXCHANGE.plan = (*_LAST_EXCHANGE.plan, groups, k)
    if metrics.enabled():
        metrics.counter_add("mesh.groupby.stages")
        metrics.counter_add("mesh.groupby.rows_in", rows_in)
        metrics.counter_add("mesh.groupby.groups", int(groups.sum()))
        # every device reduces at `k` candidate groups, whatever it holds
        metrics.counter_add("mesh.groupby.slot_rows", size * k)
    fn = _stage_program(
        "groupby", mesh, axis, state, [group.op], group.again, post, cap=k
    )
    # the reduce launch through its count read: device-ended
    with metrics.span("mesh.groupby", rows=k):
        out_t, out_c, _ = fn(state, count)
        # srt: allow-host-sync(stage boundary: the rows each device kept size the gather that follows)
        return out_t, jax.device_get(out_c)


def run_plan_mesh(
    ops: Sequence[dict],
    table: Table,
    runner: MeshRunner,
    rest: Sequence[Table] = (),
) -> Table:
    """Run a row-local plan (optionally around one ``partition``
    boundary, with one groupby on its keys behind it) data-parallel
    over ``runner``'s mesh.

    Never consumes ``table`` (the un-donated input IS the replay
    lineage); returns the exact (unpadded) result table, HOST-backed:
    its leaves are the ``numpy`` buffers the gather filled, which the
    wire serialises as they are. A consumer that computes on the result
    uploads it at first use, implicitly: the same bytes, later. Raises
    :class:`MeshUnsupported` when the plan has no mesh path and
    :class:`~..utils.faults.Degraded` when the runner's ladder hits
    its device floor.
    """
    _LAST_EXCHANGE.plan = None
    pre, part, group, post = _check_supported(ops, table, rest)
    # a bucket-padded wire upload shrinks to its real rows first: the
    # mesh stage derives its own shard padding, and the caller's padded
    # input stays untouched (it is the fallback path's donation)
    table = buckets.unpad_table(table)
    n = int(table.row_count)
    axis = runner.axis
    if part is None:
        return runner.run_stage(
            "plan.mesh", _rowlocal_stage(list(ops), table, n, axis)
        )
    return runner.run_stage(
        "plan.mesh.partition",
        _partition_stage(pre, part, group, post, table, n, axis),
    )


def prepare_exchange(ops: Sequence[dict], table: Table,
                     runner: MeshRunner) -> Optional[dict]:
    """Stage the host-side pack + scan-side counts pass for ``table``
    at the runner's CURRENT mesh — the work ``run_plan_mesh_stream``
    overlaps with the previous batch's exchange launch. Returns the
    prepared dict ``_partition_stage`` consumes, or None when the plan
    has no partition boundary (nothing worth staging ahead)."""
    pre, part, _, _ = _check_supported(ops, table, ())
    if part is None:
        return None
    table = buckets.unpad_table(table)
    n = int(table.row_count)
    axis = runner.axis
    mesh = runner.mesh
    size = int(mesh.shape[axis])
    with metrics.span("mesh.pack"):
        pt, cnt = _pack_sharded(table, mesh, axis, n)
    spl = _splitters_of(part, table)
    return {
        "mesh": mesh, "size": size, "pt": pt, "cnt": cnt,
        "splitters": spl,
        "counts": _counts_pass(pre, part, spl, mesh, axis, pt, cnt),
    }


def run_plan_mesh_stream(
    ops: Sequence[dict],
    batches: Sequence[Table],
    runner: MeshRunner,
) -> list:
    """Drive ``batches`` through one plan with exchange/compute overlap.

    While batch N's exchange launch runs on the caller thread, batch
    N+1's scan-side counts pass and host-side pack run on the pipeline
    workers (``pipeline.stage_ahead``; worker busy time is metered as
    ``pipeline.overlap_ms``). With the pipeline off, batches run
    sequentially — byte-identical results either way, in input order.
    Degradation safety: a prepared pack targets the mesh it was staged
    for; if the runner degraded in between, the stage re-derives from
    the host-side lineage at the new size.
    """
    from .. import pipeline

    batches = list(batches)
    if not batches:
        return []
    pre, part, group, post = _check_supported(ops, batches[0], ())

    def prepare(b: Table):
        return (b, prepare_exchange(ops, b, runner))

    def execute(prepped):
        b, prepared = prepped
        t = buckets.unpad_table(b)
        n = int(t.row_count)
        axis = runner.axis
        if part is None:
            return runner.run_stage(
                "plan.mesh", _rowlocal_stage(list(ops), t, n, axis)
            )
        return runner.run_stage(
            "plan.mesh.partition",
            _partition_stage(pre, part, group, post, t, n, axis,
                             prepared=prepared),
        )

    return pipeline.stage_ahead(batches, prepare, execute, "mesh.prepare")
