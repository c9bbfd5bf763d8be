"""Shuffle exchange: the ICI all-to-all replacement for the RAPIDS
UCX/NCCL shuffle manager (SURVEY.md §2.5, §5.8).

``exchange`` is called *inside* ``shard_map``: each device buckets its
local rows by Spark-compatible partition id (pmod(murmur3)), packs them
into fixed-capacity per-destination send buffers, and one
``jax.lax.all_to_all`` moves every bucket to its owner over ICI. Fixed
capacity keeps shapes static for XLA (the shuffle-side instance of the
two-phase discipline); received padding is tracked with an occupancy mask
that downstream capped ops treat as absent rows.

``shuffle_table`` is the host-level wrapper: shard -> plan capacity
(exact per-(src,dst) counts, the generalization of the reference's
two-phase sizing, row_conversion.cu:505-511) -> shard_map(exchange)
-> globally sharded padded table + occupancy. The default path is
LOSSLESS: capacity is planned from the real counts, and any overflow
(possible only with an explicit undersized ``capacity``) raises
``ShuffleOverflowError`` instead of silently dropping rows.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..column import Column, Table
from ..ops.partition import partition_ids_hash
from ..utils import faults, flight, metrics, profiler
from .mesh import SHUFFLE_AXIS, shard_map, shard_table
from .tolerant import run_collective


class ShuffleOverflowError(faults.PermanentError):
    """An exchange received more rows for a (src, dst) pair than its
    static capacity — rows would have been dropped. Raised by the host
    wrappers; never silent.

    Typed as :class:`~..utils.faults.PermanentError`: a replay at the
    same capacity overflows identically, so retry/breaker accounting
    must not treat it as transient (``faults.retryable_class`` is False
    and the breaker ignores it). Still a ``RuntimeError`` subclass via
    ``FaultError`` for existing callers."""


def validate_on_overflow(on_overflow: str) -> None:
    """Shared host-wrapper argument check: typos must not silently
    disable overflow detection."""
    if on_overflow not in ("raise", "allow"):
        raise ValueError(
            f"on_overflow must be 'raise' or 'allow', got {on_overflow!r}"
        )


def check_overflow(
    overflow,
    capacity: int,
    what: str,
    unit: str = "rows per (src, dst) pair",
    remedy: str = "pass capacity=None to auto-plan",
) -> None:
    """Raise ``ShuffleOverflowError`` if any device reported overflow."""
    # srt: allow-host-sync(lossless-exchange verdict: the overflow check exists to block until the counts land)
    worst = int(jnp.max(overflow))
    if worst > 0:
        raise ShuffleOverflowError(
            f"{what} exchange capacity {capacity} undersized by {worst} "
            f"{unit}; {remedy}"
        )


def check_overflow_compact(overflow, out_size: int, what: str) -> None:
    """Overflow check for the ragged-compact exchange, whose capacity is
    the TOTAL per-device receive buffer (not a per-pair slot count)."""
    check_overflow(
        overflow,
        out_size,
        what,
        unit="rows in the per-device receive buffer",
        remedy="pass out_size=None / capacity=None to auto-plan",
    )


def partition_counts(
    sharded: Table,
    columns: Optional[Sequence[Union[int, str]]],
    mesh: Mesh,
    axis: str = SHUFFLE_AXIS,
) -> jax.Array:
    """(num, num) per-(src, dst) row counts — the shuffle planning pass.

    Row [s, d] is how many of source s's rows hash to partition d. The
    max entry is the exact minimal per-pair exchange capacity.
    """
    num = int(mesh.shape[axis])

    def body(local: Table):
        dest = partition_ids_hash(local, columns, num)
        return jnp.bincount(dest, length=num).astype(jnp.int32)[None, :]

    fn = shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    # the counts matrix IS the lineage for everything downstream: its
    # launch gets the same replay boundary as the exchange itself
    return run_collective(
        "shuffle.partition_counts", lambda: fn(sharded), site="shuffle"
    )


def _round_capacity(exact: int) -> int:
    """Round a planned capacity up to the next power of two (min 16) so
    repeated shuffles of similar volume reuse one compiled executable."""
    cap = 16
    while cap < exact:
        cap *= 2
    return cap


def plan_capacity(
    sharded: Table,
    columns: Optional[Sequence[Union[int, str]]],
    mesh: Mesh,
    axis: str = SHUFFLE_AXIS,
) -> int:
    """Exact-overflow-free exchange capacity for ``sharded`` (host sync)."""
    with metrics.span("shuffle.plan"):
        counts = partition_counts(sharded, columns, mesh, axis)
        # srt: allow-host-sync(two-phase sizing: the planning pass exists to produce this host capacity)
        cap = _round_capacity(int(jnp.max(counts)))
    if metrics.enabled():
        metrics.counter_add("shuffle.plans")
        metrics.gauge_set("shuffle.pair_capacity", cap)
    return cap


def exchange(
    local: Table,
    dest: jax.Array,
    num_partitions: int,
    capacity: int,
    axis: str = SHUFFLE_AXIS,
    row_valid: Optional[jax.Array] = None,
):
    """All-to-all one device's rows to their destination partitions.

    Must run inside ``shard_map`` over ``axis`` (axis size ==
    ``num_partitions``). Returns (received table padded to
    ``num_partitions * capacity`` rows, occupancy mask, overflow counts):
    rows beyond ``capacity`` per (src, dst) pair are DROPPED — callers
    size ``capacity`` from the partitioning stats and must check
    ``overflow`` (max per-dest count) when in doubt.
    """
    n = local.row_count
    ok = (
        row_valid
        if row_valid is not None
        else jnp.ones((n,), dtype=jnp.bool_)
    )
    # invalid rows -> bucket num_partitions (beyond every real partition)
    dest = jnp.where(ok, dest, num_partitions).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)
    counts = jnp.bincount(dest, length=num_partitions + 1)[
        :num_partitions
    ].astype(jnp.int32)
    start = jnp.cumsum(counts) - counts

    j = jnp.arange(capacity, dtype=jnp.int32)
    flat_idx = jnp.clip(start[:, None] + j[None, :], 0, max(n - 1, 0))
    idx = order[flat_idx]  # (P, cap) source row per slot
    slot_valid = j[None, :] < jnp.minimum(counts[:, None], capacity)

    def pack(x):
        if x is None:
            return None
        return x[idx]  # (P, cap, ...)

    send = jax.tree_util.tree_map(pack, local)
    recv = jax.tree_util.tree_map(
        lambda x: None
        if x is None
        else jax.lax.all_to_all(x, axis, 0, 0),
        send,
    )
    recv_valid = jax.lax.all_to_all(slot_valid, axis, 0, 0)

    def flatten(x):
        if x is None:
            return None
        return x.reshape((num_partitions * capacity,) + x.shape[2:])

    out = jax.tree_util.tree_map(flatten, recv)
    occupancy = recv_valid.reshape((num_partitions * capacity,))
    overflow = jnp.max(counts) - capacity  # > 0 => rows were dropped
    return out, occupancy, overflow


def exchange_by_hash(
    local: Table,
    columns: Optional[Sequence[Union[int, str]]],
    num_partitions: int,
    capacity: int,
    axis: str = SHUFFLE_AXIS,
    row_valid: Optional[jax.Array] = None,
):
    """exchange() keyed by Spark hash partitioning of ``columns``."""
    dest = partition_ids_hash(local, columns, num_partitions)
    return exchange(local, dest, num_partitions, capacity, axis, row_valid)


def total_recv_capacity(counts) -> int:
    """Per-device compact-exchange buffer size: the max over destinations
    of the TOTAL rows received (host sync), rounded. This is the SPMD
    floor — under a static-shape SPMD program every device materializes
    the same output shape, so the best possible per-device buffer is the
    hottest destination's actual row total, NOT num_partitions x the
    hottest (src, dst) pair (the round-2 skew-OOM failure mode)."""
    # srt: allow-host-sync(two-phase sizing: the planning pass exists to produce this host capacity)
    return recv_capacity(int(jnp.max(jnp.sum(counts, axis=0))))


def recv_capacity(max_recv: int) -> int:
    """:func:`total_recv_capacity` of a hottest destination's row total
    that is on the host already (the mesh stage's counts program returns
    it beside the counts)."""
    cap = _round_capacity(max_recv)
    if metrics.enabled():
        metrics.counter_add("shuffle.plans")
        metrics.gauge_set("shuffle.recv_capacity", cap)
    return cap


class SkewPlan:
    """The adaptive-skew decision from the planning counts (ISSUE 17).

    ``engaged`` means at least one destination's planned recv total
    exceeds ``factor x`` the mean — the Spark AQE skew-join-split
    signal, read here from the same two-phase counts the capacity
    sizing already computes. ``k`` is the salt fan-out: hot keys spread
    across ``k`` sub-partitions, sized so each carries roughly a mean
    destination's rows.
    """

    __slots__ = ("engaged", "factor", "k", "hot", "max_recv", "mean_recv")

    def __init__(self, engaged, factor, k, hot, max_recv, mean_recv):
        self.engaged = engaged
        self.factor = factor
        self.k = k
        self.hot = tuple(hot)
        self.max_recv = max_recv
        self.mean_recv = mean_recv

    @property
    def ratio(self) -> float:
        return (
            self.max_recv / self.mean_recv if self.mean_recv > 0 else 0.0
        )

    def to_doc(self) -> dict:
        return {
            "engaged": self.engaged,
            "factor": self.factor,
            "k": self.k,
            "hot_destinations": list(self.hot),
            "max_recv": self.max_recv,
            "mean_recv": self.mean_recv,
            "ratio": self.ratio,
        }


def plan_skew(counts, factor: Optional[float] = None) -> SkewPlan:
    """Skew decision for a planned exchange (host sync, planning pass).

    ``counts`` is the (P, P) per-(src, dst) matrix from
    :func:`partition_counts`. Destinations whose planned recv totals
    (column sums) exceed ``factor x`` the mean are hot; ``factor``
    defaults to the ``SKEW_SPLIT_FACTOR`` flag and the whole machinery
    gates on the ``SKEW_SPLIT`` master switch.
    """
    import numpy as np

    from ..utils import config

    if factor is None:
        factor = float(config.get_flag("SKEW_SPLIT_FACTOR"))
    raw = config.get_flag("SKEW_SPLIT")
    # test overrides arrive unparsed ("0" must read as off, like the env)
    split_on = config._as_bool(raw) if isinstance(raw, str) else bool(raw)
    # srt: allow-host-sync(two-phase sizing: the skew decision is part of the planning pass)
    recv = np.asarray(jax.device_get(jnp.sum(counts, axis=0))).astype(
        np.int64
    )
    num = int(recv.shape[0])
    total = int(recv.sum())
    max_recv = int(recv.max()) if recv.size else 0
    mean = total / num if num else 0.0
    if not split_on or num < 2 or total == 0:
        return SkewPlan(False, factor, 1, (), max_recv, mean)
    hot = [int(d) for d in np.nonzero(recv > factor * mean)[0]]
    if not hot:
        return SkewPlan(False, factor, 1, (), max_recv, mean)
    k = int(min(num, max(2, -(-max_recv // max(int(mean), 1)))))
    if metrics.enabled():
        metrics.gauge_set("shuffle.skew_k", k)
        metrics.gauge_set("shuffle.skew_hot_destinations", len(hot))
    return SkewPlan(True, factor, k, hot, max_recv, mean)


def _ragged_impl(impl: Optional[str]) -> str:
    """Resolve the exchange implementation for the active backend.

    ``ragged`` is the TPU path: one ``jax.lax.ragged_all_to_all``
    collective moving exactly the real rows over ICI. XLA:CPU does not
    implement ragged-all-to-all, so the virtual-mesh test tier uses
    ``dense_compact``: a uniform ``all_to_all`` at per-pair capacity
    followed by an on-device compaction to the identical ragged layout
    (same rows, same order — the impls are interchangeable oracle-wise).
    """
    if impl is not None:
        if impl not in ("ragged", "dense_compact"):
            raise ValueError(f"unknown exchange impl {impl!r}")
        return impl
    from ..kernels import on_tpu

    return "ragged" if on_tpu() else "dense_compact"


def exchange_ragged(
    local: Table,
    dest: jax.Array,
    counts: jax.Array,
    out_size: int,
    axis: str = SHUFFLE_AXIS,
    impl: str = "dense_compact",
    row_valid: Optional[jax.Array] = None,
    pair_capacity: Optional[int] = None,
):
    """Compact all-to-all: each device receives exactly its real rows.

    Must run inside ``shard_map`` over ``axis``. ``counts`` is the global
    (P, P) per-(src, dst) row-count matrix from :func:`partition_counts`
    (replicated). The received layout is ragged-compact: ``[src-0 rows |
    src-1 rows | ...]`` with all padding at the tail — so the per-device
    buffer is ``out_size`` rows total (sized by
    :func:`total_recv_capacity`), not ``P x pair_capacity``. Returns
    (compact table padded to ``out_size`` rows, occupancy mask,
    overflow = rows received beyond ``out_size``).
    """
    num = counts.shape[0]
    s = jax.lax.axis_index(axis)
    n = local.row_count
    ok = (
        row_valid
        if row_valid is not None
        else jnp.ones((n,), dtype=jnp.bool_)
    )
    dest = jnp.where(ok, dest, num).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True).astype(jnp.int32)
    csort = jax.tree_util.tree_map(
        lambda x: None if x is None else x[order], local
    )

    C = counts.astype(jnp.int32)
    send_sizes = C[s]  # (P,)
    input_offsets = jnp.cumsum(send_sizes) - send_sizes
    # receiver d lays out sender blocks in src order: sender s's block
    # starts at sum_{s'<s} C[s', d]
    output_offsets_all = jnp.cumsum(C, axis=0) - C  # (src, dst)
    output_offsets = output_offsets_all[s]
    recv_sizes = C[:, s]
    n_recv = jnp.sum(recv_sizes)

    if impl == "ragged":
        # clamp so an explicit undersized out_size can never write out of
        # bounds; the dropped tail is reported via overflow and raised by
        # the host wrappers
        off_c = jnp.minimum(output_offsets, out_size)
        send_c = jnp.minimum(send_sizes, jnp.maximum(out_size - off_c, 0))
        recv_off = jnp.minimum(output_offsets_all[:, s], out_size)
        recv_c = jnp.minimum(
            recv_sizes, jnp.maximum(out_size - recv_off, 0)
        )

        def ragged(wire):
            out = jnp.zeros((out_size,) + wire.shape[1:], wire.dtype)
            return jax.lax.ragged_all_to_all(
                wire, out, input_offsets, send_c, off_c, recv_c,
                axis_name=axis,
            )

        def ex(x):
            if x is None:
                return None
            if x.dtype == jnp.bool_:
                return ragged(x.astype(jnp.uint8)).astype(x.dtype)
            if x.dtype.itemsize == 8:
                # the TPU compiler has no 64-bit ragged-all-to-all ("X64
                # element types ... rewriting is not implemented"): the
                # rows travel as (n, 2) u32 words, one collective
                w = jax.lax.bitcast_convert_type(x, jnp.uint32)
                return jax.lax.bitcast_convert_type(ragged(w), x.dtype)
            return ragged(x)

        out_tbl = jax.tree_util.tree_map(ex, csort)
        occupancy = jnp.arange(out_size, dtype=jnp.int32) < n_recv
        overflow = n_recv - out_size
        return out_tbl, occupancy, overflow

    # dense_compact: uniform all_to_all at per-pair capacity, then an
    # on-device compaction to the identical ragged layout (CPU test
    # tier). The transient (P, pair_cap) buffers shrink to the real
    # hottest-pair count when the host wrapper threads it through
    # (pair_capacity from the planning counts); out_size is only the
    # always-correct fallback bound.
    pair_cap = min(pair_capacity or out_size, out_size)
    j = jnp.arange(pair_cap, dtype=jnp.int32)
    start = input_offsets
    flat_idx = jnp.clip(start[:, None] + j[None, :], 0, max(n - 1, 0))
    idx = order[flat_idx]
    slot_valid = j[None, :] < jnp.minimum(send_sizes[:, None], pair_cap)

    def pack(x):
        if x is None:
            return None
        return x[idx]

    send = jax.tree_util.tree_map(pack, local)
    recv = jax.tree_util.tree_map(
        lambda x: None if x is None else jax.lax.all_to_all(x, axis, 0, 0),
        send,
    )
    recv_valid = jax.lax.all_to_all(slot_valid, axis, 0, 0)  # (P, cap)
    # compact: flatten in src order, stable-partition valid slots first.
    # With a tight pair_capacity the slot grid (num * pair_cap) can be
    # SMALLER than out_size — pad the index; the padded tail is masked
    # to zeros by occupancy below (n_recv <= num * pair_cap always).
    flat_valid = recv_valid.reshape(-1)
    comp = jnp.argsort(~flat_valid, stable=True).astype(jnp.int32)
    slots = num * pair_cap
    if slots < out_size:
        comp = jnp.pad(comp, (0, out_size - slots))
    else:
        comp = comp[:out_size]
    occupancy = jnp.arange(out_size, dtype=jnp.int32) < n_recv

    def compact(x):
        if x is None:
            return None
        flat = x.reshape((num * pair_cap,) + x.shape[2:])
        g = flat[comp]
        pad_shape = (1,) * (g.ndim - 1)
        m = occupancy.reshape((out_size,) + pad_shape)
        return jnp.where(m, g, jnp.zeros_like(g))

    out_tbl = jax.tree_util.tree_map(compact, recv)
    overflow = n_recv - out_size
    return out_tbl, occupancy, overflow


def exchange_ragged_by_hash(
    local: Table,
    columns: Optional[Sequence[Union[int, str]]],
    counts: jax.Array,
    out_size: int,
    axis: str = SHUFFLE_AXIS,
    impl: str = "dense_compact",
    row_valid: Optional[jax.Array] = None,
    pair_capacity: Optional[int] = None,
):
    """:func:`exchange_ragged` keyed by Spark hash partitioning."""
    dest = partition_ids_hash(local, columns, counts.shape[0])
    return exchange_ragged(
        local, dest, counts, out_size, axis, impl, row_valid,
        pair_capacity,
    )


@metrics.traced("shuffle.table_compact")
def shuffle_table_compact(
    table: Table,
    columns: Optional[Sequence[Union[int, str]]],
    mesh: Mesh,
    out_size: Optional[int] = None,
    axis: str = SHUFFLE_AXIS,
    impl: Optional[str] = None,
    on_overflow: str = "raise",
    donate_input: bool = False,
):
    """Host-level compact shuffle: plan counts, ragged-exchange the rows.

    Unlike :func:`shuffle_table` (uniform per-pair capacity, received
    shape ``P x capacity``), the received buffer is ``out_size`` rows
    total per device — the hottest destination's REAL row total (rounded)
    — so correlated skew (e.g. pre-sorted input where one source feeds
    one destination) no longer inflates every device's allocation by a
    factor of P. Returns (sharded compact table, occupancy, overflow).

    Fault tolerance: the exchange launch is a ``shuffle``-site replay
    boundary — the sharded input + planned counts captured here are the
    lineage, so a transient failure re-runs ONLY this exchange.
    ``donate_input=True`` declares the caller's buffers consumed by the
    exchange and makes it at-most-once (zero retries, PR 10's
    doomed-replay rule).
    """
    metrics.counter_add("shuffle.exchanges")
    metrics.counter_add("shuffle.rows_exchanged", table.row_count)
    profiler.note_shuffle(table.row_count)
    if flight.enabled():
        flight.record("I", "shuffle.exchange", table.row_count)
    validate_on_overflow(on_overflow)
    impl = _ragged_impl(impl)
    sharded = shard_table(table, mesh, axis)
    counts = partition_counts(sharded, columns, mesh, axis)
    size = out_size or total_recv_capacity(counts)
    # srt: allow-host-sync(two-phase sizing: the planning pass exists to produce this host capacity)
    pair_cap = _round_capacity(int(jnp.max(counts)))

    def run(local, C):
        out, occ, overflow = exchange_ragged_by_hash(
            local, columns, C, size, axis, impl,
            pair_capacity=pair_cap,
        )
        return out, occ, overflow[None]

    fn = shard_map(
        run,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    out, occ, overflow = run_collective(
        "shuffle.table_compact", lambda: fn(sharded, counts),
        site="shuffle", donated=donate_input,
    )
    if on_overflow == "raise":
        check_overflow_compact(overflow, size, "compact shuffle")
    return out, occ, overflow


@metrics.traced("shuffle.table")
def shuffle_table(
    table: Table,
    columns: Optional[Sequence[Union[int, str]]],
    mesh: Mesh,
    capacity: Optional[int] = None,
    axis: str = SHUFFLE_AXIS,
    on_overflow: str = "raise",
    donate_input: bool = False,
):
    """Host-level shuffle: row-shard ``table`` and hash-exchange it.

    Returns (globally sharded padded table, occupancy column, overflow).
    ``capacity=None`` (the default) runs the planning pass and sizes the
    exchange exactly — no row can ever be dropped. An explicit capacity
    skips planning; if it turns out undersized, ``on_overflow="raise"``
    (default) raises ``ShuffleOverflowError``; ``"allow"`` opts into the
    caller checking the returned overflow counts itself.

    Fault tolerance: the exchange launch is a ``shuffle``-site replay
    boundary — the sharded input + partition spec captured here are the
    lineage, so a transient failure re-runs ONLY this exchange (never
    upstream work). ``donate_input=True`` declares the caller's buffers
    consumed by the exchange and makes it at-most-once (zero retries,
    PR 10's doomed-replay rule).
    """
    metrics.counter_add("shuffle.exchanges")
    metrics.counter_add("shuffle.rows_exchanged", table.row_count)
    profiler.note_shuffle(table.row_count)
    if flight.enabled():
        flight.record("I", "shuffle.exchange", table.row_count)
    validate_on_overflow(on_overflow)
    num = int(mesh.shape[axis])
    sharded = shard_table(table, mesh, axis)
    if capacity is None:
        capacity = plan_capacity(sharded, columns, mesh, axis)

    def run(local):
        out, occ, overflow = exchange_by_hash(
            local, columns, num, capacity, axis
        )
        return out, occ, overflow[None]

    fn = shard_map(
        run, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    out, occ, overflow = run_collective(
        "shuffle.table", lambda: fn(sharded),
        site="shuffle", donated=donate_input,
    )
    if on_overflow == "raise":
        check_overflow(overflow, capacity, "shuffle")
    return out, occ, overflow
