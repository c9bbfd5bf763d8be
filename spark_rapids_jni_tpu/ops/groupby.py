"""Group-by aggregation (cudf ``groupby``), sort-based.

TPU has no device-wide atomic hash-table idiom (SURVEY.md §7 hard part 1),
so aggregation is sort-based: normalize keys (ops/keys.py) -> stable
lexsort -> segment boundaries -> XLA segment reductions (which lower to
sorted scatter-adds, efficient on TPU). Null keys form their own group,
like Spark/cudf.

Two forms (see ops/__init__ docstring): ``groupby_aggregate`` host-syncs
the group count; ``groupby_aggregate_capped`` is fully jittable with
``num_segments`` as the static capacity — ``groupby_sort`` (everything
at the input's row count) then ``groupby_reduce`` (everything per
group, ``num_segments`` wide) in one trace; the served runners launch
the two apart and size the second from the group count
(bucketed.py).

Design note — string keys are NOT auto-dictionary-encoded here (unlike
joins, ops/join.py): encoding costs a full-width sort of its own, the
very pass this groupby already performs once, so for a one-shot
aggregation it can only add work. Joins amortize the encode across the
build sort plus 2·log(m) binary-search passes, where one int32 word vs
pad/8+1 words pays for itself.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtype as dt
from ..column import Column, Table
from . import compute
from . import keys as keys_mod
from .gather import gather_table

_AGG_OPS = {
    "sum", "count", "min", "max", "mean", "variance", "std",
    "collect_list", "collect_set", "nunique", "first", "last",
}
_COLLECT_OPS = {"collect_list", "collect_set"}


@dataclasses.dataclass(frozen=True)
class GroupbyAgg:
    """One aggregation: (value column, op, output name).

    ``list_capacity`` is the static per-group element capacity for
    ``collect_list``/``collect_set`` outputs (the LIST pad width) in the
    jittable capped API — groups with more elements are truncated to it
    (the caller owns the capacity, like every ``*_capped`` API); the
    eager API sizes it from the largest group automatically."""

    column: Union[int, str]
    op: str
    name: Optional[str] = None
    list_capacity: Optional[int] = None

    def __post_init__(self):
        if self.op not in _AGG_OPS:
            raise ValueError(f"unknown aggregation {self.op!r}")


def _key_words(key_cols: Sequence[Column], row_valid):
    """The sort's key words, most significant first, and the value from
    which the first word belongs to a padding row.

    The key is a tuple of bit fields: the occupancy bit (``row_valid``
    excludes rows entirely: padding sorts behind every real row), then
    for each key column its validity bit, if it has one (null keys group
    together, and a null's payload must not split the group), and its
    order words. Consecutive fields are folded into one word while they
    fit 64 bits, and a word of at most 32 bits is a u32: two INT8 keys
    and the occupancy bit make ONE u32 word where they were three u64
    words, each an operand and a 64-bit compare of every sort pass (the
    TPU compiler's time on a 2^23-row sort grows faster than the operand
    count: PERF.md, PR 27). A 64-bit key's own words are what they
    always were."""
    fields: list[tuple[jax.Array, int]] = []
    if row_valid is not None:
        # invalid rows last: 0 for valid, 1 for padding
        fields.append((jnp.where(row_valid, jnp.uint64(0), jnp.uint64(1)), 1))
    for c in key_cols:
        order = keys_mod.column_order_fields(c)
        if c.validity is not None:
            fields.append((c.validity.astype(jnp.uint64), 1))
            order = [
                (jnp.where(c.validity, w, jnp.uint64(0)), b) for w, b in order
            ]
        fields.extend(order)
    packed: list[list] = []  # [[fields of one word]]
    for f in fields:
        if packed and sum(b for _, b in packed[-1]) + f[1] <= 64:
            packed[-1].append(f)
        else:
            packed.append([f])
    words = []
    for group in packed:
        w = group[0][0]
        for v, b in group[1:]:  # first field in the high bits
            w = (w << jnp.uint64(b)) | v
        narrow = sum(b for _, b in group) <= 32
        words.append(w.astype(jnp.uint32) if narrow else w)
    first_bits = sum(b for _, b in packed[0])
    occupied_from = jnp.asarray(1 << (first_bits - 1), words[0].dtype)
    return words, occupied_from


def _segment_ids(
    key_cols: Sequence[Column],
    row_valid: Optional[jax.Array] = None,
    payload: Sequence[jax.Array] = (),
):
    """(perm, seg_ids, num_groups_device, sorted_payload): stable sort +
    boundary scan.

    ``row_valid`` excludes rows entirely (shuffle-padding occupancy): the
    leading occupancy bit sorts them behind every real row, where their
    garbage keys may split into any number of trailing segments; the group
    count is therefore the highest segment id holding a valid row.

    The ``payload`` arrays ride through the variadic sort as non-key
    operands.
    """
    words, occupied_from = _key_words(key_cols, row_valid)
    # one variadic stable sort carries the iota along, yielding the
    # sorted key words AND the permutation together — no post-sort
    # re-gather of each word (jnp.lexsort would return only the perm)
    n_rows = words[0].shape[0]
    iota = jnp.arange(n_rows, dtype=jnp.int32)
    sorted_all = jax.lax.sort(
        tuple(words) + (iota,) + tuple(payload),
        num_keys=len(words),
    )
    sorted_words = list(sorted_all[: len(words)])
    perm = sorted_all[len(words)]
    sorted_payload = list(sorted_all[len(words) + 1 :])
    boundary = jnp.zeros(perm.shape, dtype=jnp.bool_).at[0].set(True)
    for w in sorted_words:
        boundary = boundary | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), w[1:] != w[:-1]]
        )
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    if row_valid is not None:
        # Padding rows sort behind every real row (leading occupancy bit)
        # but can form any number of trailing garbage segments — the real
        # group count is the highest segment id holding a valid row.
        # Sorted validity is the top bit of the first sorted key word
        # (the occupancy field), so it neither rides the sort nor pays
        # a gather.
        rv_sorted = sorted_words[0] < occupied_from
        num_groups = jnp.max(jnp.where(rv_sorted, seg + 1, 0))
    else:
        num_groups = seg[-1] + 1
    return perm, seg, num_groups, sorted_payload


def _segment_bounds(seg, num_segments: int):
    """Per-segment [start, end) row ranges via binary search over the
    (sorted, nondecreasing) segment-id vector — the TPU replacement for
    scatter-based segment lookups (XLA lowers ``jax.ops.segment_*`` to
    device scatters, serial-ish on TPU: ~1.5 s at 16M rows on a v5e).

    Each search is ~log2(n) rounds of ``num_segments``-wide random
    gathers, and a random gather runs at ~10 M elements/s on a v5e: at
    ``num_segments`` = n = 2^23 one search took 1.4-1.7 s and at 2^20
    0.16 s (ledger, PR 25); at ``num_segments`` = 2^13 over n = 2^23 it
    takes 1.4 ms (chip run, PR 26). The cost is in ``num_segments``, so
    the served runners pass the bucket of the group count
    (:func:`groupby_reduce`), not of the input."""
    ids = jnp.arange(num_segments, dtype=seg.dtype)
    starts = jnp.searchsorted(seg, ids, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(seg, ids, side="right").astype(jnp.int32)
    return starts, ends


def _sorted_segment_sum(masked_vals, starts, ends):
    """Segment sums of a row-sorted vector as cumsum differences.

    ``total[s] = c[end-1] - c[start-1]`` with ``c = cumsum(vals)``.
    For integer accumulators this is EXACT even if the running cumsum
    wraps: two's-complement overflow cancels in the subtraction. For
    floats XLA computes the cumsum as a log-depth associative scan, so
    rounding error grows O(log n), comparable to a tree reduction."""
    n = masked_vals.shape[0]
    c = jnp.cumsum(masked_vals)
    hi = c[jnp.clip(ends - 1, 0, max(n - 1, 0))]
    lo = jnp.where(
        starts > 0, c[jnp.clip(starts - 1, 0, max(n - 1, 0))], 0
    )
    return jnp.where(ends > starts, hi - lo, 0)


def _sorted_segment_extreme(masked_vals, seg, ends, is_min: bool):
    """Per-segment min/max of a row-sorted vector via one segmented
    associative scan (log-depth, fully vectorized — no scatter): the
    running extreme resets at segment boundaries, and the value at each
    segment's last row is the segment's extreme."""
    n = masked_vals.shape[0]

    def combine(a, b):
        s1, m1 = a
        s2, m2 = b
        same = s1 == s2
        ext = jnp.minimum(m1, m2) if is_min else jnp.maximum(m1, m2)
        return s2, jnp.where(same, ext, m2)

    _, scanned = jax.lax.associative_scan(combine, (seg, masked_vals))
    return scanned[jnp.clip(ends - 1, 0, max(n - 1, 0))]


def _valid_rank_rows(valid_sorted, starts, ranks):
    """Scatter-free within-segment compaction core: the sorted-row index
    of each segment's r-th VALID row, found by binary search over the
    running valid count (rank r lives at the first row where
    cumsum(valid) reaches base + r). ``ranks`` is (num_segments, k);
    out-of-range ranks clip to arbitrary rows — masking is the
    caller's job via per-segment valid counts."""
    n = valid_sorted.shape[0]
    cvalid = jnp.cumsum(valid_sorted.astype(jnp.int32))
    base = jnp.where(
        starts > 0, cvalid[jnp.clip(starts - 1, 0, max(n - 1, 0))], 0
    )
    target = base[:, None] + ranks
    row_idx = jnp.searchsorted(cvalid, target.reshape(-1), side="left")
    return (
        jnp.clip(row_idx, 0, max(n - 1, 0))
        .astype(jnp.int32)
        .reshape(target.shape)
    )


def _nth_valid_gather(vals_sorted, valid_sorted, starts, pad: int):
    """The value of the j-th VALID row of each segment, j = 1..pad."""
    ranks = jnp.arange(1, pad + 1, dtype=jnp.int32)[None, :]
    rows = _valid_rank_rows(valid_sorted, starts, ranks)
    return vals_sorted[rows]


def _first_occurrence(dtype, seg, vals_sorted, valid_sorted):
    """Value-sort rows within each segment and mark the first occurrence
    of each distinct valid value (the shared core of collect_set and
    nunique). Returns (resorted values, first-occurrence mask)."""
    # vals are arithmetic values (FLOAT64 decoded from bits): re-encode
    # to storage before order-keying, which expects the bit layout
    tmp = Column(compute.encode_values(vals_sorted, dtype), dtype, None)
    vword = keys_mod.column_order_keys(tmp)[0]
    # valid rows first within the segment (stable), then by value
    inval = jnp.where(valid_sorted, jnp.uint64(0), jnp.uint64(1))
    seg2, _, vword2, vals2, valid2 = jax.lax.sort(
        (seg, inval, vword, vals_sorted, valid_sorted), num_keys=3
    )
    new_seg = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), seg2[1:] != seg2[:-1]]
    )
    new_val = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), vword2[1:] != vword2[:-1]]
    )
    return vals2, valid2 & (new_seg | new_val)


def _collect_segment(
    dtype: dt.DType,
    op: str,
    pad: int,
    seg,
    vals_sorted,
    valid_sorted,
    starts,
    ends,
) -> Column:
    """collect_list / collect_set -> LIST column of (num_segments, pad)
    child values + per-group lengths. Nulls are dropped (Spark
    collect_list/collect_set semantics); collect_set returns each
    group's distinct values in ascending order (deterministic; cudf
    leaves set order unspecified)."""
    from ..column import _LIST_CHILD_IDS

    if dtype.id not in _LIST_CHILD_IDS:
        raise TypeError(
            f"{op} not supported for {dtype} (LIST children are "
            "int8..64, uint8..64, float32, bool)"
        )
    if op == "collect_set":
        vals_sorted, valid_sorted = _first_occurrence(
            dtype, seg, vals_sorted, valid_sorted
        )
    counts = _sorted_segment_sum(
        valid_sorted.astype(jnp.int32), starts, ends
    )
    lens = jnp.minimum(counts, pad).astype(jnp.int32)
    mat = _nth_valid_gather(vals_sorted, valid_sorted, starts, pad)
    slot_ok = jnp.arange(pad, dtype=jnp.int32)[None, :] < lens[:, None]
    # typed zero: a bare 0 would promote BOOL8 children to int64 and
    # misreport list_child_dtype
    mat = jnp.where(slot_ok, mat, jnp.zeros((), mat.dtype))
    return Column(mat, dt.DType(dt.TypeId.LIST), None, lens)


def _aggregate_segment(
    dtype: dt.DType,
    op: str,
    seg,
    bounds,
    vals,
    valid,
    list_capacity: Optional[int] = None,
) -> Column:
    """One aggregation over sorted segments: ``vals`` / ``valid`` are
    the value column (a (lo, hi) limb pair for DECIMAL128) and its mask
    in SORTED row order, ``bounds`` the ``(starts, ends)`` of the
    ``num_segments`` candidate segments. All paths are scatter-free
    (sorted-segment design): counts/sums are cumsum differences over the
    sorted rows, min/max a segmented associative scan, lookups
    searchsorted — the idiomatic TPU lowering of what cudf does with
    atomics+hash tables (SURVEY.md §7 hard part 1). Every scan runs over
    the rows, every gather at ``num_segments``."""
    is_dec128 = dtype.id == dt.TypeId.DECIMAL128
    starts, ends = bounds
    num_segments = starts.shape[0]
    n_valid = _sorted_segment_sum(valid.astype(jnp.int64), starts, ends)
    has = n_valid > 0

    if op == "count":
        return Column(n_valid, dt.INT64, None)

    if op in ("first", "last"):
        # first/last VALID value per group (Spark first()/last() with
        # ignoreNulls): the collect_list rank machinery at a single
        # per-segment rank — 1 for first, n_valid for last
        ranks = (
            jnp.ones_like(n_valid)[:, None]
            if op == "first"
            else n_valid.astype(jnp.int32)[:, None]
        )
        row = _valid_rank_rows(valid, starts, ranks)[:, 0]
        if is_dec128:
            lo, hi_l = vals
            data = jnp.stack([lo[row], hi_l[row]], axis=1)
            return Column(data, dtype, has)
        return compute.from_values(vals[row], dtype, has)

    if op in _COLLECT_OPS or op == "nunique":
        if is_dec128 or dtype.is_string:
            raise TypeError(f"{op} not supported for {dtype}")
        if op == "nunique":
            _, first = _first_occurrence(dtype, seg, vals, valid)
            return Column(
                _sorted_segment_sum(
                    first.astype(jnp.int64), starts, ends
                ),
                dt.INT64,
                None,
            )
        if list_capacity is None:
            raise ValueError(
                f"{op} in the capped API needs GroupbyAgg.list_capacity "
                "(the static LIST pad width)"
            )
        return _collect_segment(
            dtype, op, list_capacity, seg, vals, valid, starts, ends
        )

    if is_dec128:
        return _aggregate_segment_dec128(
            dtype, op, vals, valid, seg, starts, ends, n_valid, has
        )

    if op in ("sum", "mean"):
        acc_dtype = jnp.float64 if dtype.is_floating else jnp.int64
        total = _sorted_segment_sum(
            jnp.where(valid, vals, 0).astype(acc_dtype), starts, ends
        )
        if op == "mean":
            mean = total.astype(jnp.float64) / jnp.maximum(n_valid, 1)
            if dtype.is_decimal:
                mean = mean * (10.0 ** dtype.scale)
            return compute.from_values(mean, dt.FLOAT64, has)
        if dtype.is_floating:
            return compute.from_values(total, dt.FLOAT64, has)
        if dtype.is_decimal:
            return compute.from_values(
                total, dt.DType(dt.TypeId.DECIMAL64, dtype.scale), has
            )
        return compute.from_values(total, dt.INT64, has)

    if op in ("variance", "std"):
        # two-pass: segment mean, gather back to rows, segment-sum of
        # squared deviations (the mean-subtracting formula; the naive
        # E[x^2]-E[x]^2 shortcut catastrophically cancels for
        # large-magnitude values). Sample variance, ddof=1; groups with
        # fewer than 2 valid rows are null.
        fvals = vals.astype(jnp.float64)
        if dtype.is_decimal:
            fvals = fvals * (10.0 ** dtype.scale)
        nf = n_valid.astype(jnp.float64)
        s1 = _sorted_segment_sum(
            jnp.where(valid, fvals, 0.0), starts, ends
        )
        mean = s1 / jnp.maximum(nf, 1)
        dev = fvals - mean[jnp.clip(seg, 0, num_segments - 1)]
        sq = _sorted_segment_sum(
            jnp.where(valid, dev * dev, 0.0), starts, ends
        )
        var = sq / jnp.maximum(nf - 1, 1)
        out = jnp.sqrt(var) if op == "std" else var
        return compute.from_values(out, dt.FLOAT64, n_valid > 1)

    # min / max via masked sentinels + segmented scan
    if dtype.is_floating:
        sentinel = np.inf if op == "min" else -np.inf
    elif dtype.is_boolean:
        sentinel = op == "min"
    else:
        info = np.iinfo(np.dtype(dtype.storage_dtype))
        sentinel = info.max if op == "min" else info.min
    masked = jnp.where(valid, vals, jnp.asarray(sentinel, vals.dtype))
    out = _sorted_segment_extreme(masked, seg, ends, op == "min")
    return compute.from_values(out, dtype, has)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SortedGroups:
    """What :func:`groupby_sort` hands :func:`groupby_reduce`: the rows
    in group order, every array at the INPUT's row count.

    ``keys`` are the key columns in input row order under their output
    names; ``perm`` the sorted-to-input row map; ``seg`` each sorted
    row's segment id; ``payload`` the sorted value arrays and masks;
    ``slots`` (static) one ``(op, list_capacity, output name, value
    dtype, payload index, value-array count, mask's payload index)`` per
    aggregation."""

    keys: Table
    perm: jax.Array
    seg: jax.Array
    payload: tuple
    slots: tuple

    def tree_flatten(self):
        return (self.keys, self.perm, self.seg, self.payload), self.slots

    @classmethod
    def tree_unflatten(cls, slots, children):
        return cls(*children, slots)


def groupby_sort(
    table: Table,
    by: Sequence[Union[int, str]],
    aggs: Sequence[GroupbyAgg],
    row_valid: Optional[jax.Array] = None,
) -> tuple[SortedGroups, jax.Array]:
    """First half of the capped groupby, all of it at the input's row
    count: the variadic stable sort with the value columns as payload,
    the boundary scan, the group count. -> (sorted state, count).

    Everything per GROUP is :func:`groupby_reduce`'s, which takes its
    width as an argument — a runner that reads the count between the
    halves runs the second at the bucket of the group count."""
    key_cols = [table.column(c) for c in by]
    key_names = [
        c if isinstance(c, str)
        else (table.names[c] if table.names else f"key{i}")
        for i, c in enumerate(by)
    ]

    # value columns ride the variadic sort as payload (one fused sort
    # instead of a 100M-row device gather per agg column)
    distinct: dict = {}
    payload: list = []
    slots = []
    # every value column with no validity of its own is valid where the
    # row is: they share ONE mask operand instead of sorting one each
    shared_mask = None
    for agg in aggs:
        col = table.column(agg.column)
        if id(col) not in distinct:
            if col.dtype.id == dt.TypeId.DECIMAL128:
                # limb columns ride the sort as two 1-D u64 operands
                v_entries = [col.data[:, 0], col.data[:, 1]]
            else:
                v_entries = [compute.values(col)]
            first = len(payload)
            payload.extend(v_entries)
            if col.validity is None and shared_mask is not None:
                mask_at = shared_mask
            else:
                m = compute.valid_mask(col)
                if row_valid is not None:
                    m = jnp.logical_and(m, row_valid)
                mask_at = len(payload)
                payload.append(m)
                if col.validity is None:
                    shared_mask = mask_at
            distinct[id(col)] = (first, len(v_entries), mask_at)
        base = (
            agg.column
            if isinstance(agg.column, str)
            else (table.names[agg.column] if table.names else f"c{agg.column}")
        )
        slots.append(
            (agg.op, agg.list_capacity, agg.name or f"{agg.op}_{base}",
             col.dtype) + distinct[id(col)]
        )
    perm, seg, num_groups, sorted_payload = _segment_ids(
        key_cols, row_valid, payload
    )
    state = SortedGroups(
        Table(key_cols, key_names), perm, seg, tuple(sorted_payload),
        tuple(slots),
    )
    return state, num_groups


def groupby_reduce(
    state: SortedGroups,
    num_groups,
    num_segments: int,
    return_collect_overflow: bool = False,
):
    """Second half of the capped groupby: segment bounds, the key
    gather and every aggregation for ``num_segments`` candidate groups
    -> the padded result of ``num_segments`` rows (with the collect
    overflow scalar when asked for, see ``groupby_aggregate_capped``).

    ``num_segments`` may be anything from the group count up: rows
    below ``num_groups`` come out the same bytes whatever it is (the
    cumsums still run over every sorted row, and ``c[end-1] -
    c[start-1]`` reads the same elements); only the width of the
    searches and gathers changes with it."""
    perm, seg, sorted_payload = state.perm, state.seg, state.payload

    # representative (first) sorted row of each segment -> key values
    n = perm.shape[0]
    bounds = _segment_bounds(seg, num_segments)
    starts, ends = bounds
    in_range = jnp.arange(num_segments, dtype=jnp.int32) < num_groups
    first_rows = perm[jnp.clip(starts, 0, max(n - 1, 0))]

    out_cols: list[Column] = []
    for col in state.keys.columns:
        k = gather_table(Table([col]), first_rows).columns[0]
        valid = jnp.logical_and(
            compute.valid_mask(k), in_range
        )
        out_cols.append(Column(k.data, k.dtype, valid, k.lengths))
    out_names = list(state.keys.names)

    collect_overflow = jnp.zeros((), jnp.int64)
    for op, list_capacity, name, dtype, j, nv, mask_at in state.slots:
        vals_sorted = (
            tuple(sorted_payload[j : j + nv])
            if nv > 1
            else sorted_payload[j]
        )
        r = _aggregate_segment(
            dtype, op, seg, bounds, vals_sorted, sorted_payload[mask_at],
            list_capacity=list_capacity,
        )
        valid = jnp.logical_and(compute.valid_mask(r), in_range)
        out_cols.append(Column(r.data, r.dtype, valid, r.lengths))
        out_names.append(name)
        if return_collect_overflow and op in _COLLECT_OPS:
            # pre-clamp element count of a group == its valid-row count
            # (collect drops nulls), which the count machinery already
            # computes from the same sorted payload. For collect_set
            # this is an UPPER bound (valid rows, not distinct values):
            # a conservative overflow signal, never a missed one.
            n_valid = _sorted_segment_sum(
                sorted_payload[mask_at].astype(jnp.int64), starts, ends
            )
            collect_overflow = jnp.maximum(
                collect_overflow,
                jnp.max(jnp.where(in_range, n_valid, 0)),
            )

    out = Table(out_cols, out_names)
    if return_collect_overflow:
        return out, collect_overflow
    return out


def groupby_aggregate_capped(
    table: Table,
    by: Sequence[Union[int, str]],
    aggs: Sequence[GroupbyAgg],
    num_segments: int,
    row_valid: Optional[jax.Array] = None,
    return_collect_overflow: bool = False,
) -> tuple[Table, jax.Array]:
    """Jittable groupby: (padded result of ``num_segments`` rows, count).

    Padding rows have null keys/values (validity False past the count).
    ``row_valid`` excludes rows (e.g. shuffle-padding occupancy).

    :func:`groupby_sort` then :func:`groupby_reduce` in ONE trace, for
    callers that cannot read the group count in between (inside
    ``shard_map``, under a caller's jit). The served runners launch the
    halves apart and size the second from the count (``bucketed.py``).

    ``return_collect_overflow=True`` appends a device scalar: the
    LARGEST pre-clamp valid-element count of any group across the
    collect_list/collect_set aggregations (0 when there are none).
    ``collect_*`` outputs silently truncate groups past
    ``list_capacity`` — unlike every other ``*_capped`` API, whose
    two-phase counts let callers detect overflow — so callers that
    need losslessness check ``overflow <= list_capacity`` and resize
    (r3 advisor finding)."""
    state, num_groups = groupby_sort(table, by, aggs, row_valid=row_valid)
    if return_collect_overflow:
        out, overflow = groupby_reduce(
            state, num_groups, num_segments, return_collect_overflow=True
        )
        return out, num_groups, overflow
    return groupby_reduce(state, num_groups, num_segments), num_groups


def groupby_aggregate(
    table: Table,
    by: Sequence[Union[int, str]],
    aggs: Sequence[GroupbyAgg],
) -> Table:
    """Eager groupby with exact output size (one host sync). Collect
    aggregations without an explicit ``list_capacity`` get sized from
    the largest group's valid-row count (a cheap count pre-pass)."""
    if table.row_count == 0:
        # 0 rows -> 0 groups, but the output SCHEMA must still be exact:
        # run the real pipeline on one all-null dummy row (which forms
        # one null-key group) and slice it away
        dummy_cols = [
            Column(
                jnp.zeros((1,) + c.data.shape[1:], c.data.dtype),
                c.dtype,
                jnp.zeros((1,), jnp.bool_),
                None
                if c.lengths is None
                else jnp.zeros((1,), c.lengths.dtype),
            )
            for c in table.columns
        ]
        aggs = [
            dataclasses.replace(a, list_capacity=a.list_capacity or 1)
            if a.op in _COLLECT_OPS
            else a
            for a in aggs
        ]
        padded, _ = groupby_aggregate_capped(
            Table(dummy_cols, table.names), by, aggs, num_segments=1
        )
        from .copying import slice_rows

        return slice_rows(padded, 0, 0)
    needs = [
        a for a in aggs
        if a.op in _COLLECT_OPS and a.list_capacity is None
    ]
    if needs:
        counts = groupby_aggregate(
            table,
            by,
            [
                GroupbyAgg(a.column, "count", name=f"__collect_n{i}")
                for i, a in enumerate(needs)
            ],
        )
        sized = {}
        for i, a in enumerate(needs):
            c = counts.columns[len(by) + i].to_numpy()
            sized[id(a)] = max(1, int(c.max())) if c.size else 1
        aggs = [
            dataclasses.replace(a, list_capacity=sized[id(a)])
            if id(a) in sized
            else a
            for a in aggs
        ]
    padded, num_groups = groupby_aggregate_capped(
        table, by, aggs, num_segments=max(table.row_count, 1)
    )
    g = int(num_groups)
    cols = [
        Column(
            c.data[:g],
            c.dtype,
            None if c.validity is None else c.validity[:g],
            None if c.lengths is None else c.lengths[:g],
        )
        for c in padded.columns
    ]
    return Table(cols, padded.names)


def _aggregate_segment_dec128(
    dtype, op, vals, valid, seg, starts, ends, n_valid, has
):
    """DECIMAL128 aggregations over sorted segments (ops/int128.py).

    sum is EXACT mod 2**128: each limb splits into 32-bit halves whose
    per-segment totals fit u64 without wrap (n < 2**32), and the four
    partial sums recombine with 128-bit carries. min/max run one
    segmented lexicographic scan over the order-key words. mean /
    variance use the float64 approximation of the 128-bit value."""
    from . import int128

    lo, hi = vals
    scale = dtype.scale

    if op in ("sum", "mean"):
        m32 = jnp.uint64(0xFFFFFFFF)
        zero = jnp.uint64(0)
        parts = []
        for limb in (lo, hi):
            parts.append(jnp.where(valid, limb & m32, zero))
            parts.append(jnp.where(valid, limb >> jnp.uint64(32), zero))
        s_ll, s_lh, s_hl, s_hh = [
            _sorted_segment_sum(p.astype(jnp.int64), starts, ends).astype(
                jnp.uint64
            )
            for p in parts
        ]
        out_lo, out_hi = s_ll, jnp.zeros_like(s_ll)
        out_lo, out_hi = int128.add(
            out_lo, out_hi, s_lh << jnp.uint64(32), s_lh >> jnp.uint64(32)
        )
        out_lo, out_hi = int128.add(
            out_lo, out_hi, jnp.zeros_like(s_hl), s_hl
        )
        out_lo, out_hi = int128.add(
            out_lo, out_hi, jnp.zeros_like(s_hh), s_hh << jnp.uint64(32)
        )
        if op == "mean":
            mean = (
                int128.to_float64(out_lo, out_hi)
                / jnp.maximum(n_valid, 1)
                * (10.0 ** scale)
            )
            return compute.from_values(mean, dt.FLOAT64, has)
        data = jnp.stack([out_lo, out_hi], axis=1)
        return Column(data, dt.DType(dt.TypeId.DECIMAL128, scale), has)

    if op in ("variance", "std"):
        fvals = int128.to_float64(lo, hi) * (10.0 ** scale)
        nf = n_valid.astype(jnp.float64)
        s1 = _sorted_segment_sum(
            jnp.where(valid, fvals, 0.0), starts, ends
        )
        mean = s1 / jnp.maximum(nf, 1)
        num_segments = starts.shape[0]
        dev = fvals - mean[jnp.clip(seg, 0, num_segments - 1)]
        sq = _sorted_segment_sum(
            jnp.where(valid, dev * dev, 0.0), starts, ends
        )
        var = sq / jnp.maximum(nf - 1, 1)
        out = jnp.sqrt(var) if op == "std" else var
        return compute.from_values(out, dt.FLOAT64, n_valid > 1)

    # min / max: lexicographic segmented scan over order-key words
    sign = np.uint64(1) << np.uint64(63)
    key_hi = hi ^ sign
    is_min = op == "min"
    sent = jnp.uint64(0xFFFFFFFFFFFFFFFF) if is_min else jnp.uint64(0)
    k_hi = jnp.where(valid, key_hi, sent)
    k_lo = jnp.where(valid, lo, sent)

    def combine(a, b):
        s1, h1, l1 = a
        s2, h2, l2 = b
        same = s1 == s2
        if is_min:
            a_wins = (h1 < h2) | ((h1 == h2) & (l1 <= l2))
        else:
            a_wins = (h1 > h2) | ((h1 == h2) & (l1 >= l2))
        take_a = same & a_wins
        return s2, jnp.where(take_a, h1, h2), jnp.where(take_a, l1, l2)

    _, sc_hi, sc_lo = jax.lax.associative_scan(
        combine, (seg, k_hi, k_lo)
    )
    n = lo.shape[0]
    idx = jnp.clip(ends - 1, 0, max(n - 1, 0))
    out_hi = sc_hi[idx] ^ sign
    out_lo = sc_lo[idx]
    data = jnp.stack([out_lo, out_hi], axis=1)
    return Column(data, dt.DType(dt.TypeId.DECIMAL128, scale), has)
