"""Column factories & utilities — the cudf factory/primitive surface.

TPU-native equivalents of the cudf factories and utilities the reference
binds to (SURVEY.md §2.3 "Column factories & utilities":
``make_fixed_width_column`` / ``make_numeric_column`` at
row_conversion.cu:392-394,551-552, ``cudf::detail::sequence`` at :390,
scalars at :494-502, plus the copying/reshape family the vendored cudf
Java test suite exercises: concatenate, slice/split, interleave).

All constructors return device-resident Columns and are jit-friendly
(static shapes; no host syncs).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dt
from .column import Column, Table
from .ops import compute


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------

def sequence(n: int, start=0, step=1, dtype: dt.DType = dt.INT32) -> Column:
    """0, step, 2*step, ... — cudf::detail::sequence (row_conversion.cu:389-390),
    the arithmetic progression behind list offsets."""
    vals = start + step * jnp.arange(n, dtype=jnp.int64)
    return compute.from_values(vals, dtype, None)


def full(n: int, value, dtype: dt.DType) -> Column:
    """A column of ``n`` copies of ``value`` (cudf make_*_scalar + fill)."""
    if dtype.is_string:
        if isinstance(value, str):
            value = value.encode("utf-8", "surrogateescape")
        return Column.from_strings([value] * n)
    vals = jnp.full((n,), value, dtype=np.dtype(dtype.device_dtype))
    return compute.from_values(vals, dtype, None)


def full_null(n: int, dtype: dt.DType) -> Column:
    """An all-null column (payload zeros, validity all-False)."""
    valid = jnp.zeros((n,), dtype=jnp.bool_)
    if dtype.is_string:
        return Column(
            jnp.zeros((n, 1), dtype=jnp.uint8),
            dt.STRING,
            valid,
            jnp.zeros((n,), dtype=jnp.int32),
        )
    data = jnp.zeros((n,), dtype=dtype.storage_dtype)
    return Column(data, dtype, valid)


def empty_like(col: Column, n: Optional[int] = None) -> Column:
    """An uninitialized-contents column with the same dtype/layout
    (cudf make_fixed_width_column with UNINITIALIZED masks,
    row_conversion.cu:546-557 — here zeros, XLA has no uninitialized)."""
    rows = col.row_count if n is None else n
    if col.dtype.is_string:
        return Column(
            jnp.zeros((rows, col.pad_width), dtype=jnp.uint8),
            dt.STRING,
            None,
            jnp.zeros((rows,), dtype=jnp.int32),
        )
    return Column(jnp.zeros((rows,), dtype=col.data.dtype), col.dtype, None)


# ---------------------------------------------------------------------------
# copying / reshape
# ---------------------------------------------------------------------------

def concatenate(cols: Sequence[Column]) -> Column:
    """Vertical concatenation (cudf::concatenate)."""
    if not cols:
        raise ValueError("concatenate of no columns")
    dtype = cols[0].dtype
    for c in cols[1:]:
        if c.dtype != dtype:
            raise TypeError(f"dtype mismatch: {c.dtype!r} vs {dtype!r}")
    n_total = sum(c.row_count for c in cols)

    if dtype.is_string:
        pad = max(c.pad_width for c in cols)
        mats = [
            jnp.pad(c.data, ((0, 0), (0, pad - c.pad_width)))
            if c.pad_width < pad
            else c.data
            for c in cols
        ]
        data = jnp.concatenate(mats, axis=0)
        lengths = jnp.concatenate([c.lengths for c in cols])
    else:
        data = jnp.concatenate([c.data for c in cols])
        lengths = None

    if any(c.validity is not None for c in cols):
        validity = jnp.concatenate(
            [
                c.validity
                if c.validity is not None
                else jnp.ones((c.row_count,), dtype=jnp.bool_)
                for c in cols
            ]
        )
    else:
        validity = None
    out = Column(data, dtype, validity, lengths)
    assert out.row_count == n_total
    return out


def concatenate_tables(tables: Sequence[Table]) -> Table:
    """Row-wise table concatenation (schema must match)."""
    if not tables:
        raise ValueError("concatenate of no tables")
    k = tables[0].num_columns
    for t in tables[1:]:
        if t.num_columns != k:
            raise ValueError("column count mismatch")
    cols = [
        concatenate([t.columns[i] for t in tables]) for i in range(k)
    ]
    return Table(cols, tables[0].names)


def slice_column(col: Column, start: int, end: int) -> Column:
    """Zero-copy-ish contiguous row slice (cudf::slice)."""
    data = col.data[start:end]
    validity = None if col.validity is None else col.validity[start:end]
    lengths = None if col.lengths is None else col.lengths[start:end]
    return Column(data, col.dtype, validity, lengths)


def slice_table(table: Table, start: int, end: int) -> Table:
    return Table(
        [slice_column(c, start, end) for c in table.columns], table.names
    )


def split_table(table: Table, splits: Sequence[int]) -> list:
    """cudf::split — cut points -> list of contiguous sub-tables."""
    bounds = [0, *splits, table.row_count]
    return [
        slice_table(table, bounds[i], bounds[i + 1])
        for i in range(len(bounds) - 1)
    ]


def interleave_columns(cols: Sequence[Column]) -> Column:
    """Row-interleave equal-length same-type columns
    (cudf::interleave_columns: out[i*k+j] = cols[j][i])."""
    if not cols:
        raise ValueError("interleave of no columns")
    dtype = cols[0].dtype
    if dtype.is_string:
        raise TypeError("interleave_columns: fixed-width only")
    n = cols[0].row_count
    for c in cols:
        if c.dtype != dtype or c.row_count != n:
            raise ValueError("interleave requires same dtype and length")
    k = len(cols)
    data = jnp.stack([c.data for c in cols], axis=1).reshape(n * k)
    if any(c.validity is not None for c in cols):
        validity = jnp.stack(
            [
                c.validity
                if c.validity is not None
                else jnp.ones((n,), dtype=jnp.bool_)
                for c in cols
            ],
            axis=1,
        ).reshape(n * k)
    else:
        validity = None
    return Column(data, dtype, validity)


def copy_if_else(lhs: Column, rhs: Column, mask: Column) -> Column:
    """Per-row select: mask ? lhs : rhs (cudf::copy_if_else). Null mask
    rows follow Spark CASE WHEN: a null predicate selects ``rhs``."""
    if not mask.dtype.is_boolean:
        raise TypeError("copy_if_else mask must be BOOL8")
    if lhs.dtype != rhs.dtype:
        raise TypeError("copy_if_else requires matching dtypes")
    take_l = mask.data
    if mask.validity is not None:
        take_l = jnp.logical_and(take_l, mask.validity)
    if lhs.dtype.is_string:
        pad = max(lhs.pad_width, rhs.pad_width)
        lmat = jnp.pad(lhs.data, ((0, 0), (0, pad - lhs.pad_width)))
        rmat = jnp.pad(rhs.data, ((0, 0), (0, pad - rhs.pad_width)))
        data = jnp.where(take_l[:, None], lmat, rmat)
        lengths = jnp.where(take_l, lhs.lengths, rhs.lengths)
    else:
        data = jnp.where(take_l, lhs.data, rhs.data)
        lengths = None
    lv = (
        lhs.validity
        if lhs.validity is not None
        else jnp.ones((lhs.row_count,), dtype=jnp.bool_)
    )
    rv = (
        rhs.validity
        if rhs.validity is not None
        else jnp.ones((rhs.row_count,), dtype=jnp.bool_)
    )
    validity = jnp.where(take_l, lv, rv)
    if lhs.validity is None and rhs.validity is None:
        validity = None
    return Column(data, lhs.dtype, validity, lengths)


# ---------------------------------------------------------------------------
# shape buckets (utils/buckets.py applied at the Python level)
#
# The dispatch plane (planops.dispatch) buckets automatically;
# these are the Python-level entry points for callers that drive the op
# library directly and want the same compiled-shape reuse: pad once,
# run the *_capped ops with `row_valid`, unpad at the end.
# ---------------------------------------------------------------------------


def pad_to_bucket(table: Table, bucket: Optional[int] = None) -> Table:
    """Pad ``table`` to its row-count bucket (or an explicit ``bucket``),
    carrying the logical row count on the result (``Table.logical_rows``).
    Returns the input unchanged when bucketing is disabled
    (``SPARK_RAPIDS_TPU_BUCKETS=off``) or the size has no bucket."""
    from .utils import buckets

    if bucket is None:
        bucket = buckets.bucket_for(table.logical_row_count)
        if bucket is None:
            return table
    return buckets.pad_table(table, bucket)


def unpad_table(table: Table) -> Table:
    """Exact-shape view of a possibly bucket-padded table (inverse of
    :func:`pad_to_bucket`; identity for exact tables)."""
    from .utils import buckets

    return buckets.unpad_table(table)


def run_plan(
    ops: Sequence[dict],
    table: Table,
    rest: Sequence[Table] = (),
    unpad: bool = True,
    donate_input: bool = False,
) -> Table:
    """Python-level plan entry: execute a JSON-able op LIST (the
    ``table_plan_wire``/``table_plan_resident`` format) over
    device-resident Tables. Maximal runs of fusable ops compile into
    single cached executables (plan.py) — one launch per segment —
    and boundary ops dispatch per-op. ``unpad=True`` (default) returns
    an exact-shape result; pass ``unpad=False`` to keep the
    bucket-padded table (``Table.logical_rows`` carries the real
    count) when feeding another plan or bucketed op.

    ``donate_input=True`` declares ``table`` consumed by this plan:
    nothing else references its buffers, so the first fused segment may
    donate them and update HBM in place (``hbm.donated_bytes``). The
    caller must not touch ``table`` afterwards."""
    from . import plan as plan_mod
    from .utils import buckets, profiler

    ops = list(ops)
    schema = None
    report = None
    if profiler.enabled():
        # key the plan-stats record like the wire entries do; static
        # analysis here is observational only — plan.run_plan stays the
        # loud validator for this path
        from . import plancheck

        try:
            schema = plancheck.schema_of_table(table)
            report = plancheck.analyze(
                ops, schema=schema, rows=int(table.logical_row_count),
            )
        # srt: allow-broad-except(stats keying is best-effort; plan.run_plan still validates loudly)
        except Exception:
            schema = report = None
    with profiler.maybe_session(
        ops, label="plan_python", schema=schema, static=report,
    ):
        out = plan_mod.run_plan(
            ops, table, tuple(rest), donate_input=donate_input
        )
        return buckets.unpad_table(out) if unpad else out


# ---------------------------------------------------------------------------
# validity bitmask packing (Arrow wire form <-> device bool vectors)
# ---------------------------------------------------------------------------

def pack_bitmask(valid: jax.Array) -> jax.Array:
    """(n,) bool -> ceil(n/8) uint8, LSB-first (Arrow/cudf bitmask_type
    layout; the device-side analog of interop.pack_validity). Jittable.

    Delegates to the row codec's bit packer (rows._pack_validity_bytes) —
    one normative implementation of the LSB-first layout, shared with the
    packed-row validity tail."""
    from . import rows

    n = valid.shape[0]
    # one "row" whose columns are the n bits
    return rows._pack_validity_bytes(valid[None, :], n)[0]


def unpack_bitmask(packed: jax.Array, n: int) -> jax.Array:
    """ceil(n/8) uint8 LSB-first -> (n,) bool (inverse of pack_bitmask,
    same shared core as the row codec)."""
    from . import rows

    return rows._unpack_validity_bytes(packed[None, :], n)[0]
