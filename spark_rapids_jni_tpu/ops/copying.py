"""Copy-family ops (cudf ``concatenate`` / ``interleave_columns`` /
``copy_if_else`` / ``sequence``).

Capability-surface rows of SURVEY.md §2.3: column factories and
table-assembly utilities the vendored cudf Java suite exercises. All
shapes here are static functions of the inputs, so every op jits.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax.numpy as jnp
import numpy as np

from .. import dtype as dt
from ..column import Column, Table
from . import compute


def concatenate_columns(cols: Sequence[Column]) -> Column:
    """Vertical concatenation of same-dtype columns."""
    if not cols:
        raise ValueError("concatenate needs at least one column")
    d = cols[0].dtype
    for c in cols[1:]:
        if c.dtype != d:
            raise TypeError(f"concatenate dtype mismatch: {d} vs {c.dtype}")
    lengths = None
    if d.is_string:
        # strings carry a (n,) lengths vector beside the padded matrix;
        # repad to the widest so row widths agree before concatenating
        from .strings import repad

        width = max(c.data.shape[1] for c in cols)
        cols = [repad(c, width) for c in cols]
        data = jnp.concatenate([c.data for c in cols], axis=0)
        lengths = jnp.concatenate([c.lengths for c in cols])
    else:
        data = jnp.concatenate([c.data for c in cols], axis=0)
    if any(c.validity is not None for c in cols):
        valid = jnp.concatenate([compute.valid_mask(c) for c in cols])
    else:
        valid = None
    return Column(data, d, valid, lengths)


def concatenate(tables: Sequence[Table]) -> Table:
    """Vertical concatenation of same-schema tables (cudf
    ``Table.concatenate``)."""
    if not tables:
        raise ValueError("concatenate needs at least one table")
    first = tables[0]
    for t in tables[1:]:
        if t.num_columns != first.num_columns:
            raise ValueError("concatenate: column counts differ")
    out = [
        concatenate_columns([t.columns[i] for t in tables])
        for i in range(first.num_columns)
    ]
    # an unnamed table (every wire table, every filter's output) stays unnamed
    return Table(out, first.names)


def interleave_columns(table: Table) -> Column:
    """Row-major interleave of same-dtype columns into one column
    (cudf ``interleave_columns``): output row i*ncols+j = col j row i."""
    d = table.columns[0].dtype
    for c in table.columns[1:]:
        if c.dtype != d:
            raise TypeError("interleave_columns needs uniform dtype")
    if d.is_string:
        raise TypeError("interleave_columns: fixed-width only")
    data = jnp.stack([c.data for c in table.columns], axis=1).reshape(-1)
    if any(c.validity is not None for c in table.columns):
        valid = jnp.stack(
            [compute.valid_mask(c) for c in table.columns], axis=1
        ).reshape(-1)
    else:
        valid = None
    return Column(data, d, valid)


def copy_if_else(
    mask: Column, lhs: Union[Column, object], rhs: Union[Column, object]
) -> Column:
    """Per-row select: mask TRUE -> lhs, else rhs (cudf ``copy_if_else``).
    Null mask rows select rhs (Spark CASE WHEN semantics). Scalars are
    broadcast."""
    if not mask.dtype.is_boolean:
        raise TypeError("copy_if_else mask must be BOOL8")
    pred = mask.data
    if mask.validity is not None:
        pred = jnp.logical_and(pred, mask.validity)
    n = len(mask)

    def as_column(x, like: Column | None):
        if isinstance(x, Column):
            return x
        if like is None:
            raise TypeError("copy_if_else: both sides scalar is ambiguous")
        vals = jnp.full((n,), x)
        return compute.from_values(vals, like.dtype, None)

    lhs_col = as_column(lhs, rhs if isinstance(rhs, Column) else None)
    rhs_col = as_column(rhs, lhs_col)
    if lhs_col.dtype != rhs_col.dtype:
        raise TypeError(
            f"copy_if_else dtype mismatch: {lhs_col.dtype} vs {rhs_col.dtype}"
        )
    lengths = None
    if lhs_col.dtype.is_string:
        if lhs_col.data.shape[1] != rhs_col.data.shape[1]:
            from .strings import repad

            width = max(lhs_col.data.shape[1], rhs_col.data.shape[1])
            lhs_col, rhs_col = repad(lhs_col, width), repad(rhs_col, width)
        data = jnp.where(pred[:, None], lhs_col.data, rhs_col.data)
        lengths = jnp.where(pred, lhs_col.lengths, rhs_col.lengths)
    else:
        data = jnp.where(pred, lhs_col.data, rhs_col.data)
    if lhs_col.validity is None and rhs_col.validity is None:
        valid = None
    else:
        valid = jnp.where(
            pred, compute.valid_mask(lhs_col), compute.valid_mask(rhs_col)
        )
    return Column(data, lhs_col.dtype, valid, lengths)


def sequence(n: int, start=0, step=1, dtype: dt.DType = dt.INT32) -> Column:
    """Arithmetic sequence column (cudf ``sequence``; the offsets builder
    of the reference's row conversion, row_conversion.cu:389-390)."""
    vals = start + step * jnp.arange(n, dtype=jnp.int64)
    return compute.from_values(vals, dtype, None)


def cross_join(left: Table, right: Table) -> Table:
    """Cartesian product (cudf ``cross_join`` / Java ``Table.crossJoin``):
    every left row paired with every right row, left-major order. Output
    size is the static product, so the op jits."""
    from .gather import gather_table

    nl, nr = left.row_count, right.row_count
    li = jnp.repeat(
        jnp.arange(nl, dtype=jnp.int32), nr, total_repeat_length=nl * nr
    )
    ri = jnp.tile(jnp.arange(nr, dtype=jnp.int32), nl)
    lg = gather_table(left, li)
    rg = gather_table(right, ri)
    lnames = list(left.names) if left.names else [
        f"l{i}" for i in range(left.num_columns)
    ]
    rnames = list(right.names) if right.names else [
        f"r{i}" for i in range(right.num_columns)
    ]
    return Table(list(lg.columns) + list(rg.columns), lnames + rnames)


def scatter(source: Table, indices, target: Table) -> Table:
    """Rows of ``source`` written into ``target`` at ``indices`` (cudf
    ``scatter``): out[indices[i]] = source[i], other rows unchanged.
    Schemas must match; which duplicate index wins is unspecified (as in
    cudf — JAX documents conflicting ``.at[].set`` updates as
    implementation-defined order)."""
    if source.num_columns != target.num_columns:
        raise ValueError("scatter: column counts differ")
    idx = jnp.asarray(indices).astype(jnp.int32)
    out_cols = []
    for s, t in zip(source.columns, target.columns):
        if s.dtype != t.dtype:
            raise TypeError(
                f"scatter dtype mismatch: {s.dtype} vs {t.dtype}"
            )
        if s.dtype.is_string and s.data.shape[1] != t.data.shape[1]:
            from .strings import repad

            width = max(s.data.shape[1], t.data.shape[1])
            s, t = repad(s, width), repad(t, width)
        data = t.data.at[idx].set(s.data)
        valid = None
        if s.validity is not None or t.validity is not None:
            valid = compute.valid_mask(t).at[idx].set(
                compute.valid_mask(s)
            )
        lengths = t.lengths
        if t.lengths is not None:
            lengths = t.lengths.at[idx].set(s.lengths)
        out_cols.append(Column(data, t.dtype, valid, lengths))
    return Table(out_cols, target.names)


def slice_rows(table: Table, start: int, stop: int) -> Table:
    """Zero-copy row range [start, stop) of every column (cudf
    ``slice``). The single place the per-Column data/validity/lengths
    slicing lives — chunked joins, split, and empty-schema fast paths
    all use it."""
    return Table(
        [
            Column(
                c.data[start:stop],
                c.dtype,
                None if c.validity is None else c.validity[start:stop],
                None if c.lengths is None else c.lengths[start:stop],
            )
            for c in table.columns
        ],
        table.names,
    )


def split(table: Table, splits: Sequence[int]) -> list[Table]:
    """Partition rows at the given boundaries (cudf ``Table.split`` /
    ``contiguous_split``, the mechanism behind the reference's 2 GB
    batching): ``splits=[s1, s2]`` yields [0,s1), [s1,s2), [s2,n)."""
    n = table.row_count
    bounds = [0] + [int(s) for s in splits] + [n]
    for a, b in zip(bounds, bounds[1:]):
        if not (0 <= a <= b <= n):
            raise ValueError(f"split: bad boundaries {splits}")
    return [slice_rows(table, a, b) for a, b in zip(bounds, bounds[1:])]


def repeat(table: Table, counts) -> Table:
    """Each row i replicated ``counts[i]`` times, in order (cudf
    ``Table.repeat``). A scalar count repeats every row that many times
    (jittable: static output size); a per-row count vector is eager
    (host-syncs the total, the cudf call model)."""
    from .gather import gather_table

    n = table.row_count
    if np.isscalar(counts):
        k = int(counts)
        if k < 0:
            raise ValueError("repeat: count must be non-negative")
        idx = jnp.repeat(
            jnp.arange(n, dtype=jnp.int32), k, total_repeat_length=n * k
        )
        return gather_table(table, idx)
    c = np.asarray(counts)
    if c.shape != (n,):
        raise ValueError(f"repeat: counts shape {c.shape} != ({n},)")
    if (c < 0).any():
        raise ValueError("repeat: counts must be non-negative")
    idx = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), c))
    return gather_table(table, idx)


def sample(table: Table, n: int, seed: int = 0,
           replacement: bool = False) -> Table:
    """Random row sample (cudf ``Table.sample``), jax PRNG keyed by
    ``seed`` — deterministic for a given seed like cudf's."""
    import jax

    from .gather import gather_table

    rows = table.row_count
    key = jax.random.PRNGKey(seed)
    if replacement:
        if rows == 0 and n > 0:
            raise ValueError("sample with replacement from an empty table")
        idx = jax.random.randint(key, (n,), 0, max(rows, 1))
    else:
        if n > rows:
            raise ValueError(f"sample of {n} from {rows} rows")
        idx = jax.random.permutation(key, rows)[:n]
    return gather_table(table, idx.astype(jnp.int32))
