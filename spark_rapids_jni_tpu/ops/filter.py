"""Filter (cudf ``apply_boolean_mask``) in the two-phase discipline.

XLA needs static shapes, so a data-dependent filter comes in two forms
(SURVEY.md §7 hard part 5 — generalizing the reference's two-phase 2 GB
batching at row_conversion.cu:505-511):

* ``filter_table`` — eager: host-sync the surviving count, return an
  exactly-sized table (the cudf/JNI call model).
* ``filter_table_capped`` — jittable: caller supplies a static capacity;
  returns a padded table + device row count. Selected rows are compacted
  to the front with a stable cumsum+gather (no scatter conflicts — the
  TPU-friendly replacement for CUDA stream compaction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..column import Column, Table
from . import compute
from .gather import gather_table


def selection_mask(mask: Column) -> jax.Array:
    """Spark WHERE keeps rows where the predicate is TRUE (not null)."""
    if not mask.dtype.is_boolean:
        raise TypeError("filter mask must be BOOL8")
    keep = mask.data
    if mask.validity is not None:
        keep = jnp.logical_and(keep, mask.validity)
    return keep


def _compaction_indices(keep: jax.Array, capacity: int):
    """Stable indices of kept rows, padded to ``capacity``."""
    n = keep.shape[0]
    # positions[i] = output slot of row i (exclusive cumsum of keep)
    slots = jnp.cumsum(keep) - keep.astype(jnp.int32)
    count = jnp.sum(keep).astype(jnp.int32)
    # inverse permutation via scatter of row ids into their slots
    idx = jnp.zeros((capacity,), dtype=jnp.int32)
    row_ids = jnp.arange(n, dtype=jnp.int32)
    idx = idx.at[jnp.where(keep, slots, capacity)].set(row_ids, mode="drop")
    return idx, count


def filter_table_capped(
    table: Table, mask: Column, capacity: int
) -> tuple[Table, jax.Array]:
    """Jittable filter: (padded table of ``capacity`` rows, device count).

    Rows past the count are clones of kept rows (garbage but in-bounds);
    consumers must respect the count.
    """
    keep = selection_mask(mask)
    idx, count = _compaction_indices(keep, capacity)
    return gather_table(table, idx), count


def filter_table(table: Table, mask: Column) -> Table:
    """Eager filter with exact output size (one host sync for the count)."""
    keep = selection_mask(mask)
    count = int(jnp.sum(keep))
    if count == table.row_count:
        return table
    idx, _ = _compaction_indices(keep, max(count, 1))
    out = gather_table(table, idx[:count] if count else idx[:0])
    return out
