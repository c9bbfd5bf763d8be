"""TPC-DS q5/q23/q64-shaped queries over the op library.

Not the literal TPC-DS SQL (whose dimension DDL is far wider) but the
same operator DAGs at the same shapes — the structures configs 4-5
name:

* q5-shape:  multi-channel fact union -> date filter -> dimension join
             -> rollup aggregation.
* q23-shape: frequent-item CTE (groupby+filter) -> semi join against the
             fact table -> per-customer aggregation.
* q64-shape: chained multi-dimension joins (item, customer, date) with
             predicates -> wide-key aggregation.

Each query runs single-chip (eager ops) or distributed over a mesh
(shuffle-exchange + local capped ops under one jitted shard_map — the
GpuShuffleExchangeExec replacement, SURVEY.md §2.5/§5.8).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import ops
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
from spark_rapids_jni_tpu.parallel.distributed import (
    broadcast_inner_join,
    distributed_groupby,
    distributed_inner_join,
    distributed_semi_join,
)


def _date_filter(t: Table, lo: int, hi: int) -> Table:
    mask = Column(
        jnp.logical_and(t["date_sk"].data >= lo, t["date_sk"].data < hi),
        dt.BOOL8,
        None,
    )
    return ops.filter_table(t, mask)


# ---------------------------------------------------------------------------
# q5-shape: channel union -> date window -> join item -> category rollup
# ---------------------------------------------------------------------------

def q5(tables: dict, date_lo: int = 100, date_hi: int = 200) -> Table:
    store = _date_filter(tables["store_sales"], date_lo, date_hi)
    web = _date_filter(tables["web_sales"], date_lo, date_hi)
    allsales = ops.concatenate([store, web])
    joined = ops.inner_join(allsales, tables["item"], ["item_sk"])
    rev = ops.mul(joined["quantity"], joined["sales_price"])
    with_rev = Table(
        [*joined.columns, rev], [*joined.names, "revenue"]
    )
    return ops.groupby_aggregate(
        with_rev,
        ["category_id"],
        [
            GroupbyAgg("revenue", "sum"),
            GroupbyAgg("net_profit", "sum"),
            GroupbyAgg("revenue", "count"),
        ],
    )


def q5_distributed(tables: dict, mesh, date_lo=100, date_hi=200):
    """Distributed q5: the union + filter happen per-shard inside the
    fact tables (cheap, embarrassingly parallel); the item dimension
    join is a BROADCAST hash join (the BroadcastHashJoinExec plan Spark
    picks for dimension tables — fact side stays sharded in place, zero
    fact rows cross the ICI); the aggregation shuffles by category."""
    store = _date_filter(tables["store_sales"], date_lo, date_hi)
    web = _date_filter(tables["web_sales"], date_lo, date_hi)
    allsales = _pad_to_mesh(ops.concatenate([store, web]), mesh)
    # padding rows carry _PAD_KEY, which matches no real item_sk — the
    # inner broadcast join drops them with no special handling
    joined_sh, counts = broadcast_inner_join(
        allsales, tables["item"], ["item_sk"], mesh
    )
    joined = _unpad_join(joined_sh, counts)
    rev = ops.mul(joined["quantity"], joined["sales_price"])
    with_rev = Table([*joined.columns, rev], [*joined.names, "revenue"])
    # pad rows to a multiple of the mesh size for sharding; the
    # ragged-compact exchange auto-plans its buffer from the real
    # per-destination totals (12 categories = maximal skew is fine)
    padded = _pad_to_mesh(with_rev, mesh)
    return distributed_groupby(
        padded,
        ["category_id"],
        [
            GroupbyAgg("revenue", "sum"),
            GroupbyAgg("net_profit", "sum"),
            GroupbyAgg("revenue", "count"),
        ],
        mesh,
    )


# ---------------------------------------------------------------------------
# q23-shape: frequent items CTE -> semi join -> per-customer spend
# ---------------------------------------------------------------------------

def q23(tables: dict, min_count: int = 4) -> Table:
    sales = tables["store_sales"]
    freq = ops.groupby_aggregate(
        sales, ["item_sk"], [GroupbyAgg("item_sk", "count")]
    )
    hot = ops.filter_table(
        freq,
        Column(freq["count_item_sk"].data >= min_count, dt.BOOL8, None),
    )
    hot_sales = ops.semi_join(sales, hot, ["item_sk"])
    spend = ops.mul(hot_sales["quantity"], hot_sales["sales_price"])
    t = Table([*hot_sales.columns, spend], [*hot_sales.names, "spend"])
    return ops.groupby_aggregate(
        t, ["customer_sk"], [GroupbyAgg("spend", "sum")]
    )


def q23_distributed(tables: dict, mesh, min_count: int = 4):
    sales = tables["store_sales"]
    # distributed frequent-item count (shuffle by item)
    sales_padded = _pad_to_mesh(sales, mesh)
    freq_padded, counts, _ = distributed_groupby(
        sales_padded,
        ["item_sk"],
        [GroupbyAgg("item_sk", "count")],
        mesh,
    )
    # gather the (small) hot-item list to every chip, host-side finish
    freq = unpad_groupby(freq_padded, counts)
    hot = ops.filter_table(
        freq,
        Column(freq["count_item_sk"].data >= min_count, dt.BOOL8, None),
    )
    # distributed LEFT SEMI against the hot-item list: both sides
    # hash-exchange by item over ICI, then membership lands in the
    # occupancy column of the exchanged shards (the compaction below is
    # a host-side convenience for the next stage)
    hot_pad = _pad_to_mesh(hot, mesh)
    sales_sh, occ, _, _ = distributed_semi_join(
        sales_padded, hot_pad, ["item_sk"], mesh
    )
    hot_sales = _unpad_occupancy(sales_sh, occ)
    spend = ops.mul(hot_sales["quantity"], hot_sales["sales_price"])
    t = Table([*hot_sales.columns, spend], [*hot_sales.names, "spend"])
    # customer_sk is uniform (~rows/20 distinct): the balanced default
    # capacity scales with the mesh instead of replicating the table
    t_padded = _pad_to_mesh(t, mesh)
    return distributed_groupby(
        t_padded, ["customer_sk"], [GroupbyAgg("spend", "sum")], mesh
    )


# ---------------------------------------------------------------------------
# q64-shape: chained dimension joins -> wide-key aggregation
# ---------------------------------------------------------------------------

def _price_cutoff(col, max_price: float):
    """Threshold in the column's own representation (decimal columns
    hold unscaled values: $150.00 at scale -2 is 15000)."""
    scale = col.dtype.scale if col.dtype.is_decimal else 0
    return max_price * (10 ** -scale)


def q64(tables: dict, max_price: float = 150.0) -> Table:
    sales = tables["store_sales"]
    item = tables["item"]
    cheap = ops.filter_table(
        item,
        Column(
            ops.compute.values(item["current_price"])
            <= _price_cutoff(item["current_price"], max_price),
            dt.BOOL8,
            None,
        ),
    )
    j1 = ops.inner_join(sales, cheap, ["item_sk"])
    j2 = ops.inner_join(j1, tables["customer"], ["customer_sk"])
    j3 = ops.inner_join(j2, tables["date_dim"], ["date_sk"])
    rev = ops.mul(j3["quantity"], j3["sales_price"])
    t = Table([*j3.columns, rev], [*j3.names, "revenue"])
    return ops.groupby_aggregate(
        t,
        ["brand_id", "state_id", "year"],
        [GroupbyAgg("revenue", "sum"), GroupbyAgg("revenue", "count")],
    )


def q64_distributed(tables: dict, mesh, max_price: float = 150.0):
    """Distributed q64: the big fact-fact-shaped join (sales x customer)
    shuffles both sides; the small dimension joins (filtered item,
    date_dim) are broadcast hash joins — the fact side never crosses
    the ICI for them."""
    sales = tables["store_sales"]
    item = tables["item"]
    cheap = ops.filter_table(
        item,
        Column(
            ops.compute.values(item["current_price"])
            <= _price_cutoff(item["current_price"], max_price),
            dt.BOOL8,
            None,
        ),
    )
    j1_sh, j1_counts = broadcast_inner_join(
        _pad_to_mesh(sales, mesh), cheap, ["item_sk"], mesh
    )
    j1 = _unpad_join(j1_sh, j1_counts)
    lpad = _pad_to_mesh(j1, mesh)
    rpad = _pad_to_mesh(tables["customer"], mesh)
    num = int(np.prod(list(mesh.shape.values())))
    # customer_sk is unique on the right, so per-device real matches are
    # bounded by the left rows received (<= lpad.row_count); pad rows
    # share _PAD_KEY on both sides and cross-join on one device, adding
    # at most (num-1)^2 pairs
    # exchange capacities auto-plan (lossless); an undersized explicit
    # out_capacity would raise rather than silently corrupt the result
    joined, counts, lov, rov = distributed_inner_join(
        lpad,
        rpad,
        ["customer_sk"],
        mesh,
        out_capacity=lpad.row_count + (num - 1) ** 2,
    )
    out = _unpad_join(joined, counts)
    j3_sh, j3_counts = broadcast_inner_join(
        _pad_to_mesh(out, mesh), tables["date_dim"], ["date_sk"], mesh
    )
    j3 = _unpad_join(j3_sh, j3_counts)
    rev = ops.mul(j3["quantity"], j3["sales_price"])
    t = Table([*j3.columns, rev], [*j3.names, "revenue"])
    return ops.groupby_aggregate(
        t,
        ["brand_id", "state_id", "year"],
        [GroupbyAgg("revenue", "sum"), GroupbyAgg("revenue", "count")],
    )


# ---------------------------------------------------------------------------
# padding helpers (mesh sharding wants row_count % devices == 0; padding
# rows carry a key no real row uses so they aggregate separately and are
# dropped on unpad)
# ---------------------------------------------------------------------------

_PAD_KEY = np.int64(-(2**62))


def _pad_to_mesh(table: Table, mesh) -> Table:
    num = int(np.prod(list(mesh.shape.values())))
    n = table.row_count
    rem = (-n) % num
    if rem == 0:
        return table
    pad_cols = []
    for c in table.columns:
        if c.dtype.is_string:
            # empty-string padding rows (zero bytes, zero lengths)
            data = jnp.zeros((rem, c.data.shape[1]), jnp.uint8)
            pad_cols.append(
                Column(data, c.dtype, None, jnp.zeros((rem,), jnp.int32))
            )
            continue
        fill_vals = jnp.full(
            (rem,) + tuple(c.data.shape[1:]), _PAD_KEY
        ).astype(c.data.dtype)
        pad_cols.append(Column(fill_vals, c.dtype, None))
    pad = Table(pad_cols, list(table.names))
    return ops.concatenate([table, pad])


def _real_mask(table: Table):
    """Per-row bool: not a _PAD_KEY padding row (keyed off the first
    column, which _pad_to_mesh fills with the sentinel)."""
    return table.columns[0].data != jnp.asarray(
        _PAD_KEY, table.columns[0].data.dtype
    )


def unpad_groupby(padded: Table, counts) -> Table:
    """Compact the sharded padded result: keep each device's first
    count rows, drop padding groups (the _PAD_KEY key). Device-side
    filter so storage encodings (FLOAT64 bit patterns) stay intact."""
    cnt = jnp.asarray(counts).reshape(-1)
    n_dev = cnt.shape[0]
    per = padded.row_count // n_dev
    slot = jnp.arange(padded.row_count, dtype=jnp.int32)
    occupied = (slot % per) < cnt[slot // per]
    mask = Column(
        jnp.logical_and(occupied, _real_mask(padded)), dt.BOOL8, None
    )
    return ops.filter_table(padded, mask)


def _unpad_join(padded: Table, counts) -> Table:
    """Same shard-stacking for distributed join output."""
    return unpad_groupby(padded, counts)


def _unpad_occupancy(sharded: Table, occ) -> Table:
    """Compact a padded-shard result by its occupancy column (the
    semi/anti join convention), dropping _PAD_KEY padding rows too."""
    mask = Column(
        jnp.logical_and(jnp.asarray(occ), _real_mask(sharded)),
        dt.BOOL8,
        None,
    )
    return ops.filter_table(sharded, mask)


# compat alias: tests and older call sites used the private name
_unpad_groupby = unpad_groupby
