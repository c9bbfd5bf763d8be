"""TPC-DS-class real-data benchmark: seeded dbgen-equivalent to Parquet
plus scan-driven q5/q23/q64 pipelines with pandas oracles.

Round-4 VERDICT item 6: the in-memory DAGs in benchmarks/queries.py
prove operator shapes, but configs 4-5 call for REAL
Parquet scans — decimals, strings, nulls, row-group streaming — feeding
shuffle/join/agg. This module is that end-to-end path:

  generate_parquet  spec-inspired star schema (store_sales, web_sales,
                    item, customer, date_dim) at a scale factor:
                    SF 1 ~ 2.88M store_sales rows (the TPC-DS ratio),
                    DECIMAL(7,2) money columns, nullable FKs (~4%, like
                    dbgen), string dimension attributes.
  q5_stream         channel union -> date-window pushdown -> item join
                    -> category rollup, streamed per row group.
  q23_stream        frequent-item CTE over store_sales -> semi join of
                    web_sales -> per-customer aggregation.
  q64_stream        store_sales -> item (price filter) -> customer ->
                    wide-key aggregation.
  oracle_*          the same queries in pandas/pyarrow on the same
                    files; run_all() compares counts exactly and money
                    totals at float64 precision (sums in cents stay
                    under 2^53 through SF100, so this is exact too).

Streaming model: dimensions load resident (they are the small side;
the reference broadcasts them, GpuBroadcastHashJoinExec), fact batches
arrive via io.parquet.scan_parquet with predicate pushdown + prefetch,
each batch joins + partially aggregates on device, and one final
groupby combines the partials — the two-level shape the chunked
groupby (ops/groupby_chunked.py) uses, applied across IO batches.
"""

from __future__ import annotations

import os
import time

import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import ops
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.io.parquet import read_parquet, scan_parquet
from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg, groupby_aggregate

# spec row-count ratios (TPC-DS dbgen at SF1, rounded)
_SS_PER_SF = 2_880_000
_WS_PER_SF = 720_000
_CUST_PER_SF = 100_000
_ITEM_SF1 = 18_000
_N_DATES = 73_049  # 1900..2100, the fixed TPC-DS calendar


def _money(rng, n, lo=50, hi=20_000):
    """DECIMAL(7,2) money as unscaled cents."""
    return rng.integers(lo, hi, n, dtype=np.int64)


def generate_parquet(out_dir: str, scale: float = 0.01, seed: int = 0):
    """Write the star schema to ``out_dir``; returns a manifest dict."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(seed)
    n_ss = max(int(_SS_PER_SF * scale), 1000)
    n_ws = max(int(_WS_PER_SF * scale), 250)
    n_cust = max(int(_CUST_PER_SF * scale), 100)
    n_item = max(int(_ITEM_SF1 * max(scale, 1) ** 0.5), 100)
    os.makedirs(out_dir, exist_ok=True)
    money = pa.decimal128(7, 2)

    def write(name, table, row_group_rows):
        pq.write_table(
            table, os.path.join(out_dir, f"{name}.parquet"),
            row_group_size=row_group_rows,
        )

    # date_dim: dense sk, year/moy derivable from sk
    d_sk = np.arange(_N_DATES, dtype=np.int64)
    write(
        "date_dim",
        pa.table({
            "d_date_sk": d_sk,
            "d_year": 1900 + d_sk // 365,
            "d_moy": (d_sk % 365) // 31 + 1,
        }),
        _N_DATES,
    )

    # item: skewed brand/category, string attributes, decimal price
    i_sk = np.arange(n_item, dtype=np.int64)
    write(
        "item",
        pa.table({
            "i_item_sk": i_sk,
            "i_item_id": pa.array(
                [f"AAAAAAAA{i:08d}" for i in range(n_item)]
            ),
            "i_brand_id": rng.integers(1, 1000, n_item),
            "i_category_id": rng.integers(1, 11, n_item),
            "i_brand": pa.array(
                [f"brand#{int(b):03d}" for b in rng.integers(0, 200, n_item)]
            ),
            "i_category": pa.array(
                [
                    ["Books", "Home", "Electronics", "Jewelry", "Men",
                     "Music", "Shoes", "Sports", "Children", "Women"][c]
                    for c in rng.integers(0, 10, n_item)
                ]
            ),
            "i_current_price": pa.array(
                _money(rng, n_item) / 100.0
            ).cast(money),
        }),
        max(n_item, 1024),
    )

    # customer: nullable names/birth year (dbgen leaves ~3% null)
    c_sk = np.arange(n_cust, dtype=np.int64)
    first = rng.integers(0, 512, n_cust)
    last = rng.integers(0, 2048, n_cust)
    name_null = rng.random(n_cust) < 0.03
    write(
        "customer",
        pa.table({
            "c_customer_sk": c_sk,
            "c_first_name": pa.array(
                [None if m else f"F{v:03d}" for m, v in zip(name_null, first)]
            ),
            "c_last_name": pa.array(
                [None if m else f"L{v:04d}" for m, v in zip(name_null, last)]
            ),
            "c_birth_year": pa.array(
                np.where(rng.random(n_cust) < 0.03, -1,
                         rng.integers(1930, 2005, n_cust))
            ).cast(pa.int64()),
            # ca_state folded onto customer (spec keeps it on the
            # customer_address dimension; one less table, same join/agg
            # shape for the q64 group-by)
            "c_state_id": rng.integers(0, 50, n_cust),
        }),
        max(n_cust, 4096),
    )

    def fact(n):
        # zipf item popularity: the join/shuffle skew that matters
        item_fk = (rng.zipf(1.2, n) - 1) % n_item
        cust_null = rng.random(n) < 0.04  # dbgen null FK rate
        cust_fk = rng.integers(0, n_cust, n)
        return pa.table({
            "sold_date_sk": rng.integers(0, _N_DATES, n),
            "item_sk": item_fk.astype(np.int64),
            "customer_sk": pa.array(cust_fk, mask=cust_null),
            "quantity": rng.integers(1, 100, n),
            "sales_price": pa.array(_money(rng, n) / 100.0).cast(money),
            "ext_sales_price": pa.array(
                _money(rng, n, 100, 3_000_000) / 100.0
            ).cast(money),
            "net_profit": pa.array(
                rng.integers(-500_000, 1_200_000, n) / 100.0
            ).cast(money),
        })

    rg = 1 << 19  # ~512k-row groups: the streaming batch unit
    write("store_sales", fact(n_ss), rg)
    write("web_sales", fact(n_ws), rg)
    return {
        "dir": out_dir, "scale": scale, "store_sales": n_ss,
        "web_sales": n_ws, "item": n_item, "customer": n_cust,
    }


# ---------------------------------------------------------------------------
# streamed queries (scan -> join -> agg)
# ---------------------------------------------------------------------------


def _combine_partials(partials, by, agg_specs):
    whole = ops.concatenate(partials) if len(partials) > 1 else partials[0]
    return groupby_aggregate(whole, by, agg_specs)


_DATE_LO, _DATE_HI = 36_000, 36_730  # a 2-year window in the calendar


def q5_stream(data_dir: str, prefetch: int = 2) -> Table:
    """Channel union -> date pushdown -> item join -> category rollup."""
    from spark_rapids_jni_tpu.io.predicates import col as C

    item = read_parquet(
        os.path.join(data_dir, "item.parquet"),
        columns=["i_item_sk", "i_category_id"],
    )
    pred = (C("sold_date_sk") >= _DATE_LO) & (C("sold_date_sk") < _DATE_HI)
    partials = []
    for name in ("store_sales", "web_sales"):
        for batch in scan_parquet(
            os.path.join(data_dir, f"{name}.parquet"),
            columns=["sold_date_sk", "item_sk", "ext_sales_price",
                     "net_profit"],
            filters=pred,
            prefetch=prefetch,
        ):
            joined = ops.inner_join(
                batch, item, ["item_sk"], ["i_item_sk"]
            )
            partials.append(
                groupby_aggregate(
                    joined, ["i_category_id"],
                    [GroupbyAgg("ext_sales_price", "sum", "sales"),
                     GroupbyAgg("net_profit", "sum", "profit"),
                     GroupbyAgg("item_sk", "count", "n")],
                )
            )
    return _combine_partials(
        partials, ["i_category_id"],
        [GroupbyAgg("sales", "sum", "sales"),
         GroupbyAgg("profit", "sum", "profit"),
         GroupbyAgg("n", "sum", "n")],
    )


def q23_stream(data_dir: str, min_count: int = 50, prefetch: int = 2) -> Table:
    """Frequent-item CTE -> semi join -> per-customer aggregation."""
    # pass 1: item frequency over store_sales
    partials = []
    for batch in scan_parquet(
        os.path.join(data_dir, "store_sales.parquet"),
        columns=["item_sk"],
        prefetch=prefetch,
    ):
        partials.append(
            groupby_aggregate(
                batch, ["item_sk"], [GroupbyAgg("item_sk", "count", "n")]
            )
        )
    freq = _combine_partials(
        partials, ["item_sk"], [GroupbyAgg("n", "sum", "n")]
    )
    hot_mask = Column(freq["n"].data >= min_count, dt.BOOL8, None)
    hot = ops.filter_table(freq, hot_mask)

    # pass 2: web_sales rows on frequent items -> customer totals
    partials = []
    for batch in scan_parquet(
        os.path.join(data_dir, "web_sales.parquet"),
        columns=["item_sk", "customer_sk", "sales_price"],
        prefetch=prefetch,
    ):
        kept = ops.semi_join(batch, hot, ["item_sk"])
        partials.append(
            groupby_aggregate(
                kept, ["customer_sk"],
                [GroupbyAgg("sales_price", "sum", "total")],
            )
        )
    return _combine_partials(
        partials, ["customer_sk"], [GroupbyAgg("total", "sum", "total")]
    )


def q64_stream(
    data_dir: str, max_price: float = 50.0, prefetch: int = 2
) -> Table:
    """store_sales -> item(price<cap) -> customer -> (brand, birth_year)."""
    item = read_parquet(
        os.path.join(data_dir, "item.parquet"),
        columns=["i_item_sk", "i_brand_id", "i_current_price"],
    )
    # DECIMAL(7,2) predicate on the unscaled cents (exact, no decode)
    unscaled_cap = int(round(max_price * 100))
    keep = Column(
        item["i_current_price"].data < unscaled_cap, dt.BOOL8, None
    )
    item = ops.filter_table(item, keep)
    customer = read_parquet(
        os.path.join(data_dir, "customer.parquet"),
        columns=["c_customer_sk", "c_birth_year"],
    )
    partials = []
    for batch in scan_parquet(
        os.path.join(data_dir, "store_sales.parquet"),
        columns=["item_sk", "customer_sk", "ext_sales_price"],
        prefetch=prefetch,
    ):
        j1 = ops.inner_join(batch, item, ["item_sk"], ["i_item_sk"])
        j2 = ops.inner_join(
            j1, customer, ["customer_sk"], ["c_customer_sk"]
        )
        partials.append(
            groupby_aggregate(
                j2, ["i_brand_id", "c_birth_year"],
                [GroupbyAgg("ext_sales_price", "sum", "sales"),
                 GroupbyAgg("item_sk", "count", "n")],
            )
        )
    return _combine_partials(
        partials, ["i_brand_id", "c_birth_year"],
        [GroupbyAgg("sales", "sum", "sales"), GroupbyAgg("n", "sum", "n")],
    )


# ---------------------------------------------------------------------------
# pandas oracles (same files, same predicates)
# ---------------------------------------------------------------------------


_MONEY_COLS = {
    "sales_price", "ext_sales_price", "net_profit", "i_current_price",
}


def _read_pd(data_dir, name, columns):
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pq.read_table(os.path.join(data_dir, f"{name}.parquet"),
                      columns=columns)
    # decimal -> float64 for the oracle (sums in cents stay < 2^53)
    t = pa.table(
        {
            c: (t[c].cast(pa.float64()) if c in _MONEY_COLS else t[c])
            for c in t.column_names
        }
    )
    return t.to_pandas()


def oracle_q5(data_dir):
    import pandas as pd

    item = _read_pd(data_dir, "item", ["i_item_sk", "i_category_id"])
    frames = []
    for name in ("store_sales", "web_sales"):
        df = _read_pd(
            data_dir, name,
            ["sold_date_sk", "item_sk", "ext_sales_price", "net_profit"],
        )
        df = df[(df.sold_date_sk >= _DATE_LO) & (df.sold_date_sk < _DATE_HI)]
        frames.append(df)
    fact = pd.concat(frames).merge(
        item, left_on="item_sk", right_on="i_item_sk"
    )
    return (
        fact.groupby("i_category_id")
        .agg(sales=("ext_sales_price", "sum"),
             profit=("net_profit", "sum"), n=("item_sk", "count"))
        .reset_index()
    )


def oracle_q23(data_dir, min_count: int = 50):
    ss = _read_pd(data_dir, "store_sales", ["item_sk"])
    hot = ss.groupby("item_sk").size()
    hot = set(hot[hot >= min_count].index)
    ws = _read_pd(
        data_dir, "web_sales", ["item_sk", "customer_sk", "sales_price"]
    )
    hot_ws = ws[ws.item_sk.isin(hot)]
    kept = hot_ws.dropna(subset=["customer_sk"])
    # ours groups null customer keys too; pandas dropna covers the
    # non-null groups, the null group's total is verified separately
    out = kept.groupby("customer_sk").sales_price.sum().reset_index()
    null_sum = float(hot_ws[hot_ws.customer_sk.isna()].sales_price.sum())
    return out, null_sum


def oracle_q64(data_dir, max_price: float = 50.0):
    item = _read_pd(
        data_dir, "item", ["i_item_sk", "i_brand_id", "i_current_price"]
    )
    item = item[item.i_current_price.astype(float) < max_price]
    cust = _read_pd(data_dir, "customer", ["c_customer_sk", "c_birth_year"])
    ss = _read_pd(
        data_dir, "store_sales", ["item_sk", "customer_sk", "ext_sales_price"]
    )
    j = (
        ss.dropna(subset=["customer_sk"])
        .merge(item, left_on="item_sk", right_on="i_item_sk")
        .merge(cust, left_on="customer_sk", right_on="c_customer_sk")
    )
    return (
        j.groupby(["i_brand_id", "c_birth_year"])
        .agg(sales=("ext_sales_price", "sum"), n=("item_sk", "count"))
        .reset_index()
    )


def load_tables(data_dir: str) -> dict:
    """Load the Parquet star schema into the in-memory column names the
    benchmarks/queries.py DAGs (and their distributed variants) expect —
    the bridge between this module's real files and the mesh pipelines."""
    ss = read_parquet(
        os.path.join(data_dir, "store_sales.parquet"),
        columns=["item_sk", "customer_sk", "sold_date_sk", "quantity",
                 "sales_price", "net_profit"],
    )
    ws = read_parquet(
        os.path.join(data_dir, "web_sales.parquet"),
        columns=["item_sk", "customer_sk", "sold_date_sk", "quantity",
                 "sales_price", "net_profit"],
    )

    def rename(t, names):
        return Table(list(t.columns), names)

    fact_names = ["item_sk", "customer_sk", "date_sk", "quantity",
                  "sales_price", "net_profit"]
    item = read_parquet(
        os.path.join(data_dir, "item.parquet"),
        columns=["i_item_sk", "i_brand_id", "i_category_id",
                 "i_current_price", "i_brand"],
    )
    customer = read_parquet(
        os.path.join(data_dir, "customer.parquet"),
        columns=["c_customer_sk", "c_birth_year", "c_state_id"],
    )
    date_dim = read_parquet(os.path.join(data_dir, "date_dim.parquet"))
    return {
        "store_sales": rename(ss, fact_names),
        "web_sales": rename(ws, fact_names),
        "item": rename(
            item,
            ["item_sk", "brand_id", "category_id", "current_price", "brand"],
        ),
        "customer": rename(
            customer, ["customer_sk", "birth_year", "state_id"]
        ),
        "date_dim": rename(date_dim, ["date_sk", "year", "moy"]),
    }


def run_distributed(data_dir: str, devices: int) -> list[dict]:
    """q5/q23/q64 distributed DAGs over an N-device mesh, fed from the
    Parquet files (scan -> shuffle-exchange -> join -> agg): the
    config-4 shape with real data instead of in-memory
    synthetics."""
    from benchmarks import queries
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices)
    tables = load_tables(data_dir)
    out = []
    runs = [
        ("q5", lambda: queries.q5_distributed(
            tables, mesh, date_lo=_DATE_LO, date_hi=_DATE_HI)),
        ("q23", lambda: queries.q23_distributed(tables, mesh, min_count=50)),
        ("q64", lambda: queries.q64_distributed(tables, mesh)),
    ]
    for name, fn in runs:
        fn()  # compile warmup
        t0 = time.perf_counter()
        r = fn()
        leaf = r[0] if isinstance(r, tuple) else r
        np.asarray(leaf.columns[0].data.ravel()[-1:])
        out.append(
            {"name": f"tpcds_{name}_mesh{devices}",
             "seconds": round(time.perf_counter() - t0, 3),
             "devices": devices}
        )
    return out


def _dec_to_float(col: Column) -> np.ndarray:
    vals = np.asarray(col.to_numpy(), dtype=np.float64)
    if col.dtype.is_decimal:
        vals = vals * (10.0 ** col.dtype.scale)
    return vals


def run_all(data_dir: str, prefetch: int = 2) -> list[dict]:
    """Run the three pipelines; wall-clock + oracle verdicts."""
    results = []

    t0 = time.perf_counter()
    q5 = q5_stream(data_dir, prefetch)
    np.asarray(q5.columns[1].data.ravel()[-1:])  # force
    q5_s = time.perf_counter() - t0
    o5 = oracle_q5(data_dir)
    order = np.argsort(np.asarray(q5["i_category_id"].to_numpy()))
    ok5 = (
        q5.row_count == len(o5)
        and np.allclose(
            _dec_to_float(q5["sales"])[order],
            o5.sort_values("i_category_id")["sales"].to_numpy(np.float64),
        )
        and np.array_equal(
            np.asarray(q5["n"].to_numpy())[order],
            o5.sort_values("i_category_id")["n"].to_numpy(np.int64),
        )
    )
    results.append(
        {"name": "tpcds_q5_stream", "seconds": round(q5_s, 3),
         "groups": q5.row_count, "oracle_match": bool(ok5)}
    )

    t0 = time.perf_counter()
    q23 = q23_stream(data_dir)
    np.asarray(q23.columns[1].data.ravel()[-1:])
    q23_s = time.perf_counter() - t0
    o23, null_sum = oracle_q23(data_dir)
    kk = q23["customer_sk"]
    nonnull = (
        np.ones(q23.row_count, bool)
        if kk.validity is None
        else np.asarray(kk.validity)
    )
    totals = _dec_to_float(q23["total"])
    got_tot = totals[nonnull].sum()
    got_null = totals[~nonnull].sum()  # exactly one null-key group
    ok23 = (
        int(nonnull.sum()) == len(o23)
        and int((~nonnull).sum()) <= 1
        and np.isclose(got_tot, o23.sales_price.sum())
        and np.isclose(got_null, null_sum)
    )
    results.append(
        {"name": "tpcds_q23_stream", "seconds": round(q23_s, 3),
         "groups": q23.row_count, "oracle_match": bool(ok23)}
    )

    t0 = time.perf_counter()
    q64 = q64_stream(data_dir)
    np.asarray(q64.columns[2].data.ravel()[-1:])
    q64_s = time.perf_counter() - t0
    o64 = oracle_q64(data_dir)
    ok64 = q64.row_count == len(o64) and np.isclose(
        _dec_to_float(q64["sales"]).sum(), o64.sales.sum()
    )
    results.append(
        {"name": "tpcds_q64_stream", "seconds": round(q64_s, 3),
         "groups": q64.row_count, "oracle_match": bool(ok64)}
    )
    return results
