"""TPC-H ``l_orderkey`` of the partition's ``lineitem``: every order of the
partition, as many times as it has lines (`gen_q3_orderkey.order_lines`),
cut at this table's rows in key order and handed over largest key first.
``l_orderkey`` is no seeded value, so the column is the same for every
seed and the seed moves the line's other values. ``orders`` names the
rows of the ``orders`` table as a share of this table's
(``orders_per_line``: 1 of 4), whose keys are all this column may hold.

Why a fixed order, and this one (PERF.md section 6, PR 40, round 3): a
filter's compaction fills the rows behind its count with clones of the
table's ROW 0, and a join probes them like any row: behind Q3's filter
46% of the probe's lanes search row 0's key. A search that reads its
tables from HBM pays for that one path by its addresses: a second of 23
by the key a seeded order puts there, and 0.36 s more by whether the
build side's size crosses 194,560 rows when the key is the smallest.
Only a key above every build key takes a path no seed moves."""

import numpy as np

from .gen_q3_orderkey import keys_of, order_lines


def make(spec, n, rng, cols, npdt):
    num, den = spec["orders_per_line"]
    keys = keys_of(spec, n * int(num) // int(den))
    lines = np.repeat(keys, order_lines(keys))
    if len(lines) < n:
        raise ValueError(
            f"gen q3_lineorderkey: {len(keys)} orders carry {len(lines)} lines, not {n}")
    return lines[:n][::-1].astype(npdt)
