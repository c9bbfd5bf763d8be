"""TPC-H ``l_orderkey`` of one scan batch of ``lineitem`` taken from
anywhere in the scale factor's key range: the orders from ``first_order``
on (dbgen's order ``i``, from 0, has the sparse key
``(i // 8) * 32 + i % 8 + 1``, 4.2.3), each as many times as it has lines
(`gen_q3_orderkey.order_lines`, a fixed hash of the key, 1..7), cut at
this table's rows in key order and handed over largest key first, as
`gen_q3_lineorderkey` hands its own.

`gen_q3_lineorderkey` serves a batch only from the START of the key
range (the first orders of a partition), where SF10's keys stay under
2^24 for the first 4,194,304 orders: every key of an 8,000,000-row
batch is then a whole number that float32 holds, and no control that
narrows the key could ever lose a group. ``first_order`` puts the batch
where the scale factor's keys really lie (SF10's reach 60,000,000), so
that a 64-bit key carried in 24 bits of mantissa merges neighbouring
orders. ``l_orderkey`` is no seeded value: the column is the same for
every seed and the seed moves the line's other values."""

import numpy as np

from .gen_q3_orderkey import order_lines


def make(spec, n, rng, cols, npdt):
    num, den = spec["orders_per_line"]
    first, total = int(spec["first_order"]), int(spec["orders_total"])
    i = first + np.arange(n * int(num) // int(den), dtype=np.int64)
    if len(i) and i[-1] >= total:
        raise ValueError(
            f"gen q18_lineorderkey: orders {first}..{i[-1]} pass the scale "
            f"factor's {total}")
    keys = (i // 8) * 32 + i % 8 + 1
    lines = np.repeat(keys, order_lines(keys))
    if len(lines) < n:
        raise ValueError(
            f"gen q18_lineorderkey: {len(keys)} orders carry {len(lines)} lines, not {n}")
    return lines[:n][::-1].astype(npdt)
