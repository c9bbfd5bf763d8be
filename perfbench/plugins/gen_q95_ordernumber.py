"""TPC-DS ``ws_order_number`` of ONE shuffle partition of ``web_sales``
hash partitioned by the order number, as q95's ``ws_wh`` self-join
holds it: walk the scale factor's order numbers upward from
``first_order``, keep those Spark sends to this partition
(``pmod(murmur3(key, 42), partitions)``), give each its ``lines`` (lo,
hi) line items (dsdgen: a web order has 8 to 16) by a fixed hash of the
key, an order's lines adjacent, keys ascending, cut at this
table's rows.

``first_order`` puts every key past 2^24 (as `gen_q18_lineorderkey`
learnt: a 64-bit key that never leaves 24 bits can be carried in
float32 or INT32 with nothing lost, and no comparison could tell).
``ws_order_number`` is no seeded value: the column is the same for every
seed and variant, so the join's output rows (the sum of every order's
lines squared) are ONE number a size, which
perfbench/tests/test_tpcds_q95.py asserts against the output's bucket;
the seed moves the warehouses."""

import functools

import numpy as np

from ..reference import murmur3_long
from .gen_q3_orderkey import _mix

_LINES_SALT = 0x95


def order_lines(keys: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The order's number of lines, uniform in lo..hi, from its key."""
    return lo + (_mix(keys, _LINES_SALT) % np.uint64(hi - lo + 1)).astype(np.int64)


@functools.lru_cache(maxsize=4)
def partition_lines(n: int, first: int, total: int, partition: int,
                    partitions: int, lo: int, hi: int) -> np.ndarray:
    """The first ``n`` rows of the partition's order numbers from
    ``first`` on, every order as often as it has lines, ascending."""
    want = min(total - first + 1, (n // lo + 1) * partitions + 4096)
    while True:
        keys = first + np.arange(want, dtype=np.int64)
        pid = np.mod(murmur3_long(keys).astype(np.int64), partitions)
        mine = keys[pid == partition]
        lines = order_lines(mine, lo, hi)
        have = int(lines.sum())
        if have >= n or first + want > total:
            break
        want = min(total - first + 1, 2 * want)
    if have < n:
        raise ValueError(
            f"gen q95_ordernumber: orders {first}..{total} of partition "
            f"{partition} carry {have} lines, not {n}")
    out = np.repeat(mine, lines)[:n]
    out.setflags(write=False)
    return out


def make(spec, n, rng, cols, npdt):
    lo, hi = spec["lines"]
    return partition_lines(
        n, int(spec["first_order"]), int(spec["orders_total"]),
        int(spec["partition"]), int(spec["partitions"]), int(lo), int(hi),
    ).astype(npdt)
