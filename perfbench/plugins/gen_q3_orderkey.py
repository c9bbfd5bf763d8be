"""TPC-H ``o_orderkey`` of ONE shuffle partition, and what an order's key
alone decides.

dbgen's order keys are sparse: order ``i`` (from 0) has the key
``(i // 8) * 32 + i % 8 + 1``, 8 of every 32 values (4.2.3). Spark sends
a key to partition ``pmod(murmur3(key, 42), partitions)``; this
partition's orders are the first ``n`` of those, in key order, over the
scale factor's ``orders_total``. ``make_table`` builds every table from a
generator of its own, so what ties ``lineitem`` to ``orders`` has to be
a function of the key that both compute: the order's date and its number
of lines are fixed hashes of the key (`order_date`, `order_lines`), the
same for every seed. The seed moves the rows' order.
"""

import functools

import numpy as np

from ..reference import murmur3_long

_DATE_SALT = 0x51ED270B
# with this salt the first 2,000,000 orders of partition 0 of 7 carry
# 8,006,679 lines and the first 4,000 carry 16,119
# (perfbench/tests/test_tpch_q3.py holds that they suffice)
_LINES_SALT = 0x6


def _mix(keys: np.ndarray, salt: int) -> np.ndarray:
    """splitmix64's finaliser over ``key + salt``: uint64, well spread."""
    with np.errstate(over="ignore"):
        h = keys.astype(np.uint64) + np.uint64(salt)
        h = (h ^ (h >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        h = (h ^ (h >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return h ^ (h >> np.uint64(31))


def order_date(keys: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The order's date, uniform in [lo, hi] days, from its key alone."""
    return lo + (_mix(keys, _DATE_SALT) % np.uint64(hi - lo + 1)).astype(np.int64)


def order_lines(keys: np.ndarray) -> np.ndarray:
    """The order's number of lines, uniform in 1..7 (4.2.3), from its key."""
    return 1 + (_mix(keys, _LINES_SALT) % np.uint64(7)).astype(np.int64)


@functools.lru_cache(maxsize=4)
def partition_keys(n: int, partition: int, partitions: int, total: int) -> np.ndarray:
    """The first ``n`` order keys of the partition, ascending (int64)."""
    want = min(total, int(n * partitions * 1.05) + 1024)
    while True:
        i = np.arange(want, dtype=np.int64)
        keys = (i // 8) * 32 + i % 8 + 1
        pid = np.mod(murmur3_long(keys).astype(np.int64), partitions)
        mine = keys[pid == partition]
        if len(mine) >= n or want >= total:
            break
        want = min(total, 2 * want)
    if len(mine) < n:
        raise ValueError(f"gen q3_orderkey: the partition has {len(mine)} orders, not {n}")
    out = mine[:n]
    out.setflags(write=False)
    return out


def keys_of(spec: dict, n: int) -> np.ndarray:
    return partition_keys(n, int(spec["partition"]), int(spec["partitions"]),
                          int(spec["orders_total"]))


def make(spec, n, rng, cols, npdt):
    return rng.permutation(keys_of(spec, n)).astype(npdt)
