"""TPC-H 4.2.3 ``l_extendedprice`` = ``l_quantity`` x the part's retail
price, ``p_retailprice`` = (90000 + ((partkey / 10) mod 20001) + 100 x
(partkey mod 1000)) / 100: exact cents (scale -2), at most 104,950.00."""

import numpy as np


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    partkey = partkey.astype(np.int64)
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def make(spec, n, rng, cols, npdt):
    units = cols[spec["quantity"]].values.astype(np.int64) // 100
    return (units * retail_cents(cols[spec["partkey"]].values)).astype(npdt)
