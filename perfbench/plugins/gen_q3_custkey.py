"""TPC-H ``o_custkey``: uniform over the customers, never a multiple of 3
(4.2.3: a third of the customers have no order). The customers are
``customers_per_order[0] / customers_per_order[1]`` of this table's rows
(1,500,000 for 2,000,000 orders), so a rehearsal's smaller tables stay
referentially consistent."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    num, den = spec["customers_per_order"]
    customers = n * int(num) // int(den)
    # the k-th key that is no multiple of 3: 1, 2, 4, 5, 7, 8, ...
    k = rng.integers(0, customers - customers // 3, n, dtype=np.int64)
    return (k + k // 2 + 1).astype(npdt)
