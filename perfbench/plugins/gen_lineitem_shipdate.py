"""TPC-H 4.2.3 ``l_shipdate`` = the order's date + [lag_lo, lag_hi] days,
the order date uniform in [order_lo, order_hi] (days since 1970-01-01;
every row draws its own order date)."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    order = rng.integers(spec["order_lo"], spec["order_hi"] + 1, n, dtype=np.int64)
    lag = rng.integers(spec["lag_lo"], spec["lag_hi"] + 1, n, dtype=np.int64)
    return (order + lag).astype(npdt)
