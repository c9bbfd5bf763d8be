"""TPC-H 4.2.3 ``l_shipdate`` = the ORDER's date + [lag_lo, lag_hi] days:
the date `gen_q3_orderkey.order_date` gives the line's order key, the one
``orders`` holds for it."""

import numpy as np

from .gen_q3_orderkey import order_date


def make(spec, n, rng, cols, npdt):
    order = order_date(cols[spec["orderkey"]].values, spec["order_lo"], spec["order_hi"])
    lag = rng.integers(spec["lag_lo"], spec["lag_hi"] + 1, n, dtype=np.int64)
    return (order + lag).astype(npdt)
