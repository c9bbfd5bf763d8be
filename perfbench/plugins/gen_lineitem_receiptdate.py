"""TPC-H 4.2.3 ``l_receiptdate`` = ``l_shipdate`` + [lag_lo, lag_hi] days."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    lag = rng.integers(spec["lag_lo"], spec["lag_hi"] + 1, n, dtype=np.int64)
    return (cols[spec["shipdate"]].values.astype(np.int64) + lag).astype(npdt)
