"""TPC-H 4.2.3 ``l_quantity``: uniform whole units in [lo, hi], stored at
the column's scale -2 (DECIMAL(15,2): 1.00 .. 50.00)."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    units = rng.integers(spec["lo"], spec["hi"] + 1, n, dtype=np.int64)
    return (units * 100).astype(npdt)
