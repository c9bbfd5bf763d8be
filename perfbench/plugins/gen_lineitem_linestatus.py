"""TPC-H 4.2.3 ``l_linestatus``: O where the line ships after CURRENTDATE
(1995-06-17), else F; the letter's ASCII code."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    shipped_later = cols[spec["shipdate"]].values > spec["currentdate"]
    return np.where(shipped_later, ord("O"), ord("F")).astype(npdt)
