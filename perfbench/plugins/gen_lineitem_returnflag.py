"""TPC-H 4.2.3 ``l_returnflag``: R or A (even odds) where the line was
received by CURRENTDATE (1995-06-17), else N; the letter's ASCII code."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    received = cols[spec["receiptdate"]].values <= spec["currentdate"]
    r_or_a = np.where(rng.integers(0, 2, n) == 0, ord("R"), ord("A"))
    return np.where(received, r_or_a, ord("N")).astype(npdt)
