"""A dense unique key in a seeded order: 1..n, each once (``c_custkey``)."""

import numpy as np


def make(spec, n, rng, cols, npdt):
    return (rng.permutation(n).astype(np.int64) + 1).astype(npdt)
