"""Keys from a truncated Zipf distribution: rank ``k`` of ``[1, of]``
with probability ``k^-s / H`` (``H`` = the sum of ``k^-s`` over the
ranks), by inverse CDF (``searchsorted`` over the cumulated weights),
then rank -> key through a permutation of ``[0, of)`` drawn from the
table's own ``rng``: the hot keys, and with them the partition a hash
sends them to, differ between seeds and variants, the sizes never."""

import numpy as np


def weights(of: int, s: float) -> np.ndarray:
    """``k^-s / H`` for the ranks 1..``of``: the share of the rows each
    rank holds, hottest first."""
    w = np.arange(1, of + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


def make(spec, n, rng, cols, npdt):
    of = int(spec["of"])
    cdf = np.cumsum(weights(of, spec["s"]))
    rank = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), of - 1)
    return rng.permutation(of)[rank].astype(npdt)
