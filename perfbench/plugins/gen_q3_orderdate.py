"""TPC-H ``o_orderdate``: uniform in [lo, hi] days, a fixed hash of the
order's key (`gen_q3_orderkey.order_date`), so that ``lineitem`` can give
the same order the same date without seeing ``orders``."""

from .gen_q3_orderkey import order_date


def make(spec, n, rng, cols, npdt):
    return order_date(cols[spec["of"]].values, spec["lo"], spec["hi"]).astype(npdt)
