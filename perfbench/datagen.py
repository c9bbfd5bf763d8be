"""Seeded tables from a configuration's ``tables`` section.

Every seed gives the same sizes; only the values move. A column's
``gen`` names a kind below (or ``plugins/gen_<kind>.py``); columns are
made in order, so a later one may be computed from an earlier one.
"""

from __future__ import annotations

import numpy as np

from . import plugins
from .wirefmt import NP_DTYPES, Col, Table


def _uniform_int(spec, n, rng, cols, npdt):
    return rng.integers(spec["lo"], spec["hi"], n, dtype=np.int64).astype(npdt)


def _cents(spec, n, rng, cols, npdt):
    return (rng.integers(spec["lo"], spec["hi"], n) / 100.0).astype(npdt)


def _greater(spec, n, rng, cols, npdt):
    return (cols[spec["of"]].values > spec["than"]).astype(npdt)


def _sorted_sample(spec, n, rng, cols, npdt):
    return np.sort(rng.choice(spec["of"], n, replace=False)).astype(npdt)


def _affine_mod(spec, n, rng, cols, npdt):
    return ((cols[spec["of"]].values * spec["mul"]) % spec["mod"]).astype(npdt)


def _normal(spec, n, rng, cols, npdt):
    return rng.standard_normal(n).astype(npdt)


def _coin(spec, n, rng, cols, npdt):
    return rng.integers(0, 2, n).astype(npdt)


def _half_range(spec, n, rng, cols, npdt):
    info = np.iinfo(npdt)
    return rng.integers(info.min // 2, info.max // 2, n).astype(npdt)


GENS = {
    "uniform_int": _uniform_int, "cents": _cents, "greater": _greater,
    "sorted_sample": _sorted_sample, "affine_mod": _affine_mod,
    "normal": _normal, "coin": _coin, "half_range": _half_range,
}


def make_table(spec: dict, rows: int, rng) -> Table:
    cols, table = {}, []
    for c in spec["columns"]:
        gen = c["gen"]
        make = GENS.get(gen["kind"]) or plugins.find("gen", gen["kind"], "make")
        vals = make(gen, rows, rng, cols, NP_DTYPES[c["type"]])
        nulls = float(c.get("nulls", 0.0))
        valid = rng.random(rows) >= nulls if nulls else None
        col = Col(c["type"], int(c.get("scale", 0)), vals, valid)
        cols[c["name"]] = col
        table.append(col)
    return table
