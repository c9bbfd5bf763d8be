"""The comparison that decides ``correct``.

Every value of an answer is compared with the plain reference: integers,
bytes and validity bits exactly (limit 0 mismatches), a float64 sum
within the configuration's stated tolerance of ``float64_sum_tol x
sum(|reference column|)`` (the engine's float64 sums are cumsum
differences, so their error grows with the column's total, not the
group's). Value bytes under a null are not compared: the format leaves
them undefined.
"""

from __future__ import annotations

import numpy as np

from .wirefmt import Col, Table, table_rows


def _ordered(table: Table, order: str) -> Table:
    if order == "served":
        return table
    if order != "by_column_0":
        raise ValueError(f"compare: no answer order {order!r}")
    idx = np.argsort(table[0].values, kind="stable")
    return [Col(c.type, c.scale, c.values[idx],
                None if c.valid is None else c.valid[idx]) for c in table]


def _nearer_limit(err, lim, best_err, best_lim) -> bool:
    """Is (err, lim) nearer to, or farther over, its limit than the best?"""
    return best_lim == 0.0 or err * best_lim > best_err * lim


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.kind == "f" else a


def compare(got: Table, want: Table, spec: dict, f64_tol: float) -> dict:
    """-> mismatched values (limit 0), and the float64 sums' largest
    absolute error beside its limit (both 0.0 where no sum is compared)."""
    rows = table_rows(want)
    if len(got) != len(want) or table_rows(got) != rows:
        return {"mismatched": max(rows, table_rows(got), 1),
                "f64_err": 0.0, "f64_limit": 0.0}
    got = _ordered(got, spec.get("order", "served"))
    want = _ordered(want, spec.get("order", "served"))
    bad, err, limit = 0, 0.0, 0.0
    for g, w in zip(got, want):
        if (g.type, g.scale) != (w.type, w.scale) or g.values.shape != w.values.shape:
            bad += max(rows, 1)
            continue
        wv = np.ones(rows, bool) if w.valid is None else w.valid
        gv = np.ones(rows, bool) if g.valid is None else g.valid
        bad += int(np.count_nonzero(gv != wv))
        if w.type == "FLOAT64" and spec.get("float64") == "sum_tol":
            e = float(np.max(np.abs(g.values - w.values)[wv], initial=0.0))
            lim = f64_tol * float(np.abs(w.values[wv]).sum())
            if not np.isfinite(e):
                bad += 1
            elif _nearer_limit(e, lim, err, limit):
                err, limit = e, lim
        else:
            bad += int(np.count_nonzero(_bits(g.values)[wv] != _bits(w.values)[wv]))
    return {"mismatched": bad, "f64_err": err, "f64_limit": limit}


def fold(results) -> dict:
    """Many answers' comparisons -> the numbers a run prints: the sum of
    mismatches, and the float64 error nearest to (or farthest over) its
    limit."""
    out = {"answers_compared": 0, "mismatched_values": 0,
           "mismatched_limit": 0, "f64_sum_max_abs_err": 0.0,
           "f64_sum_limit": 0.0}
    for r in results:
        out["answers_compared"] += 1
        out["mismatched_values"] += r["mismatched"]
        if r["f64_limit"] and _nearer_limit(
            r["f64_err"], r["f64_limit"],
            out["f64_sum_max_abs_err"], out["f64_sum_limit"],
        ):
            out["f64_sum_max_abs_err"] = r["f64_err"]
            out["f64_sum_limit"] = r["f64_limit"]
    out["ok"] = bool(
        out["answers_compared"] > 0 and out["mismatched_values"] == 0
        and out["f64_sum_max_abs_err"] <= out["f64_sum_limit"]
    )
    return out
