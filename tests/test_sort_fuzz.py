"""ORDER BY fuzz vs the pandas sort oracle.

Random multi-key sorts — mixed directions, explicit and Spark-default
null placement, int/float/string keys, duplicate keys (stability) —
against ``DataFrame.sort_values`` with matching na_position."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops.sort import SortKey, sort_table


def _frame(rng, n, with_nulls):
    k1 = rng.integers(-20, 20, n, dtype=np.int64)
    k2 = rng.standard_normal(n).round(2)
    v = np.arange(n, dtype=np.int64)  # row id: makes stability visible
    valid = rng.random(n) > 0.15 if with_nulls else None
    cols = [
        Column.from_numpy(k1, validity=valid),
        Column.from_numpy(k2),
        Column.from_numpy(v),
    ]
    t = Table(cols, ["k1", "k2", "v"])
    pdf = pd.DataFrame({"k1": k1, "k2": k2, "v": v})
    if valid is not None:
        pdf["k1"] = pdf["k1"].astype("Int64").mask(~valid)
    return t, pdf


def _check(got: Table, pdf_sorted: pd.DataFrame):
    for name in got.names:
        g = got[name].to_pylist()
        w = [
            None if pd.isna(x) else (float(x) if name == "k2" else int(x))
            for x in pdf_sorted[name]
        ]
        assert g == w, name


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("asc1,asc2", [(True, True), (False, True),
                                       (True, False), (False, False)])
def test_two_key_mixed_directions(seed, asc1, asc2):
    rng = np.random.default_rng(seed)
    t, pdf = _frame(rng, 300, with_nulls=False)
    got = sort_table(t, [SortKey("k1", asc1), SortKey("k2", asc2)])
    want = pdf.sort_values(
        ["k1", "k2"], ascending=[asc1, asc2], kind="stable"
    )
    _check(got, want)


@pytest.mark.parametrize("asc", [True, False])
@pytest.mark.parametrize("nulls_first", [None, True, False])
def test_null_placement(asc, nulls_first):
    rng = np.random.default_rng(9)
    t, pdf = _frame(rng, 300, with_nulls=True)
    got = sort_table(
        t, [SortKey("k1", asc, nulls_first), SortKey("v")]
    )
    eff_first = nulls_first if nulls_first is not None else asc
    want = pdf.sort_values(
        ["k1", "v"],
        ascending=[asc, True],
        kind="stable",
        na_position="first" if eff_first else "last",
    )
    _check(got, want)


def test_stability_on_duplicate_keys():
    rng = np.random.default_rng(4)
    n = 400
    k = rng.integers(0, 5, n, dtype=np.int64)  # heavy duplicates
    v = np.arange(n, dtype=np.int64)
    t = Table([Column.from_numpy(k), Column.from_numpy(v)], ["k", "v"])
    got = sort_table(t, [SortKey("k")])
    want = pd.DataFrame({"k": k, "v": v}).sort_values("k", kind="stable")
    _check(got, want)


def test_string_key_nulls_ordered_by_secondary():
    """Multi-word (string) nullable key: EVERY key word must zero for
    null rows, or the null block reorders by hidden bytes."""
    subs = ["zz", None, "aa", None, "mm", None]
    t = Table(
        [Column.from_strings(subs),
         Column.from_numpy(np.arange(6, dtype=np.int64))],
        ["k", "r"],
    )
    out = sort_table(t, [SortKey("k", True, None), SortKey("r")])
    assert out["k"].to_pylist() == [None, None, None, "aa", "mm", "zz"]
    assert out["r"].to_pylist() == [1, 3, 5, 2, 4, 0]
