"""The materialise's two forms give the same rows (PR 51).

Where a join's output is wider than its probe side, `ops.join._expand`
and `_join_output` spread the probe side's values down each row's run of
slots (one scatter of first differences and one cumsum a 32-bit word:
`_Runs`) instead of gathering them; `mat_spreads` chooses, from the two
static widths alone. Here both forms run over the SAME match ranges and
must agree on every slot under the count: the index arrays, the masks,
and every leaf of every output column, bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops import join as J

I64 = np.iinfo(np.int64)
BUILD_ROWS = 37


def _ints(n, seed=0):
    return Column.from_numpy(
        np.random.default_rng(seed).integers(I64.min, I64.max, n, np.int64)
    )


def _floats(n):
    v = np.random.default_rng(1).standard_normal(n)
    v[:4] = [np.nan, -0.0, np.inf, -np.inf]
    bits = v.view(np.uint64).copy()
    bits[4] = 0x7FF8_0000_DEAD_BEEF  # a NaN with a payload
    return Column.from_numpy(bits.view(np.float64))


def _narrow(npdt):
    def make(n):
        info = np.iinfo(npdt)
        v = np.random.default_rng(2).integers(info.min, info.max + 1, n)
        v[:2] = [info.min, info.max]
        return Column.from_numpy(v.astype(npdt))
    return make


def _bools(n):
    v = np.random.default_rng(3).random(n) < 0.5
    return Column(jnp.asarray(v), dt.BOOL8, None)


def _nullable(n):
    rng = np.random.default_rng(4)
    return Column.from_numpy(
        rng.integers(I64.min, I64.max, n, np.int64),
        validity=rng.random(n) < 0.7,
    )


def _strings(n):
    return Column.from_strings(
        [None if i % 5 == 3 else "w" * (i % 7) + str(i) for i in range(n)]
    )


def _wrap(n):
    """Neighbours a whole INT64 range apart: every first difference
    wraps, in both words."""
    v = np.where(np.arange(n) % 2 == 0, I64.min, I64.max).astype(np.int64)
    v[n // 2] = -1
    return Column.from_numpy(v)


# counts a probe row (0: it emits nothing); the output's width is
# sum(counts) + the case's `room` (negative: a capacity under the count)
MIXED = [3, 0, 0, 5, 1, 0, 7, 2, 0, 4]
CASES = {
    "none_at_the_head": dict(counts=[0, 0, 0, 4, 6, 2, 9, 1, 3, 5]),
    "none_inside": dict(counts=[4, 6, 0, 0, 0, 2, 9, 0, 3, 5]),
    "none_at_the_tail": dict(counts=[4, 6, 2, 9, 1, 3, 5, 0, 0, 0]),
    "none_anywhere": dict(counts=[0] * 10, room=16),
    "exactly_at_total": dict(counts=MIXED, room=0),
    "one_under_total": dict(counts=MIXED, room=1),
    "capacity_under_the_count": dict(counts=MIXED, room=-6),
    "capacity_cuts_a_run_s_first_slot": dict(counts=MIXED, room=-2),
    "int64_min_and_max": dict(counts=MIXED, room=3, left=_wrap),
    "float64_bits": dict(counts=MIXED, room=3, left=_floats),
    "float32_bits": dict(
        counts=MIXED, room=3,
        left=lambda n: Column.from_numpy(
            np.array([np.nan, -0.0, 1.5, -2.25] * n, np.float32)[:n]),
    ),
    "int32": dict(counts=MIXED, room=3, left=_narrow(np.int32)),
    "int16": dict(counts=MIXED, room=3, left=_narrow(np.int16)),
    "int8": dict(counts=MIXED, room=3, left=_narrow(np.int8)),
    "bool8": dict(counts=MIXED, room=3, left=_bools),
    "a_left_column_with_nulls": dict(counts=MIXED, room=3, left=_nullable),
    "left_outer": dict(counts=MIXED, room=3, left_outer=True),
    "left_outer_at_total": dict(counts=MIXED, room=0, left_outer=True),
    "left_outer_under_the_count": dict(
        counts=MIXED, room=-3, left_outer=True, left=_nullable),
    "a_string_left_column_keeps_its_gather": dict(
        counts=MIXED, room=3, left=_strings),
    "a_string_beside_a_spread_column": dict(
        counts=MIXED, room=3, left=_strings, left_outer=True),
}


def _both_forms(monkeypatch, counts, total, left, right, left_outer):
    rng = np.random.default_rng(len(counts) + total)
    perm_r = jnp.asarray(rng.permutation(BUILD_ROWS).astype(np.int32))
    lo = jnp.asarray(
        rng.integers(0, BUILD_ROWS - max(counts), len(counts)), jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    out = {}
    for form, spreads in (("gather", False), ("spread", True)):
        monkeypatch.setattr(J, "mat_spreads", lambda t, n, s=spreads: s)
        rows, right_idx, matched, in_range = J._expand(
            perm_r, lo, counts, total, left_outer)
        assert isinstance(rows, J._Runs) == spreads
        table = J._join_output(
            left, right, [0], rows, right_idx,
            matched if left_outer else None, None)
        out[form] = (
            J._left_index(rows), right_idx, matched, in_range, table)
    return out["gather"], out["spread"]


def _same_leaves(want: Table, got: Table, under=None):
    """Every leaf of every column, bit for bit on the first ``under``
    rows (all of them by default)."""
    assert got.names == want.names
    assert [c.dtype for c in got.columns] == [c.dtype for c in want.columns]
    for name, cw, cg in zip(want.names, want.columns, got.columns):
        for leaf in ("data", "validity", "lengths"):
            w, g = getattr(cw, leaf), getattr(cg, leaf)
            assert (w is None) == (g is None), (name, leaf)
            if w is not None:
                assert w.shape == g.shape and w.dtype == g.dtype, (name, leaf)
                np.testing.assert_array_equal(
                    np.asarray(w)[:under], np.asarray(g)[:under],
                    err_msg=f"{name}.{leaf}")


@pytest.mark.parametrize("case", list(CASES))
def test_the_spread_form_equals_the_gather_form(monkeypatch, case):
    spec = CASES[case]
    counts = spec["counts"]
    left_outer = spec.get("left_outer", False)
    n = len(counts)
    emitted = sum(max(c, 1) if left_outer else c for c in counts)
    total = emitted + spec.get("room", 2)
    under = min(emitted, total)
    left = Table(
        [_ints(n), spec.get("left", _ints)(n), _narrow(np.int16)(n)],
        ["k", "v", "w"])
    right = Table([_ints(BUILD_ROWS, 5), _nullable(BUILD_ROWS)], ["k", "r"])
    gather, spread = _both_forms(
        monkeypatch, counts, total, left, right, left_outer)
    for name, g, s in zip(
        ("left_idx", "right_idx", "matched", "in_range"), gather, spread
    ):
        assert g.shape == s.shape == (total,) and g.dtype == s.dtype, name
        np.testing.assert_array_equal(
            np.asarray(g)[:under], np.asarray(s)[:under], err_msg=name)
    # in_range and the mask behind it hold on the padding too
    np.testing.assert_array_equal(np.asarray(gather[3]), np.asarray(spread[3]))
    np.testing.assert_array_equal(np.asarray(gather[2]), np.asarray(spread[2]))
    _same_leaves(gather[4], spread[4], under)
    if under:
        # and both are the rows: each slot's probe row by numpy
        emit = [max(c, 1) if left_outer else c for c in counts]
        want = np.repeat(np.arange(n), emit)[:under]
        np.testing.assert_array_equal(np.asarray(spread[0])[:under], want)
        np.testing.assert_array_equal(
            np.asarray(spread[4].columns[0].data)[:under],
            np.asarray(left.columns[0].data)[want])


@pytest.mark.parametrize("total,n_left,spreads", [
    (1 << 23, 1 << 20, True),    # tpcds-q95-wswh's self-join: fan-out 12.5
    (1 << 16, 1 << 23, False),   # TPC-H Q3's lineitem join: 39,700 rows placed
    (1 << 21, 1 << 21, False),   # Q3's orders join: a unique build key
    (1 << 20, 1 << 20, False),   # equal widths stay the gather form's
    (1 << 21, 1 << 20, True),    # the ladder's next bucket up
])
def test_the_selection_reads_two_widths(total, n_left, spreads):
    assert J.mat_spreads(total, n_left) is spreads


@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_an_eager_join_that_widens_takes_the_spread_form(monkeypatch, how):
    """Every caller of `_expand` asks the one selection: a join whose
    exact count passes its probe side's rows spreads, and the answer is
    the gather form's, row for row."""
    rng = np.random.default_rng(7)
    k = rng.integers(0, 5, 40).astype(np.int64)
    left = Table(
        [Column.from_numpy(k), _nullable(40), _strings(40)], ["k", "v", "s"])
    right = Table([Column.from_numpy(k[::-1].copy() + 1), _floats(40)],
                  ["k", "f"])
    join = getattr(J, how + "_join")
    made = []
    real = J._Runs.spread

    def seen(self, word):
        made.append(word.shape)
        return real(self, word)

    monkeypatch.setattr(J._Runs, "spread", seen)
    got = join(left, right, ["k"])
    assert got.row_count > 40 and made
    monkeypatch.setattr(J, "mat_spreads", lambda t, n: False)
    made.clear()
    want = join(left, right, ["k"])
    assert not made
    _same_leaves(want, got)
