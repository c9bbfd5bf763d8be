"""The direct-address probe of an integer build key (PR 28; its table
filled by one scatter and as wide as the key's span since PR 41).

``ops.join._probe_direct`` must return what the search
(``_probe_build``) returns, bit for bit: ``perm_r``, ``lo``, ``counts``
and ``lvalid`` of ``_match_ranges`` are compared on the same inputs
with and without ``table_size`` (``lo`` is a row's first match and,
where it has none, the first valid build row by every probe: a hole of
the span holds no insertion point, and nothing reads one), over the
cases that
could tell an address from a search apart (duplicates, nulls and
padding on either side, keys outside the span and at the type's two
ends, a span that wraps past the type's largest value, a span wider
than the probe side, holes and runs of repeats, a build side with no
valid key), in each of the table's three forms (a row a key where the
caller read that no key repeats; row and count in one word; the count
by a second gather from a build side of 2^16 rows or more). Above that,
the served runner (``bucketed._r_join``) makes ONE choice from the build
side's observed key type, span and repeats and the device's budget —
pinned here by its counters and by the joined tables of all four
bucketed hows — and the lowered probe holds no loop, one scatter, no
gather at the table's width and at most two at the probe side's.
"""

import collections

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import bucketed
from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops import join as join_mod
from spark_rapids_jni_tpu.utils import buckets, config, metrics

N_LEFT, N_RIGHT, T = 300, 64, 128

WIDE = {
    "int32": dt.INT32, "int64": dt.INT64, "decimal64": dt.decimal64(-2),
    "timestamp_us": dt.TIMESTAMP_MICROSECONDS,
}
FAMILY = {
    "int8": dt.INT8, "uint8": dt.UINT8, "int16": dt.INT16,
    "uint32": dt.UINT32, "uint64": dt.UINT64, "bool8": dt.BOOL8,
    "decimal32": dt.decimal32(-3), "timestamp_days": dt.TIMESTAMP_DAYS,
    "duration_s": dt.DURATION_SECONDS,
}


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("METRICS")
    config.clear_flag("HBM_BUDGET_GB")


def _np_dtype(d):
    return np.dtype(d.storage_dtype)


def _limits(d):
    if d.is_boolean:
        return 0, 1
    info = np.iinfo(_np_dtype(d))
    return int(info.min), int(info.max)


def _cast(values, d):
    """Python integers -> the column's storage type (exact: the cases
    keep every value inside the type's range)."""
    lo, hi = _limits(d)
    assert all(lo <= int(v) <= hi for v in values), (lo, hi)
    return np.array([int(v) for v in values], dtype=object).astype(
        _np_dtype(d)
    )


def _case(name, d, seed=0):
    """One scenario as python-int key lists and masks. Every scenario
    has N_LEFT / N_RIGHT rows and a span of at most T, so one compiled
    pair of programs a dtype serves them all."""
    rng = np.random.default_rng(seed)
    tmin, tmax = _limits(d)
    base = max(tmin, min(1000, tmax - 60))
    lnull = np.zeros(N_LEFT, bool)
    rnull = np.zeros(N_RIGHT, bool)
    lpad = np.zeros(N_LEFT, bool)
    rpad = np.zeros(N_RIGHT, bool)

    def draw(lo, hi, n):
        lo, hi = max(lo, tmin), min(hi, tmax)
        return [lo + int(x) for x in rng.integers(0, hi - lo + 1, n)]

    rk = draw(base, base + 40, N_RIGHT)         # duplicates: 64 of 41
    lk = draw(base - 10, base + 50, N_LEFT)     # some below, some above
    if name == "duplicates":
        pass
    elif name == "null_keys":
        lnull[rng.integers(0, N_LEFT, 40)] = True
        rnull[rng.integers(0, N_RIGHT, 12)] = True
    elif name == "padding":
        # padding rows hold zeros, as a padded upload does: key 0 lies
        # INSIDE this span and must be neither in the table nor matched
        base = max(tmin, -20)
        rk = draw(base, base + 40, N_RIGHT)
        lk = draw(base - 10, base + 50, N_LEFT)
        lpad[250:] = True
        rpad[50:] = True
        for i in range(250, N_LEFT):
            lk[i] = 0
        for i in range(50, N_RIGHT):
            rk[i] = 0
        lnull[rng.integers(0, 250, 20)] = True
        rnull[rng.integers(0, 50, 6)] = True
    elif name == "negative":
        base = max(tmin, -100)
        rk = draw(base, base + 40, N_RIGHT)
        lk = draw(base - 10, base + 50, N_LEFT)
    elif name == "outside_and_ends":
        # below kmin, above kmax, and the type's two ends: a 64-bit key
        # at the other end must not wrap into the span
        rk[0], rk[1] = base, base + 40
        lk[:8] = [tmin, tmax, base - 1, base + 41, base, base + 40,
                  max(tmin, tmin + 1), tmax - 1]
    elif name == "span_wraps_at_type_max":
        # kmin + arange(T) runs past the largest value
        rk = draw(tmax - 30, tmax, N_RIGHT)
        rk[0] = tmax
        lk = draw(tmax - 45, tmax, N_LEFT)
        lk[:3] = [tmin, tmax, tmax - 31]
    elif name == "span_starts_at_type_min":
        # kmin's order word is 0, where a null key's zeroed word lies
        rk = draw(tmin, tmin + 30, N_RIGHT)
        rk[0] = tmin
        lk = draw(tmin, tmin + 45, N_LEFT)
        lk[:2] = [tmin, tmax]
        lnull[rng.integers(0, N_LEFT, 30)] = True
    elif name == "all_null_build":
        rnull[:] = True
    elif name == "all_padding_build":
        rpad[:] = True
    elif name == "span_exactly_table":
        rk[0], rk[1] = base, base + T - 1
        lk = draw(base - 5, base + T + 5, N_LEFT)
        lk[:4] = [base, base + T - 1, base - 1, base + T]
    elif name == "one_key":
        rk = [base + 7] * N_RIGHT
    elif name == "span_wider_than_probe":
        # 64 keys over a span of 1,000: three times the probe side
        rk = draw(base, base + 999, N_RIGHT)
        rk[0], rk[1] = base, base + 999
        lk = draw(base - 20, base + 1020, N_LEFT)
        lk[:40] = rk[:40]
        lnull[rng.integers(0, N_LEFT, 10)] = True
    elif name == "holes_and_runs":
        # holes behind kmin and in front of kmax, runs of repeats right
        # beside holes; the probe asks for every key of the span, the
        # two ends, and one outside each end
        body = [base + 9] * 5 + [base + 10] * 7 + [base + 12] * 3
        rk = ([base] * 2 + body + [base + 57] * 4 + [base + 60]
              + [base + 30 + (i % 3) for i in range(N_RIGHT)])[:N_RIGHT]
        lk = [base - 1 + (i % 63) for i in range(N_LEFT)]
        rnull[40:44] = True
        rpad[60:] = True
    elif name in ("unique_sparse", "unique_nulls_and_padding"):
        # no valid build key repeats: what `build_key_span` reads as
        # ``repeats == 0``
        rk = [base + 2 * int(x) for x in rng.permutation(N_RIGHT)]
        lk = draw(base - 5, base + 2 * N_RIGHT + 5, N_LEFT)
        lk[:4] = [base, base + 2 * (N_RIGHT - 1), base - 1,
                  base + 2 * N_RIGHT - 1]
        if name == "unique_nulls_and_padding":
            # the nulled and the padded rows repeat a LIVE key: never
            # scattered, so the key's one live row stays the answer
            rk[5], rk[6], rk[50], rk[51] = rk[0], rk[0], rk[1], rk[2]
            rnull[5:7] = True
            rpad[50:52] = True
            lnull[rng.integers(0, N_LEFT, 20)] = True
            lpad[280:] = True
    elif name == "wide_build_repeats":
        # 2^16 build rows: row and count no longer fit one 32-bit word,
        # so a run's length is the second gather
        n = 1 << 16
        rk = draw(base, base + 5000, n)
        rk[0], rk[1] = base, base + 5000
        lk = draw(base - 10, base + 5010, N_LEFT)
        lk[:4] = [base, base + 5000, base - 1, base + 5001]
        rnull = rng.random(n) < 0.1
        rpad = np.zeros(n, bool)
        rpad[n - 3000:] = True
        rnull[:2] = False
    else:
        raise AssertionError(name)
    if d.is_boolean:
        rk = [int(k) & 1 for k in rk]
        lk = [int(k) & 1 for k in lk]
    return lk, lnull, lpad, rk, rnull, rpad


CASES = (
    "duplicates", "null_keys", "padding", "negative", "outside_and_ends",
    "span_wraps_at_type_max", "span_starts_at_type_min", "all_null_build",
    "all_padding_build", "span_exactly_table", "one_key",
)
# PR 41's: the table of a case that needs more than T entries, and the
# cases whose valid build keys repeat no value (run in the one-table
# form too)
TABLE_OF = {"span_wider_than_probe": 1024, "wide_build_repeats": 8192}
UNIQUE_CASES = ("unique_sparse", "unique_nulls_and_padding")
SCATTER_CASES = (
    ("span_wider_than_probe", "holes_and_runs") + UNIQUE_CASES
)


def _tables(d, lk, lnull, rk, rnull):
    left = Table([
        Column.from_numpy(_cast(lk, d), ~lnull, dtype=d),
        Column.from_numpy(np.arange(len(lk), dtype=np.int64)),
    ], ["k", "lv"])
    right = Table([
        Column.from_numpy(_cast(rk, d), ~rnull, dtype=d),
        Column.from_numpy(np.arange(len(rk), dtype=np.int64) * 10),
    ], ["k", "rv"])
    return left, right


@functools.lru_cache(maxsize=None)
def _ranges_fn(table_size, narrow=False, unique=False):
    def fn(left, right, lv, rv):
        return join_mod._match_ranges(
            left, right, ["k"], ["k"], lv, rv, table_size=table_size,
            narrow=narrow, unique=unique,
        )

    return jax.jit(fn)


def _assert_same_ranges(got, want, live_build_rows):
    """Bit for bit; and ``lo``, which a table's hole cannot give where
    nothing matches, is the first valid build row there by every probe
    (the invalid rows sort in front of it)."""
    for name, w, g in zip(("perm_r", "lo", "counts", "lvalid"), want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    lo, counts = np.asarray(got[1]), np.asarray(got[2])
    first = len(np.asarray(got[0])) - live_build_rows
    assert (lo[counts == 0] == first).all()


def _span_fits(rk, rnull, rpad, size):
    keys = [k for k, a, b in zip(rk, rnull, rpad) if not (a or b)]
    return not keys or max(keys) - min(keys) + 1 <= size


PROBE_CASES = (
    [(n, c) for n in WIDE for c in CASES]
    + [(n, "null_keys") for n in FAMILY]
    + [(n, "outside_and_ends") for n in FAMILY if n != "bool8"]
)
# (dtype, case, the caller read ``repeats == 0``): every case through
# the forms that keep a count, the unique ones through the one-table
# form as well
DIRECT_CASES = (
    [(n, c, False) for n, c in PROBE_CASES]
    + [(n, c, False) for n in WIDE for c in SCATTER_CASES]
    + [(n, c, True) for n in WIDE for c in UNIQUE_CASES]
    + [("int64", "wide_build_repeats", False),
       ("int16", "holes_and_runs", False),
       ("uint64", "unique_sparse", True)]
)


def _live_counts(lk, lnull, lpad, rk, rnull, rpad):
    """An independent count of every probe row's matches."""
    live = collections.Counter(
        k for k, a, b in zip(rk, rnull, rpad) if not (a or b)
    )
    return [
        0 if (a or b) else live[k] for k, a, b in zip(lk, lnull, lpad)
    ]


@pytest.mark.parametrize("dname,case,unique", DIRECT_CASES)
def test_direct_probe_equals_the_search(dname, case, unique):
    d = {**WIDE, **FAMILY}[dname]
    lk, lnull, lpad, rk, rnull, rpad = _case(case, d, seed=len(case))
    size = TABLE_OF.get(case, T)
    assert _span_fits(rk, rnull, rpad, size)
    left, right = _tables(d, lk, lnull, rk, rnull)
    lv, rv = jnp.asarray(~lpad), jnp.asarray(~rpad)
    want = _ranges_fn(None)(left, right, lv, rv)
    got = _ranges_fn(size, unique=unique)(left, right, lv, rv)
    _assert_same_ranges(got, want, int((~(rnull | rpad)).sum()))
    # the cases are not vacuous: an independent count of the matches
    per_left = _live_counts(lk, lnull, lpad, rk, rnull, rpad)
    np.testing.assert_array_equal(np.asarray(got[2]), per_left)
    assert case.startswith("all_") or sum(per_left) > 0
    if unique:
        assert max(per_left) == 1


@pytest.mark.parametrize("dname,case", PROBE_CASES)
def test_one_word_search_equals_the_search(dname, case):
    """`_probe_offsets` (PR 40: one u32 word a side, from the build
    side's first valid row) against `_probe_build` over every order
    word, on the direct probe's cases with the keys pulled 30,000,000
    apart where the type has room (a 64-bit key's span then just fits
    32 bits)."""
    d = {**WIDE, **FAMILY}[dname]
    lk, lnull, lpad, rk, rnull, rpad = _case(case, d, seed=len(case))
    tmin, tmax = _limits(d)
    # one affine map for both sides keeps which keys match (a probe key
    # pushed past the type's end stays outside the span)
    lo = min(rk)
    step = max(1, min(30_000_000, (tmax - lo) // 200))
    rk = [lo + (k - lo) * step for k in rk]
    lk = [min(tmax, max(tmin, lo + (k - lo) * step)) for k in lk]
    live = [k for k, a, b in zip(rk, rnull, rpad) if not (a or b)]
    assert join_mod.offsets_fit(min(live, default=0), max(live, default=0),
                                len(live)) == bool(live)
    left, right = _tables(d, lk, lnull, rk, rnull)
    lv, rv = jnp.asarray(~lpad), jnp.asarray(~rpad)
    want = _ranges_fn(None)(left, right, lv, rv)
    got = _ranges_fn(None, True)(left, right, lv, rv)
    _assert_same_ranges(got, want, len(live))
    per_left = _live_counts(lk, lnull, lpad, rk, rnull, rpad)
    np.testing.assert_array_equal(np.asarray(got[2]), per_left)


@pytest.mark.parametrize("kmin,kmax,rows,want", [
    (5, 5, 1, True),
    (0, (1 << 32) - 1, 2, True),    # the widest span one u32 holds
    (7, (1 << 32) + 7, 2, False),   # one past
    (0, (1 << 64) - 1, 9, False),   # INT64's whole range
    (5, 5, 0, False),               # no valid key: no span
])
def test_offsets_fit_under_32_bits_of_span(kmin, kmax, rows, want):
    assert join_mod.offsets_fit(kmin, kmax, rows) is want


def test_direct_probe_refuses_a_key_it_cannot_address():
    left, right = _tables(dt.INT64, [1, 2], np.zeros(2, bool),
                          [1, 2], np.zeros(2, bool))
    with pytest.raises(TypeError, match="direct-address probe"):
        join_mod._match_ranges(
            left, right, ["k", "lv"], ["k", "rv"], table_size=1024
        )
    with pytest.raises(TypeError, match="one-word search"):
        join_mod._match_ranges(
            left, right, ["k", "lv"], ["k", "rv"], narrow=True
        )


# ---------------------------------------------------------------------------
# the choice: ops.join.direct_table_size and the runner's two counters
# ---------------------------------------------------------------------------


# the pretend v5e of a CPU run: 16 GiB, 65% of it the budget, a
# sixteenth of that the table's: 2^27 four-byte entries, not 2^28
@pytest.mark.parametrize("span,budget_gb,want", [
    (1, None, 1024),                # no table is narrower
    (1500, None, 2048),
    (2048, None, 2048),
    (2049, None, 4096),             # the span's next power of two
    (10000, None, 16384),           # the resident query's dimension
    ((1 << 23) + 1, None, 1 << 24),  # the ladder's cap bounds batches
    (58_000_000, None, 1 << 26),    # Q3's order key, 1 partition of 7
    ((1 << 27) - 1, None, 1 << 27),  # one under the bound
    (1 << 27, None, 1 << 27),       # at it
    ((1 << 27) + 1, None, None),    # one past: 2^28 entries are 1 GiB
    (1 << 64, None, None),          # INT64's whole range
    (1 << 23, 1, 1 << 23),          # a sixteenth of 0.65 GiB: 2^23 ...
    ((1 << 23) + 1, 1, None),       # ... and not 2^24
    (1 << 28, 32, 1 << 28),         # a chip twice the size
    (1 << 30, 1 << 10, 1 << 30),    # whatever the chip, an address ...
    ((1 << 30) + 1, 1 << 10, None),  # ... is an int32
])
def test_table_size_is_the_spans_power_of_two_within_the_budget(
    span, budget_gb, want
):
    if budget_gb is not None:
        config.set_flag("HBM_BUDGET_GB", budget_gb)
    kmin = 5
    assert join_mod.direct_table_size(kmin, kmin + span - 1, 7) == want
    assert join_mod.direct_table_size(kmin, kmin + span - 1, 0) is None


def _padded(t: Table, logical: int) -> Table:
    """The first ``logical`` rows, zero-padded to their bucket."""
    return buckets.pad_table(buckets.head_table(t, logical))


def _run_join(how, left, right, on=("k",)):
    op = {"op": "join", "how": how, "on": list(on)}
    watched = ["join.probe.direct", "join.probe.search",
               "join.probe.narrow", "join.table_entries",
               "bucket.fallback_errors"]
    before = metrics.counter_values(watched)
    out = bucketed._r_join(op, left, (right,))
    after = metrics.counter_values(watched)
    return out, {k: after[k] - before[k] for k in watched}


def _rows(t: Table):
    """The logical rows of a result, every buffer: data, validity."""
    n = t.logical_row_count
    out = []
    for c in t.columns:
        out.append(np.asarray(c.data)[:n])
        out.append(None if c.validity is None
                   else np.asarray(c.validity)[:n])
    return n, t.names, out


def _assert_same_table(got: Table, want: Table):
    gn, gnames, gcols = _rows(got)
    wn, wnames, wcols = _rows(want)
    assert (gn, gnames) == (wn, wnames)
    for g, w in zip(gcols, wcols):
        if w is None:
            assert g is None
        else:
            np.testing.assert_array_equal(g, w)


def _dense_pair(d, seed=3, n_left=3000, n_right=700):
    """A fact side and a dimension whose key is dense: 700 distinct-ish
    keys of a 1,400-wide span (bucket 2,048 = 2 x the dimension's 1,024),
    with duplicates, nulls and fact keys outside the span."""
    rng = np.random.default_rng(seed)
    tmin, tmax = _limits(d)
    base = -200 if tmin < 0 else 50
    rk = [base + int(x) for x in rng.integers(0, 1400, n_right)]
    rk[0], rk[1] = base, base + 1399
    lk = [base - 50 + int(x) for x in rng.integers(0, 1500, n_left)]
    lk[:2] = [tmin, tmax]
    lnull = rng.random(n_left) < 0.05
    rnull = rng.random(n_right) < 0.05
    rnull[:2] = False
    return _tables(d, lk, lnull, rk, rnull)


def _sparse_pair(d, repeats, seed=7, n_left=3000, n_right=700):
    """A fact side and a build side whose key is SPARSE: ~700 keys 1,400
    apart, a span of 978,601 (a table of 2^20 entries: 256 times the
    probe side's bucket), unique or in runs of up to three; nulls on
    both sides, fact keys in the holes and outside the span."""
    rng = np.random.default_rng(seed)
    tmin, tmax = _limits(d)
    base = -200_000
    ks = [base + 1400 * int(x) for x in rng.permutation(n_right)]
    if repeats:
        ks = [ks[i - (i % 3)] for i in range(n_right)]
    lk = [int(x) for x in rng.choice(np.asarray(ks, dtype=object), n_left)]
    for i in range(0, n_left, 7):
        lk[i] += 1 + int(rng.integers(0, 1398))  # a hole of the span
    lk[:4] = [tmin, tmax, base - 1, max(ks) + 1]
    lnull = rng.random(n_left) < 0.05
    rnull = rng.random(n_right) < 0.05
    rnull[[ks.index(min(ks)), ks.index(max(ks))]] = False  # the span's ends
    return _tables(d, lk, lnull, ks, rnull)


# (the pair, its table's entries, whether the runner reads the build
# key as unique); the dense pair's nulls and duplicates repeat values
PAIRS = {
    "dense": (_dense_pair, 2048, False),
    "sparse_unique": (lambda d: _sparse_pair(d, False), 1 << 20, True),
    "sparse_repeats": (lambda d: _sparse_pair(d, True), 1 << 20, False),
}

# the search a test forces in place of the runner's choice: over every
# order word, or over one u32 word a side (`_probe_offsets`)
SEARCHES = {"all_words": (None, False, False),
            "one_word": (None, True, False)}


@pytest.mark.parametrize("search", SEARCHES)
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
@pytest.mark.parametrize("dname,pair", [
    ("int64", "dense"), ("int32", "dense"), ("decimal64", "dense"),
    ("timestamp_us", "dense"), ("int64", "sparse_unique"),
    ("int64", "sparse_repeats"),
])
def test_served_join_is_the_same_table_by_either_probe(
    how, dname, pair, search, monkeypatch
):
    config.set_flag("METRICS", True)
    make, entries, unique = PAIRS[pair]
    left, right = make(WIDE[dname])
    assert bucketed._probe_choice(left, right, ["k"]) == (
        entries, False, unique
    )
    got, moved = _run_join(how, left, right)
    assert moved == {"join.probe.direct": 1, "join.probe.search": 0,
                     "join.probe.narrow": 0, "join.table_entries": entries,
                     "bucket.fallback_errors": 0}
    monkeypatch.setattr(bucketed, "_probe_choice",
                        lambda *a: SEARCHES[search])
    want, moved = _run_join(how, left, right)
    assert moved["join.probe.search"] == 1
    assert moved["join.probe.narrow"] == (search == "one_word")
    assert moved["join.table_entries"] == 0
    assert got.logical_row_count > 0
    _assert_same_table(got, want)


@pytest.mark.parametrize("search", SEARCHES)
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_served_join_of_padded_inputs_by_either_probe(
    how, search, monkeypatch
):
    """Both sides arrive padded (a filter's result, a padded upload):
    the padding rows' zero keys lie inside the span."""
    config.set_flag("METRICS", True)
    left, right = _dense_pair(dt.INT64, seed=5)
    left = _padded(left, 2500)
    right = _padded(right, 600)
    assert left.row_count == 4096 and right.row_count == 1024
    got, moved = _run_join(how, left, right)
    assert moved["join.probe.direct"] == 1
    monkeypatch.setattr(bucketed, "_probe_choice",
                        lambda *a: SEARCHES[search])
    want, _ = _run_join(how, left, right)
    _assert_same_table(got, want)


def _pair_with_build_keys(rk, n_left=3000, d=dt.INT64):
    rng = np.random.default_rng(11)
    lk = [int(x) for x in rng.choice(np.asarray(rk, dtype=object), n_left)]
    return _tables(d, lk, np.zeros(n_left, bool), rk,
                   np.zeros(len(rk), bool))


# a budget whose sixteenth holds 2,048 four-byte entries and not 4,096
# (139,586 bytes: the rule at small size, `direct_table_size`)
BUDGET_OF_2048_ENTRIES_GB = 0.0002


def test_span_at_the_threshold_is_direct_and_one_past_is_searched():
    config.set_flag("METRICS", True)
    config.set_flag("HBM_BUDGET_GB", BUDGET_OF_2048_ENTRIES_GB)
    body = list(range(100, 798))
    at = _pair_with_build_keys([0] + body + [2047])
    past = _pair_with_build_keys([0] + body + [2048])
    out_at, moved = _run_join("inner", *at)
    assert (moved["join.probe.direct"], moved["join.table_entries"]) == (
        1, 2048
    )
    out_past, moved = _run_join("inner", *past)
    assert (moved["join.probe.search"], moved["join.table_entries"]) == (
        1, 0
    )
    assert out_at.logical_row_count == out_past.logical_row_count == 3000


def test_a_table_past_the_bound_is_searched():
    """...and one that is only wider than the probe side (2,048 entries
    against 1,024 probe rows: searched until PR 41) is not."""
    config.set_flag("METRICS", True)
    left, right = _pair_with_build_keys(
        [0] + list(range(100, 798)) + [1400], n_left=900
    )
    _, moved = _run_join("inner", left, right)
    assert moved == {"join.probe.direct": 1, "join.probe.search": 0,
                     "join.probe.narrow": 0, "join.table_entries": 2048,
                     "bucket.fallback_errors": 0}
    config.set_flag("HBM_BUDGET_GB", BUDGET_OF_2048_ENTRIES_GB / 2)
    _, moved = _run_join("inner", left, right)
    assert moved == {"join.probe.direct": 0, "join.probe.search": 1,
                     "join.probe.narrow": 1, "join.table_entries": 0,
                     "bucket.fallback_errors": 0}


def _sparse_int():
    rk = [int(x) * 1_000_003 for x in range(700)]
    return _pair_with_build_keys(rk), ("k",)


def _two_columns():
    left, right = _dense_pair(dt.INT64)
    left = Table([left.columns[0], left.columns[0], left.columns[1]],
                 ["k", "k2", "lv"])
    right = Table([right.columns[0], right.columns[0], right.columns[1]],
                  ["k", "k2", "rv"])
    return (left, right), ("k", "k2")


def _string_key():
    rng = np.random.default_rng(2)
    rk = [f"item{i:04d}" for i in range(700)]
    lk = [rk[int(i)] for i in rng.integers(0, 700, 3000)]
    left = Table([Column.from_strings(lk),
                  Column.from_numpy(np.arange(3000, dtype=np.int64))],
                 ["k", "lv"])
    right = Table([Column.from_strings(rk),
                   Column.from_numpy(np.arange(700, dtype=np.int64))],
                  ["k", "rv"])
    return (left, right), ("k",)


def _float64_key():
    rng = np.random.default_rng(4)
    rk = np.arange(700, dtype=np.float64)
    lk = rng.integers(0, 700, 3000).astype(np.float64)
    left = Table([Column.from_numpy(lk),
                  Column.from_numpy(np.arange(3000, dtype=np.int64))],
                 ["k", "lv"])
    right = Table([Column.from_numpy(rk),
                   Column.from_numpy(np.arange(700, dtype=np.int64))],
                  ["k", "rv"])
    return (left, right), ("k",)


def _all_null_build():
    left, right = _dense_pair(dt.INT64)
    k = right.columns[0]
    right = Table([k.with_validity(jnp.zeros((k.row_count,), jnp.bool_)),
                   right.columns[1]], right.names)
    return (left, right), ("k",)


def _sparse_past_32_bits():
    rk = [int(x) * 7_000_003 for x in range(700)]  # spans 4.9e9
    return _pair_with_build_keys(rk), ("k",)


@pytest.mark.parametrize("make,narrow", [
    (_sparse_int, 1), (_sparse_past_32_bits, 0), (_two_columns, 0),
    (_string_key, 0), (_float64_key, 0), (_all_null_build, 0),
])
def test_keys_the_table_cannot_address_take_the_search(make, narrow):
    """...over one u32 word a side where the key is one integer column
    whose valid build keys span under 2^32 values (PR 40), over every
    order word otherwise."""
    config.set_flag("METRICS", True)
    (left, right), on = make()
    out, moved = _run_join("left", left, right, on)
    assert moved == {"join.probe.direct": 0, "join.probe.search": 1,
                     "join.probe.narrow": narrow, "join.table_entries": 0,
                     "bucket.fallback_errors": 0}
    assert out.logical_row_count >= 3000


def test_choice_is_keyed_into_the_executable_cache():
    """One executable a choice: the same shapes with a dense unique key,
    a dense key that repeats (the table keeps a count) and a key too
    sparse for the device's share must not serve each other's program."""
    config.set_flag("METRICS", True)
    dense = _pair_with_build_keys(list(range(700)))
    repeats = _pair_with_build_keys([k // 2 for k in range(700)])
    sparse, _ = _sparse_int()
    assert [bucketed._probe_choice(l, r, ["k"]) for l, r in
            (dense, repeats, sparse)] == [
        (1024, False, True), (1024, False, False), (None, True, False)]
    a, moved_a = _run_join("inner", *dense)
    b, moved_b = _run_join("inner", *sparse)
    r, moved_r = _run_join("inner", *repeats)
    c, moved_c = _run_join("inner", *dense)
    assert (moved_a["join.probe.direct"], moved_b["join.probe.search"],
            moved_r["join.probe.direct"], moved_c["join.probe.direct"]) == (
        1, 1, 1, 1)
    assert a.logical_row_count == b.logical_row_count == 3000
    assert r.logical_row_count == 6000  # every key twice
    _assert_same_table(a, c)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_dispatch_plane_counts_one_direct_probe_a_join(how):
    """Through the door a served plan's join takes
    (``runtime_bridge`` -> ``dispatch_bucketed``), against the exact
    path with the bucket plane off."""
    import json

    from spark_rapids_jni_tpu import runtime_bridge as rb

    i64 = int(dt.TypeId.INT64)
    rng = np.random.default_rng(8)
    n = 1500
    lk = rng.integers(-20, 120, n, dtype=np.int64)
    lvalid = (rng.random(n) > 0.1).astype(np.uint8)
    lv = np.arange(n, dtype=np.int64)
    rk = rng.integers(0, 100, 60, dtype=np.int64)
    rv = np.arange(60, dtype=np.int64) * 7

    def run():
        tidl = rb.table_upload_wire(
            [i64, i64], [0, 0], [lk.tobytes(), lv.tobytes()],
            [lvalid.tobytes(), None], n,
        )
        tidr = rb.table_upload_wire(
            [i64, i64], [0, 0], [rk.tobytes(), rv.tobytes()],
            [None, None], 60,
        )
        jid = rb.table_op_resident(
            json.dumps({"op": "join", "how": how, "on": [0]}), [tidl, tidr]
        )
        out = rb.table_download_wire(jid)
        for t in (tidl, tidr, jid):
            rb.table_free(t)
        return out

    config.set_flag("METRICS", True)
    watched = ["join.probe.direct", "join.probe.search",
               "bucket.fallback_errors", "bucket.declined"]
    before = metrics.counter_values(watched)
    got = run()
    after = metrics.counter_values(watched)
    assert {k: after[k] - before[k] for k in watched} == {
        "join.probe.direct": 1, "join.probe.search": 0,
        "bucket.fallback_errors": 0, "bucket.declined": 0,
    }
    config.set_flag("BUCKETS", "off")
    try:
        want = run()
    finally:
        config.clear_flag("BUCKETS")
    assert metrics.counter_values(watched) == after  # the exact path counts neither
    assert got == want


# ---------------------------------------------------------------------------
# the program: no loop, at most two gathers as wide as the probe side
# ---------------------------------------------------------------------------


def _lowered_probe(table_size, n_left=4096, n_right=1024, unique=False):
    left = Table([
        Column(jax.ShapeDtypeStruct((n_left,), jnp.int64), dt.INT64, None),
        Column(jax.ShapeDtypeStruct((n_left,), jnp.int64), dt.INT64, None),
    ])
    right = Table([
        Column(jax.ShapeDtypeStruct((n_right,), jnp.int64), dt.INT64, None),
        Column(jax.ShapeDtypeStruct((n_right,), jnp.int64), dt.INT64, None),
    ])
    n32 = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(l, r, ln, rn):
        lv = buckets.tail_valid(l.row_count, ln)
        rv = buckets.tail_valid(r.row_count, rn)
        return join_mod._match_ranges(
            l, r, [0], [0], lv, rv, table_size=table_size, unique=unique
        )

    return jax.jit(fn).lower(left, right, n32, n32).as_text()


def _gathers_of_width(text, width):
    """Gather ops whose result has ``width`` elements."""
    wide = 0
    for line in text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        result = line.rsplit("->", 1)[-1]
        if re.search(rf"tensor<{width}x", result):
            wide += 1
    return wide


def _scatters(text):
    """(table entries, updates) of every scatter op: its types close
    the op's region, lines below its name."""
    return [(int(t), int(u)) for t, u in re.findall(
        r"\}\) : \(tensor<(\d+)x\w+>, tensor<(\d+)x1xi32>, "
        r"tensor<\d+x\w+>\) -> tensor<\d+x", text)]


# (build rows, the caller read ``repeats == 0``) -> gathers at the
# probe side's width: a row a key; row and count in one word; the count
# by a second gather where 2 x 17 bits do not fit the word
@pytest.mark.parametrize("n_right,unique,gathers", [
    (1024, True, 1), (1024, False, 1), (1 << 16, False, 2),
    (1 << 16, True, 1),
])
def test_lowered_probe_has_no_loop_one_scatter_and_two_wide_gathers_at_most(
    n_right, unique, gathers
):
    """The table is wider than both sides (a sparse key's span), so a
    tensor's width says what it serves: the fill and ONE scatter at the
    table's, no gather and no search there."""
    n_left, size = 4096, 1 << 18
    direct = _lowered_probe(size, n_left, n_right, unique)
    assert "stablehlo.while" not in direct
    assert direct.count('"stablehlo.scatter"') == 1
    assert _scatters(direct) == [(size, n_right)]
    assert _gathers_of_width(direct, size) == 0
    assert _gathers_of_width(direct, n_left) == gathers
    # the search, through the same reading, is what the table replaced:
    # two loops whose bodies gather both u64 words at the probe side's
    # width (the chip splits each into two u32 gathers: eight)
    search = _lowered_probe(None, n_left, n_right)
    assert search.count("stablehlo.while") == 2
    assert _gathers_of_width(search, n_left) == 4
    assert '"stablehlo.scatter"' not in search
