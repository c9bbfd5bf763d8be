"""The capped groupby's two halves against its one-trace form.

``groupby_sort`` (everything at the input's N rows) then
``groupby_reduce`` (everything per group, at any width K from the group
count up) must return, for every row below the group count, the bytes
``groupby_aggregate_capped(num_segments=N)`` returns, and dead rows
above it. The served runners launch the halves apart and take K =
``bucket_for(num_groups)`` (``bucketed._reduce_groups``): the K edges,
the per-op and fused-tail shapes and the ``groupby.input_rows`` /
``groupby.reduce_rows`` counters are pinned through the resident plan
entry point against the exact path.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops.groupby import (
    GroupbyAgg,
    groupby_aggregate_capped,
    groupby_reduce,
    groupby_sort,
)
from spark_rapids_jni_tpu.utils import buckets, config, metrics

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)

N = 96  # rows: the one-trace form's num_segments
K = 24  # the reduce half's width: >= every group count below, << N

AGGS = ("sum", "count", "mean", "min", "max", "first", "last", "nunique",
        "variance", "std")
DTYPES = ("int64", "float64", "decimal64", "decimal128")
VARIANTS = ("plain", "row_valid", "null_keys", "two_keys")


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


def _value_column(kind: str, rng) -> Column:
    ints = rng.integers(-1000, 1000, N).astype(np.int64)
    valid = jnp.asarray(rng.random(N) > 0.2)
    if kind == "int64":
        return Column.from_numpy(ints, validity=np.asarray(valid))
    if kind == "float64":
        return Column.from_numpy(
            rng.normal(size=N) * 1e3, validity=np.asarray(valid)
        )
    if kind == "decimal64":
        return Column(
            jnp.asarray(ints), dt.DType(dt.TypeId.DECIMAL64, -2), valid
        )
    limbs = np.stack(
        [ints.astype(np.uint64),
         np.where(ints < 0, np.uint64(0xFFFFFFFFFFFFFFFF), np.uint64(0))],
        axis=1,
    )
    return Column(
        jnp.asarray(limbs), dt.DType(dt.TypeId.DECIMAL128, -2), valid
    )


def _case(kind: str, variant: str):
    rng = np.random.default_rng(11)
    a = rng.integers(0, 9, N).astype(np.int64)
    b = rng.integers(0, 2, N).astype(np.int64)
    keys = [Column.from_numpy(a)]
    by = ["a"]
    names = ["a", "v"]
    if variant == "null_keys":
        keys = [Column.from_numpy(a, validity=rng.random(N) > 0.15)]
    if variant == "two_keys":
        keys.append(Column.from_numpy(b))
        by, names = ["a", "b"], ["a", "b", "v"]
    row_valid = (
        jnp.arange(N, dtype=jnp.int32) < 71
        if variant == "row_valid" else None
    )
    return Table(keys + [_value_column(kind, rng)], names), by, row_valid


def _buffers(col: Column):
    return [
        None if x is None else np.asarray(x)
        for x in (col.data, col.validity, col.lengths)
    ]


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("kind", DTYPES)
@pytest.mark.parametrize("agg", AGGS)
def test_halves_match_one_trace(agg, kind, variant):
    table, by, row_valid = _case(kind, variant)
    aggs = [GroupbyAgg("v", agg)]
    if agg == "nunique" and kind == "decimal128":
        # unsupported in both forms alike
        with pytest.raises(TypeError, match="nunique not supported"):
            groupby_aggregate_capped(table, by, aggs, N, row_valid)
        state, count = groupby_sort(table, by, aggs, row_valid)
        with pytest.raises(TypeError, match="nunique not supported"):
            groupby_reduce(state, count, K)
        return
    want, want_count = groupby_aggregate_capped(
        table, by, aggs, num_segments=N, row_valid=row_valid
    )
    state, count = groupby_sort(table, by, aggs, row_valid=row_valid)
    got = groupby_reduce(state, count, K)
    g = int(count)
    assert g == int(want_count) and 0 < g <= K
    assert got.names == want.names
    assert got.row_count == K and want.row_count == N
    for gc, wc in zip(got.columns, want.columns):
        assert gc.dtype == wc.dtype
        for gb, wb in zip(_buffers(gc), _buffers(wc)):
            assert (gb is None) == (wb is None)
            if gb is not None:
                assert gb.dtype == wb.dtype
                assert gb[:g].tobytes() == wb[:g].tobytes()
        # rows past the group count are dead in both
        assert not np.asarray(gc.validity)[g:].any()
        assert not np.asarray(wc.validity)[g:].any()


def test_reduce_at_the_input_width_is_the_one_trace_form():
    # K == N: every byte of the padded result, not only the live rows
    table, by, row_valid = _case("float64", "two_keys")
    aggs = [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")]
    want, _ = groupby_aggregate_capped(table, by, aggs, num_segments=N)
    state, count = groupby_sort(table, by, aggs)
    got = groupby_reduce(state, count, N)
    assert got.names == want.names
    for gc, wc in zip(got.columns, want.columns):
        for gb, wb in zip(_buffers(gc), _buffers(wc)):
            assert (gb is None) == (wb is None)
            if gb is not None:
                assert gb.tobytes() == wb.tobytes()


# ---------------------------------------------------------------------------
# the served runners: K = bucket_for(num_groups), per-op and fused tail
# ---------------------------------------------------------------------------

GROUP = {"op": "groupby", "by": [0], "aggs": [
    {"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"},
    {"column": 1, "agg": "max"}]}
FILTER = {"op": "filter", "mask": 2}
HEADS = {"per_op": [], "fused": [FILTER]}

# (rows, distinct keys) -> (input bucket N, reduce bucket K) on the
# 16 x2 ladder
EDGES = {
    "one_group": (100, 1, 128, 16),
    "exactly_a_bucket": (100, 32, 128, 32),
    "one_above_a_bucket": (100, 33, 128, 64),
    "every_row_its_own_group": (64, 64, 64, 64),
}


def _run_resident(ops, cols, n):
    """-> ((physical rows, logical rows), downloaded wire tuple)."""
    ids = [I64, I64, B8][: len(cols)]
    tid = rb.table_upload_wire(
        ids, [0] * len(cols), [c.tobytes() for c in cols],
        [None] * len(cols), n,
    )
    out = rb.table_plan_resident(json.dumps(ops), [tid])
    t = rb._resident_get(out)
    shape = (t.row_count, t.logical_rows)
    got = rb.table_download_wire(out)
    rb.table_free(tid)
    rb.table_free(out)
    return shape, got


@pytest.mark.parametrize("head", HEADS.values(), ids=HEADS.keys())
@pytest.mark.parametrize("edge", EDGES.values(), ids=EDGES.keys())
def test_reduce_width_is_the_bucket_of_the_group_count(edge, head):
    n, groups, n_bucket, k_bucket = edge
    rng = np.random.default_rng(groups)
    k = np.arange(n, dtype=np.int64) % groups
    rng.shuffle(k)
    v = rng.integers(-50, 50, n, dtype=np.int64)
    cols = [k, v] + ([np.ones(n, np.uint8)] if head else [])
    plan = head + [GROUP]

    config.set_flag("BUCKETS", "16:2")
    config.set_flag("METRICS", True)
    metrics.reset()
    shape, got = _run_resident(plan, cols, n)
    counters = metrics.snapshot()["counters"]
    assert shape == (k_bucket, groups)
    assert counters["groupby.input_rows"] == n_bucket
    assert counters["groupby.reduce_rows"] == k_bucket
    assert counters.get("plan.fallbacks", 0) == 0
    assert counters.get("bucket.fallback_errors", 0) == 0
    config.set_flag("BUCKETS", "off")
    assert _run_resident(plan, cols, n)[1] == got


def test_zero_groups_still_return_the_schema():
    # an all-false filter ahead of the groupby: no group at all, and the
    # reduce half still runs — at the smallest bucket
    n = 100
    k = np.arange(n, dtype=np.int64) % 5
    v = np.arange(n, dtype=np.int64)
    cols = [k, v, np.zeros(n, np.uint8)]
    plan = [FILTER, GROUP]
    config.set_flag("BUCKETS", "16:2")
    config.set_flag("METRICS", True)
    metrics.reset()
    shape, got = _run_resident(plan, cols, n)
    counters = metrics.snapshot()["counters"]
    assert shape == (16, 0)
    assert counters["groupby.reduce_rows"] == 16
    assert counters.get("plan.fallbacks", 0) == 0
    assert got[4] == 0 and len(got[0]) == 4
    config.set_flag("BUCKETS", "off")
    assert _run_resident(plan, cols, n)[1] == got


def test_per_op_and_fused_tail_share_the_reduce_executable():
    # same input bucket, same keys and aggregates, same group bucket:
    # the second half is keyed by what it reads, not by who launched it
    n = 100
    rng = np.random.default_rng(3)
    k = rng.integers(0, 7, n, dtype=np.int64)
    v = rng.integers(-5, 5, n, dtype=np.int64)
    config.set_flag("BUCKETS", "16:2")
    config.set_flag("METRICS", True)
    buckets.cache_clear()
    shape_a, got_a = _run_resident([GROUP], [k, v], n)
    metrics.reset()
    shape_b, got_b = _run_resident(
        [FILTER, GROUP], [k, v, np.ones(n, np.uint8)], n
    )
    assert shape_a == shape_b == (16, 7)
    assert got_a == got_b
    # the fused segment compiled (its sort half); the reduce half hit
    counters = metrics.snapshot()["counters"]
    assert counters["compile_cache.miss"] == 1
    assert counters["compile_cache.hit"] >= 1
    timers = metrics.snapshot()["timers"]
    assert timers["groupby.reduce"]["count"] == 1


# ---------------------------------------------------------------------------
# the sort's key words: narrow keys fold into one word, 64-bit keys keep
# theirs, and either way the groups are numpy's
# ---------------------------------------------------------------------------

KEY_KINDS = {
    "int8": (dt.INT8, np.int8, lambda r, n: r.integers(-3, 4, n)),
    "int16": (dt.INT16, np.int16, lambda r, n: r.integers(-300, 300, n) // 100),
    "int32": (dt.INT32, np.int32, lambda r, n: r.integers(-2, 3, n) * 10**9),
    "uint8": (dt.UINT8, np.uint8, lambda r, n: r.integers(250, 256, n)),
    "bool": (dt.BOOL8, np.bool_, lambda r, n: r.integers(0, 2, n)),
    "float32": (dt.FLOAT32, np.float32, lambda r, n: r.integers(-2, 3, n) * 0.5),
    "decimal32": (dt.decimal32(-2), np.int32, lambda r, n: r.integers(-2, 3, n)),
    "int64": (dt.INT64, np.int64, lambda r, n: r.integers(-2, 3, n) * 2**40),
}
# key kinds -> (words without an occupancy bit, with one): a word's dtype
KEY_SETS = {
    ("int8", "int8"): (["uint32"], ["uint32"]),
    ("int8", "bool"): (["uint32"], ["uint32"]),
    ("int16", "int16"): (["uint32"], ["uint64"]),
    ("int32", "int32"): (["uint64"], ["uint64", "uint32"]),
    ("float32", "uint8"): (["uint64"], ["uint64"]),
    ("decimal32", "int8"): (["uint64"], ["uint64"]),
    ("int8", "int64"): (["uint32", "uint64"], ["uint32", "uint64"]),
    ("int64", "int8"): (["uint64", "uint32"], ["uint32", "uint64", "uint32"]),
}


@pytest.mark.parametrize("occupancy", [False, True], ids=["whole", "padded"])
@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "null_keys"])
@pytest.mark.parametrize("kinds", sorted(KEY_SETS), ids="-".join)
def test_narrow_keys_fold_into_one_word(kinds, nulls, occupancy):
    from spark_rapids_jni_tpu.ops.groupby import _key_words

    rng = np.random.default_rng(len(kinds[0]) * 7 + len(kinds[1]))
    live = N - 17 if occupancy else N
    cols, host = [], []
    for kind in kinds:
        d, npdt, gen = KEY_KINDS[kind]
        vals = np.asarray(gen(rng, N)).astype(npdt)
        valid = rng.random(N) > 0.25 if nulls else None
        cols.append(Column(jnp.asarray(vals), d,
                           None if valid is None else jnp.asarray(valid)))
        host.append((vals, valid))
    values = rng.integers(-50, 50, N).astype(np.int64)
    table = Table(cols + [Column(jnp.asarray(values), dt.INT64, None)])
    rv = jnp.arange(N) < live if occupancy else None
    words, _ = _key_words(table.columns[:2], rv)
    if not nulls:
        assert [str(w.dtype) for w in words] == KEY_SETS[kinds][occupancy]
    out, num_groups = groupby_aggregate_capped(
        table, [0, 1], [GroupbyAgg(2, "sum"), GroupbyAgg(2, "count")],
        num_segments=N, row_valid=rv,
    )
    want = {}
    for i in range(live):
        key = tuple(None if v is not None and not v[i] else vals[i].item()
                    for vals, v in host)
        s, c = want.get(key, (0, 0))
        want[key] = (s + int(values[i]), c + 1)
    g = len(want)
    assert int(num_groups) == g
    got = {}
    for r in range(g):
        key = tuple(
            None if c.validity is not None and not bool(c.validity[r])
            else np.asarray(c.data)[r].item() for c in out.columns[:2])
        got[key] = (int(out.columns[2].data[r]), int(out.columns[3].data[r]))
    assert got == want
    # dead rows above the group count
    assert not np.asarray(out.columns[3].validity)[g:].any()
    # groups come out in key order, nulls first
    order = [tuple((k is not None, k if k is not None else 0) for k in key)
             for key in got]
    assert order == sorted(order)
