"""Plan-level fused dispatch: segmentation, parity, and compile bounds.

The contract under test (the ISSUE-4 tentpole): ``table_plan_wire`` /
``table_plan_resident`` compile each maximal run of fusable ops into
ONE cached executable and return results BYTE-IDENTICAL to the per-op
wire path (which tests/test_buckets.py pins byte-identical to the
exact path) — null counts, sort stability, group counts included — at
bucket-boundary row counts (1023/1024/1025). The recompile-regression
half pins the launch/compile economics: an 8-size ragged stream
through a 4-op fusable plan compiles at most ``#buckets`` fused
executables, double-sourced from the cache counters and from
``jax.log_compiles`` output filtered to ``srt_fused_plan`` (the
test_buckets_recompile.py discipline).
"""

import dataclasses
import json
import logging

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import buckets, config, metrics

I64 = int(dt.TypeId.INT64)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)
STR = int(dt.TypeId.STRING)


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


# ---------------------------------------------------------------------------
# segmentation
# ---------------------------------------------------------------------------


CAST = {"op": "cast", "column": 0, "type_id": F64}
SORT = {"op": "sort_by", "keys": [{"column": 0}]}
GROUP = {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]}
JOIN = {"op": "join", "on": [0]}


class TestSegmentation:
    def test_fusable_run_is_one_segment(self):
        segs = plan_mod.segment_plan([CAST, SORT, GROUP])
        assert segs == [("fused", [CAST, SORT, GROUP])]

    def test_groupby_is_tail_only(self):
        segs = plan_mod.segment_plan([CAST, GROUP, SORT, CAST])
        assert segs == [
            ("fused", [CAST, GROUP]),
            ("fused", [SORT, CAST]),
        ]

    def test_non_fusable_is_a_boundary(self):
        segs = plan_mod.segment_plan([CAST, SORT, JOIN, CAST, SORT])
        assert segs == [
            ("fused", [CAST, SORT]),
            ("exact", [JOIN]),
            ("fused", [CAST, SORT]),
        ]

    def test_single_op_runs_stay_exact(self):
        # a 1-op run gains nothing from a separate plan cache entry:
        # the per-op bucketed runner already caches it under its own key
        segs = plan_mod.segment_plan([CAST, JOIN, SORT])
        assert segs == [
            ("exact", [CAST]),
            ("exact", [JOIN]),
            ("exact", [SORT]),
        ]

    def test_collect_groupby_not_fusable(self):
        collect = {
            "op": "groupby", "by": [0],
            "aggs": [{"column": 1, "agg": "collect_list"}],
        }
        assert not planops.op_fusable(collect)
        assert plan_mod.segment_plan([CAST, SORT, collect]) == [
            ("fused", [CAST, SORT]),
            ("exact", [collect]),
        ]

    def test_negative_slice_not_fusable(self):
        # negative bounds must raise from the exact path
        assert not planops.op_fusable({"op": "slice", "start": -1})
        assert planops.op_fusable({"op": "slice", "start": 1, "stop": 9})


# ---------------------------------------------------------------------------
# fused-vs-per-op parity at bucket boundaries
# ---------------------------------------------------------------------------


def _string_wire(strings):
    """List of python strings -> Arrow offsets+payload wire bytes."""
    payload = b"".join(s.encode() for s in strings)
    offs = np.zeros(len(strings) + 1, np.int32)
    np.cumsum([len(s.encode()) for s in strings], out=offs[1:])
    return offs.tobytes() + payload


def _cols(n: int):
    """Shared parity-table columns: int64 key, int64 value with nulls,
    BOOL8 mask, and a low-cardinality STRING column."""
    rng = np.random.default_rng(n)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % 7 != 0).astype(np.uint8)
    mask = (v > 0).astype(np.uint8)
    strs = [f"w{int(x) % 5}ord" for x in k]
    return [
        (I64, 0, k.tobytes(), None),
        (I64, 0, v.tobytes(), valid.tobytes()),
        (B8, 0, mask.tobytes(), None),
        (STR, 0, _string_wire(strs), None),
    ]


# >= 5 multi-op chains over the shared 4-column table. Column indices
# track the per-op semantics (filter drops its mask column).
CHAINS = {
    "filter_cast_sort_groupby": [
        {"op": "filter", "mask": 2},
        {"op": "cast", "column": 1, "type_id": F64},
        {"op": "sort_by", "keys": [{"column": 0}]},
        {"op": "groupby", "by": [0],
         "aggs": [{"column": 1, "agg": "sum"},
                  {"column": 1, "agg": "count"}]},
    ],
    "rlike_cast_sort": [
        {"op": "rlike", "column": 3, "pattern": "w[0-2]o"},
        {"op": "cast", "column": 1, "type_id": F64},
        {"op": "sort_by", "keys": [{"column": 0}]},
    ],
    "distinct_sort_slice": [
        {"op": "distinct", "keys": [0, 1]},
        {"op": "sort_by",
         "keys": [{"column": 0}, {"column": 1, "ascending": False}]},
        {"op": "slice", "start": 3, "stop": 77},
    ],
    "cast_cast_sort_distinct_groupby": [
        {"op": "cast", "column": 1, "type_id": F64},
        {"op": "cast", "column": 0, "type_id": int(dt.TypeId.INT32)},
        {"op": "sort_by", "keys": [{"column": 1}]},
        {"op": "distinct", "keys": [0]},
        {"op": "groupby", "by": [0],
         "aggs": [{"column": 1, "agg": "max"}]},
    ],
    "slice_filter_sort": [
        {"op": "slice", "start": 0, "stop": 999_999},  # stop clamps to n
        {"op": "filter", "mask": 2},
        {"op": "sort_by", "keys": [{"column": 1}, {"column": 0}]},
    ],
}

BOUNDARY_SIZES = (1023, 1024, 1025)


def _run_plan_wire(chain, cols, n):
    return rb.table_plan_wire(
        json.dumps(chain),
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n,
    )


def _run_per_op_wire(chain, cols, n):
    cur = (
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n,
    )
    for op in chain:
        cur = rb.table_op_wire(json.dumps(op), *cur)
    return cur


class TestFusedParity:
    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    @pytest.mark.parametrize("chain", sorted(CHAINS))
    def test_fused_equals_per_op_and_exact(self, chain, n):
        cols = _cols(n)
        ops = CHAINS[chain]
        config.set_flag("BUCKETS", "")
        fused = _run_plan_wire(ops, cols, n)
        per_op = _run_per_op_wire(ops, cols, n)
        config.set_flag("BUCKETS", "off")
        exact = _run_per_op_wire(ops, cols, n)
        # byte-identical 5-tuples: type ids, scales, data bytes
        # (values, sort order, group sums), validity bytes (null
        # counts) and row counts all included
        assert fused == per_op
        assert fused == exact

    def test_fused_actually_fused(self):
        # the parity above is meaningless if everything silently fell
        # back: the 4-op chain must run as ONE fused segment
        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", True)
        metrics.reset()
        _run_plan_wire(
            CHAINS["filter_cast_sort_groupby"], _cols(1024), 1024
        )
        c = metrics.snapshot()["counters"]
        assert c["plan.segments"] == 1
        assert c["plan.fused_segments"] == 1
        assert c["plan.fused_ops"] == 4
        assert c.get("plan.fallbacks", 0) == 0
        assert c.get("plan.exact_ops", 0) == 0

    def test_resident_plan_matches_wire_plan(self):
        n = 1025
        cols = _cols(n)
        ops = CHAINS["filter_cast_sort_groupby"]
        config.set_flag("BUCKETS", "")
        fused = _run_plan_wire(ops, cols, n)
        tid = rb.table_upload_wire(
            [c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols], n,
        )
        out_id = rb.table_plan_resident(json.dumps(ops), [tid])
        got = rb.table_download_wire(out_id)
        rb.table_free(tid)
        rb.table_free(out_id)
        assert got == fused

    def test_plan_with_join_boundary(self):
        # a non-fusable multi-table op splits segments and consumes a
        # rest table; the whole plan still matches per-op dispatch
        n = 600
        rng = np.random.default_rng(5)
        k = rng.integers(0, 50, n, dtype=np.int64)
        v = rng.integers(-9, 9, n, dtype=np.int64)
        rk = np.arange(0, 50, dtype=np.int64)
        rv = rng.integers(0, 5, 50, dtype=np.int64)
        up = lambda *arrs: rb.table_upload_wire(
            [I64] * len(arrs), [0] * len(arrs),
            [a.tobytes() for a in arrs], [None] * len(arrs),
            len(arrs[0]),
        )
        plan = [
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "cast", "column": 1, "type_id": F64},
            {"op": "join", "on": [0]},
            {"op": "sort_by", "keys": [{"column": 0}, {"column": 1}]},
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 2, "agg": "sum"}]},
        ]
        lt, rt = up(k, v), up(rk, rv)
        out_id = rb.table_plan_resident(json.dumps(plan), [lt, rt])
        got = rb.table_download_wire(out_id)
        for t in (lt, rt, out_id):
            rb.table_free(t)

        cur = up(k, v)
        for op in plan:
            ids = [cur, up(rk, rv)] if op["op"] == "join" else [cur]
            nxt = rb.table_op_resident(json.dumps(op), ids)
            for t in ids:
                rb.table_free(t)
            cur = nxt
        want = rb.table_download_wire(cur)
        rb.table_free(cur)
        assert got == want

    @pytest.mark.parametrize("head", [
        [],  # one-op run: the per-op bucketed groupby runner
        [{"op": "filter", "mask": 2}],  # fused filter -> groupby segment
    ], ids=["per_op", "fused"])
    def test_groupby_output_shrinks_to_its_own_bucket(self, head):
        # 7 groups out of a 4096-row bucket: what follows the groupby
        # (here a sort_by, then the download) runs at the 1024 floor
        # bucket, not at the input's — same bytes as the exact path
        n = 3000
        rng = np.random.default_rng(9)
        k = rng.integers(0, 7, n, dtype=np.int64)
        v = rng.integers(-5, 5, n, dtype=np.int64)
        m = np.ones(n, np.uint8)
        plan = head + [GROUP]
        tail = [{"op": "sort_by", "keys": [
            {"column": 1, "ascending": False}, {"column": 0}]}]
        ids, datas = [I64, I64], [k.tobytes(), v.tobytes()]
        if head:
            ids, datas = ids + [B8], datas + [m.tobytes()]

        def run(ops):
            tid = rb.table_upload_wire(
                ids, [0] * len(ids), datas, [None] * len(ids), n
            )
            out = rb.table_plan_resident(json.dumps(ops), [tid])
            t = rb._resident_get(out)
            shape = (t.row_count, t.logical_rows)
            got = rb.table_download_wire(out)
            rb.table_free(tid)
            rb.table_free(out)
            return shape, got

        config.set_flag("BUCKETS", "")
        assert run(plan)[0] == (1024, 7)
        shape, got = run(plan + tail)
        assert shape == (1024, 7)
        config.set_flag("BUCKETS", "off")
        assert run(plan + tail)[1] == got

    def test_fused_failure_replays_per_op(self, monkeypatch):
        # a broken fused builder must not change results — the segment
        # replays per-op and the failure is counted + WARN'd once
        def boom(op, t, n, rv):
            raise RuntimeError("injected fused failure")

        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", True)
        n = 1024
        cols = _cols(n)
        ops = CHAINS["filter_cast_sort_groupby"]
        want = _run_per_op_wire(ops, cols, n)
        monkeypatch.setitem(
            planops.OPS, "cast",
            dataclasses.replace(planops.OPS["cast"], traced=boom),
        )
        # a warm cache would launch the previously compiled segment
        # without ever reaching the patched builder
        buckets.cache_clear()
        metrics.reset()
        got = _run_plan_wire(ops, cols, n)
        assert got == want
        c = metrics.snapshot()["counters"]
        assert c["plan.fallbacks"] == 1
        assert c["plan.exact_ops"] == 4
        assert c.get("plan.fused_segments", 0) == 0

    def test_huge_slice_bound_stays_fused(self):
        # a valid stop past int32 range clamps (like the exact path)
        # instead of overflowing the traced int32 conversion into a
        # permanent per-call fallback
        config.set_flag("BUCKETS", "")
        config.set_flag("METRICS", True)
        n = 1024
        cols = _cols(n)
        ops = [
            {"op": "cast", "column": 1, "type_id": F64},
            {"op": "slice", "start": 1, "stop": 2 ** 31},
        ]
        want = _run_per_op_wire(ops, cols, n)
        buckets.cache_clear()
        metrics.reset()
        got = _run_plan_wire(ops, cols, n)
        assert got == want and got[4] == n - 1
        c = metrics.snapshot()["counters"]
        assert c.get("plan.fallbacks", 0) == 0
        assert c["plan.fused_segments"] == 1

    def test_op_error_surfaces_from_exact_path(self):
        config.set_flag("BUCKETS", "")
        n = 1024
        cols = _cols(n)
        bad = [
            {"op": "cast", "column": 1, "type_id": F64},
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "unknown_op"},
        ]
        with pytest.raises(ValueError, match="unknown table op"):
            _run_plan_wire(bad, cols, n)

    def test_malformed_plan_rejected(self):
        cols = _cols(8)
        with pytest.raises(TypeError, match="JSON list"):
            _run_plan_wire({"op": "cast"}, cols, 8)
        with pytest.raises(ValueError, match="op objects"):
            rb.table_plan_wire(
                json.dumps(["cast"]),
                [c[0] for c in cols], [c[1] for c in cols],
                [c[2] for c in cols], [c[3] for c in cols], 8,
            )


class TestFactoriesEntry:
    def test_run_plan_matches_wire_plan(self):
        from spark_rapids_jni_tpu import factories
        from spark_rapids_jni_tpu.column import Column, Table

        config.set_flag("BUCKETS", "")
        n = 1023
        rng = np.random.default_rng(2)
        k = rng.integers(0, 9, n, dtype=np.int64)
        v = rng.integers(-100, 100, n, dtype=np.int64)
        m = v > 0
        t = Table(
            [Column.from_numpy(k), Column.from_numpy(v),
             Column.from_numpy(m, dtype=dt.BOOL8)],
            ["k", "v", "m"],
        )
        ops = [
            {"op": "filter", "mask": 2},
            {"op": "sort_by", "keys": [{"column": 0}, {"column": 1}]},
            {"op": "distinct", "keys": [0]},
        ]
        got = factories.run_plan(ops, t)
        assert got.logical_rows is None  # exact by default
        padded = factories.run_plan(ops, t, unpad=False)
        assert padded.logical_rows == got.row_count
        # oracle: the per-op wire path on the same bytes
        want = _run_per_op_wire(
            ops,
            [(I64, 0, k.tobytes(), None), (I64, 0, v.tobytes(), None),
             (B8, 0, m.astype(np.uint8).tobytes(), None)],
            n,
        )
        assert got.row_count == want[4]
        assert np.asarray(got.columns[0].data).tobytes() == want[2][0]
        assert np.asarray(got.columns[1].data).tobytes() == want[2][1]


# ---------------------------------------------------------------------------
# recompile regression: one executable per segment per bucket
# ---------------------------------------------------------------------------


# 8 ragged sizes spanning exactly TWO buckets of the 1024 x2 ladder
# (the test_buckets_recompile.py stream shape)
SIZES = (911, 977, 1013, 1024, 1031, 1499, 1777, 2047)
N_BUCKETS = 2

PLAN_4OP = [
    {"op": "filter", "mask": 2},
    {"op": "cast", "column": 1, "type_id": F64},
    {"op": "sort_by", "keys": [{"column": 0}]},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
]


class _CompileLog(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _plan_stream():
    for n in SIZES:
        rng = np.random.default_rng(n)
        k = rng.integers(0, 7, n, dtype=np.int64)
        v = rng.integers(-5, 5, n, dtype=np.int64)
        m = (v > 0).astype(np.uint8)
        out = rb.table_plan_wire(
            json.dumps(PLAN_4OP), [I64, I64, B8], [0, 0, 0],
            [k.tobytes(), v.tobytes(), m.tobytes()],
            [None, None, None], n,
        )
        assert out[4] > 0


def _captured_plan_stream():
    handler = _CompileLog()
    jax_logger = logging.getLogger("jax")
    jax_logger.addHandler(handler)
    try:
        with jax.log_compiles():
            _plan_stream()
    finally:
        jax_logger.removeHandler(handler)
    return [m for m in handler.messages if m.startswith("Compiling ")]


class TestPlanRecompile:
    def test_ragged_stream_compiles_at_most_buckets_executables(self):
        config.set_flag("BUCKETS", "1024:2")
        config.set_flag("METRICS", True)
        jax.clear_caches()
        buckets.cache_clear()
        metrics.reset()
        compiles = _captured_plan_stream()

        snap = metrics.snapshot()
        misses = snap["counters"]["compile_cache.miss"]
        hits = snap["counters"].get("compile_cache.hit", 0)
        # ONE segment per plan call -> at most one fused executable per
        # input bucket across the whole ragged stream, plus the groupby
        # tail's per-group half: one per (input bucket, group-count
        # bucket) pair, and 7 groups always land in the 1024 bucket.
        # Every further call is a cache hit == one launch of each
        assert misses <= 2 * N_BUCKETS, f"{misses} compiles for {N_BUCKETS}"
        assert hits == 2 * len(SIZES) - misses
        assert snap["counters"]["plan.fused_ops"] == len(SIZES) * 4
        assert snap["counters"]["plan.segments"] == len(SIZES)
        # cross-check against the ACTUAL XLA compile log
        fused = [m for m in compiles if "srt_fused_plan" in m]
        assert len(fused) <= N_BUCKETS, fused
        halves = [m for m in compiles if "srt_groupby_reduce" in m]
        assert len(halves) <= N_BUCKETS, halves
        assert snap["counters"]["groupby.reduce_rows"] == len(SIZES) * 1024
        # and nothing leaked onto the per-op bucketed path
        assert not [m for m in compiles if "srt_bucketed" in m]

    def test_second_stream_is_all_hits(self):
        config.set_flag("BUCKETS", "1024:2")
        config.set_flag("METRICS", True)
        jax.clear_caches()
        buckets.cache_clear()
        _plan_stream()  # warm
        metrics.reset()
        compiles = _captured_plan_stream()
        snap = metrics.snapshot()
        assert not [m for m in compiles if "srt_fused_plan" in m]
        assert not [m for m in compiles if "srt_groupby_reduce" in m]
        assert snap["counters"].get("compile_cache.miss", 0) == 0
        # the fused executable and the groupby's per-group half
        assert snap["counters"]["compile_cache.hit"] == 2 * len(SIZES)


# ---------------------------------------------------------------------------
# wire-serialize satellite: mask-buffer reuse counter
# ---------------------------------------------------------------------------


class TestSerializeSavedBytes:
    def test_saved_bytes_counted_for_repeated_string_shapes(self):
        config.set_flag("METRICS", True)
        n = 64
        strs = _string_wire([f"s{i % 3}" for i in range(n)])
        metrics.reset()
        out = rb.table_op_wire(
            json.dumps({"op": "slice", "start": 0, "stop": n}),
            [STR, STR, I64], [0, 0, 0],
            [strs, strs,
             np.arange(n, dtype=np.int64).tobytes()],
            [None, None, None], n,
        )
        assert out[4] == n
        snap = metrics.snapshot()
        # both STRING columns are constant-width (every "sN" is 2
        # bytes, pad=2), so each takes the ISSUE-5 serialize fast path:
        # the (n, pad) row mask is never built at all — counted as one
        # saved (n, pad) buffer per column
        assert snap["bytes"]["wire.serialize.saved_bytes"] == 2 * n * 2

    def test_saved_bytes_mask_reuse_for_ragged_strings(self):
        # ragged lengths force the mask path; the second same-shape
        # column reuses the first one's mask buffer (the pre-ISSUE-5
        # saving, still live for non-constant-width payloads)
        config.set_flag("METRICS", True)
        n = 64
        strs = _string_wire(
            [("s" * ((i % 3) + 1)) for i in range(n)]
        )
        metrics.reset()
        out = rb.table_op_wire(
            json.dumps({"op": "slice", "start": 0, "stop": n}),
            [STR, STR, I64], [0, 0, 0],
            [strs, strs,
             np.arange(n, dtype=np.int64).tobytes()],
            [None, None, None], n,
        )
        assert out[4] == n
        snap = metrics.snapshot()
        # one reuse of an (n, pad=3) bool mask buffer
        assert snap["bytes"]["wire.serialize.saved_bytes"] == n * 3
