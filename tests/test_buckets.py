"""Shape-bucket plane: policy, padding, and bucket-edge SEMANTICS.

The contract under test: with pad-to-bucket batching ON (the default),
every dispatch-plane op returns byte-identical wire results to the
exact-shape path (``SPARK_RAPIDS_TPU_BUCKETS=off``) — null counts,
groupby group counts, sort stability, and join cardinality included —
at bucket-boundary row counts (1023/1024/1025 around the default 1024
floor; a small explicit ladder for the cheap sweeps).
"""

import json

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import buckets, config, metrics

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------


class TestPolicy:
    def test_default_ladder(self):
        assert buckets.enabled()
        assert buckets.bucket_for(1) == 1024
        assert buckets.bucket_for(1023) == 1024
        assert buckets.bucket_for(1024) == 1024
        assert buckets.bucket_for(1025) == 2048
        assert buckets.bucket_for(0) is None
        assert buckets.bucket_for(-5) is None
        # past the ladder cap: exact dispatch
        assert buckets.bucket_for((1 << 23) + 1) is None

    def test_floor_growth_spec(self):
        config.set_flag("BUCKETS", "16:4")
        assert buckets.bucket_for(10) == 16
        assert buckets.bucket_for(16) == 16
        assert buckets.bucket_for(17) == 64
        assert buckets.bucket_for(65) == 256

    def test_cap_spec(self):
        config.set_flag("BUCKETS", "16:2:64")
        assert buckets.bucket_for(64) == 64
        assert buckets.bucket_for(65) is None

    def test_explicit_list(self):
        config.set_flag("BUCKETS", "8,64,512")
        assert buckets.bucket_for(5) == 8
        assert buckets.bucket_for(8) == 8
        assert buckets.bucket_for(9) == 64
        assert buckets.bucket_for(65) == 512
        assert buckets.bucket_for(513) is None

    def test_off_values(self):
        for spec in ("off", "0", "none", "false", "disabled"):
            config.set_flag("BUCKETS", spec)
            assert not buckets.enabled()
            assert buckets.bucket_for(100) is None

    def test_invalid_spec_raises_loudly(self):
        config.set_flag("BUCKETS", "banana")
        with pytest.raises(ValueError, match="SPARK_RAPIDS_TPU_BUCKETS"):
            buckets.policy()
        config.set_flag("BUCKETS", "16:1")  # growth < 2
        with pytest.raises(ValueError):
            buckets.policy()


# ---------------------------------------------------------------------------
# pad / unpad / Table.logical_rows
# ---------------------------------------------------------------------------


def _mixed_table(n: int) -> Table:
    rng = np.random.default_rng(n)
    k = rng.integers(0, 7, n, dtype=np.int64)
    v = rng.integers(-50, 50, n, dtype=np.int64)
    valid = rng.random(n) > 0.2
    strs = [f"s{int(x) % 5}" if valid[i] else None
            for i, x in enumerate(k)]
    return Table(
        [
            Column.from_numpy(k),
            Column.from_numpy(v, validity=valid),
            Column.from_strings(strs),
        ],
        ["k", "v", "s"],
    )


class TestPadUnpad:
    def test_round_trip(self):
        t = _mixed_table(10)
        p = buckets.pad_table(t, 16)
        assert p.row_count == 16
        assert p.logical_rows == 10
        assert p.logical_row_count == 10
        assert p.is_padded
        # padded tail: zero data, False validity, zero lengths
        assert not np.asarray(p.columns[1].validity)[10:].any()
        assert not np.asarray(p.columns[2].lengths)[10:].any()
        back = buckets.unpad_table(p)
        assert back.row_count == 10
        assert not back.is_padded
        assert back.to_pydict() == t.to_pydict()

    def test_logical_rows_validation(self):
        c = Column.from_numpy(np.arange(4, dtype=np.int64))
        with pytest.raises(ValueError):
            Table([c], logical_rows=5)
        with pytest.raises(ValueError):
            Table([c], logical_rows=-1)

    def test_pad_down_rejected(self):
        t = _mixed_table(10)
        with pytest.raises(ValueError):
            buckets.pad_table(t, 4)

    def test_factories_entry_points(self):
        from spark_rapids_jni_tpu import factories

        config.set_flag("BUCKETS", "16:2")
        t = _mixed_table(10)
        p = factories.pad_to_bucket(t)
        assert p.row_count == 16 and p.logical_rows == 10
        assert factories.unpad_table(p).to_pydict() == t.to_pydict()
        config.set_flag("BUCKETS", "off")
        assert factories.pad_to_bucket(t) is t

    def test_pad_to_bucket_passes_through_larger_padded(self):
        # a capped-op output can sit at a bucket ABOVE its logical
        # count's own bucket; re-bucketing must pass it through, not
        # try to pad down
        from spark_rapids_jni_tpu import factories

        config.set_flag("BUCKETS", "16:2")
        t = _mixed_table(10)
        big = buckets.pad_table(t, 64)
        assert factories.pad_to_bucket(big) is big
        again = factories.pad_to_bucket(factories.pad_to_bucket(t))
        assert again.row_count == 16 and again.logical_rows == 10

    def test_is_bucketable_gate(self):
        assert planops.op_bucketable({"op": "sort_by", "keys": []})
        assert planops.op_bucketable({"op": "join", "how": "semi"})
        assert planops.op_bucketable({"op": "join"})  # default inner
        assert not planops.op_bucketable({"op": "join", "how": "full"})
        assert not planops.op_bucketable({"op": "explode"})
        assert not planops.op_bucketable({"op": "concat"})
        assert planops.op_bucketable(
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "sum"}]}
        )
        assert not planops.op_bucketable(
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "collect_list"}]}
        )

    def test_padded_table_is_a_pytree(self):
        import jax

        t = buckets.pad_table(_mixed_table(10), 16)
        leaves, treedef = jax.tree.flatten(t)
        back = jax.tree.unflatten(treedef, leaves)
        assert back.logical_rows == 10
        assert back.names == ("k", "v", "s")


# ---------------------------------------------------------------------------
# bucket-edge semantics: bucketing on == off, byte for byte
# ---------------------------------------------------------------------------


def _wire(op: dict, cols, n: int):
    """Run one wire op over (dtype_id, bytes, valid_bytes|None) cols."""
    return rb.table_op_wire(
        json.dumps(op),
        [c[0] for c in cols],
        [0] * len(cols),
        [c[1] for c in cols],
        [c[2] for c in cols],
        n,
    )


def _int_cols(n: int, null_every: int = 7):
    rng = np.random.default_rng(n)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % null_every != 0).astype(np.uint8)
    return k, v, valid


def _both_arms(run):
    """Run ``run()`` with bucketing on, then off; return both results."""
    config.set_flag("BUCKETS", "")
    on = run()
    config.set_flag("BUCKETS", "off")
    off = run()
    config.clear_flag("BUCKETS")
    return on, off


BOUNDARY_SIZES = (1023, 1024, 1025)


class TestBucketEdgeSemantics:
    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_cast_preserves_null_count(self, n):
        k, v, valid = _int_cols(n)

        def run():
            out = _wire(
                {"op": "cast", "column": 1,
                 "type_id": int(dt.TypeId.FLOAT64)},
                [(I64, k.tobytes(), None), (I64, v.tobytes(), valid.tobytes())],
                n,
            )
            return out

        on, off = _both_arms(run)
        assert on == off
        assert on[4] == n
        # null count survives the bucket boundary exactly
        nulls = np.frombuffer(on[3][1], np.uint8)
        assert int((nulls == 0).sum()) == int((valid == 0).sum())

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_groupby_group_counts(self, n):
        k, v, valid = _int_cols(n)

        def run():
            return _wire(
                {"op": "groupby", "by": [0],
                 "aggs": [{"column": 1, "agg": "sum"},
                          {"column": 1, "agg": "count"}]},
                [(I64, k.tobytes(), None), (I64, v.tobytes(), valid.tobytes())],
                n,
            )

        on, off = _both_arms(run)
        assert on == off
        assert on[4] == len(np.unique(k))  # group count exact

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_sort_stability_and_null_placement(self, n):
        k, _, valid = _int_cols(n, null_every=5)
        iota = np.arange(n, dtype=np.int64)  # stability witness

        def run():
            return _wire(
                {"op": "sort_by", "keys": [{"column": 0}]},
                [(I64, k.tobytes(), valid.tobytes()),
                 (I64, iota.tobytes(), None)],
                n,
            )

        on, off = _both_arms(run)
        assert on == off
        assert on[4] == n
        # independent oracle: stable argsort with nulls first (Spark
        # ascending default), ties broken by original position
        key = np.where(valid.astype(bool), k, np.int64(-(1 << 40)))
        order = np.lexsort((iota, key))
        got = np.frombuffer(on[2][1], np.int64)
        np.testing.assert_array_equal(got, iota[order])

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    @pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
    def test_join_cardinality(self, n, how):
        k, v, valid = _int_cols(n)
        rng = np.random.default_rng(n + 1)
        kr = rng.integers(0, 5, 40, dtype=np.int64)  # keys 0-4 of 0-8
        vr = rng.integers(0, 10, 40, dtype=np.int64)

        def run():
            tidl = rb.table_upload_wire(
                [I64, I64], [0, 0], [k.tobytes(), v.tobytes()],
                [valid.tobytes(), None], n,
            )
            tidr = rb.table_upload_wire(
                [I64, I64], [0, 0], [kr.tobytes(), vr.tobytes()],
                [None, None], 40,
            )
            jid = rb.table_op_resident(
                json.dumps({"op": "join", "how": how, "on": [0]}),
                [tidl, tidr],
            )
            out = rb.table_download_wire(jid)
            for t in (tidl, tidr, jid):
                rb.table_free(t)
            return out

        on, off = _both_arms(run)
        assert on == off
        # independent cardinality oracle (null keys never match)
        kv = np.where(valid.astype(bool), k, np.int64(-1))
        matches = {key: int((kr == key).sum()) for key in range(9)}
        per_left = np.array([matches.get(int(x), 0) for x in kv])
        want = {
            "inner": int(per_left.sum()),
            "left": int(np.maximum(per_left, 1).sum()),
            "semi": int((per_left > 0).sum()),
            "anti": int((per_left == 0).sum()),
        }[how]
        assert on[4] == want

    @pytest.mark.parametrize("n", BOUNDARY_SIZES)
    def test_filter_and_distinct(self, n):
        k, v, valid = _int_cols(n)
        mask = (v > 0).astype(np.uint8)

        def run():
            f = _wire(
                {"op": "filter", "mask": 2},
                [(I64, k.tobytes(), None), (I64, v.tobytes(), None),
                 (B8, mask.tobytes(), None)],
                n,
            )
            d = _wire(
                {"op": "distinct", "keys": [0]},
                [(I64, k.tobytes(), None), (I64, v.tobytes(), None)],
                n,
            )
            return f, d

        on, off = _both_arms(run)
        assert on == off
        assert on[0][4] == int(mask.sum())
        assert on[1][4] == len(np.unique(k))

    def test_resident_chain_parity(self):
        n = 1025
        k, v, _ = _int_cols(n)
        mask = (v > 0).astype(np.uint8)

        def run():
            tid = rb.table_upload_wire(
                [I64, I64, B8], [0, 0, 0],
                [k.tobytes(), v.tobytes(), mask.tobytes()],
                [None, None, None], n,
            )
            f = rb.table_op_resident(
                json.dumps({"op": "filter", "mask": 2}), [tid]
            )
            s = rb.table_op_resident(
                json.dumps({"op": "sort_by", "keys": [{"column": 0}]}), [f]
            )
            g = rb.table_op_resident(
                json.dumps({"op": "groupby", "by": [0],
                            "aggs": [{"column": 1, "agg": "sum"}]}), [s]
            )
            rows = [rb.table_num_rows(x) for x in (tid, f, s, g)]
            out = rb.table_download_wire(g)
            for t in (tid, f, s, g):
                rb.table_free(t)
            return rows, out

        on, off = _both_arms(run)
        assert on == off
        assert on[0][0] == n  # resident row counts are LOGICAL counts

    def test_rlike_empty_matching_pattern_excludes_padding(self):
        # ".*" matches the empty string — padding rows (length-0
        # strings) must still be excluded by the occupancy gate
        n = 1000
        strs = [f"row{i}" for i in range(n)]

        def run():
            col = Column.from_strings(strs)
            out = planops.dispatch(
                {"op": "rlike", "column": 0, "pattern": ".*"},
                Table([col], ["s"]),
            )
            return out.logical_row_count

        on, off = _both_arms(run)
        assert on == off == n

    def test_nonbucketable_op_unpads_first(self):
        # slice is not bucketed: a padded resident input must be
        # unpadded before the exact path sees it
        n = 1000
        k, v, _ = _int_cols(n)

        def run():
            tid = rb.table_upload_wire(
                [I64, I64], [0, 0], [k.tobytes(), v.tobytes()],
                [None, None], n,
            )
            s = rb.table_op_resident(
                json.dumps({"op": "slice", "start": 5, "stop": 900}), [tid]
            )
            out = rb.table_download_wire(s)
            for t in (tid, s):
                rb.table_free(t)
            return out

        on, off = _both_arms(run)
        assert on == off
        assert on[4] == 895


# ---------------------------------------------------------------------------
# metrics integration
# ---------------------------------------------------------------------------


class TestBucketMetrics:
    def test_pad_waste_and_cache_counters(self):
        config.set_flag("METRICS", True)
        config.set_flag("BUCKETS", "")
        metrics.reset()
        buckets.cache_clear()
        n = 1000
        k, v, _ = _int_cols(n)
        for _ in range(2):
            _wire(
                {"op": "sort_by", "keys": [{"column": 0}]},
                [(I64, k.tobytes(), None), (I64, v.tobytes(), None)],
                n,
            )
        snap = metrics.snapshot()
        assert snap["counters"]["compile_cache.miss"] == 1
        assert snap["counters"]["compile_cache.hit"] == 1
        assert snap["counters"]["bucket.pad_tables"] >= 2
        # 24 pad rows x 16 B/row, twice
        assert snap["bytes"]["bucket.pad_waste_bytes"] >= 2 * 24 * 16
        assert "bucket.size" in snap["histograms"]
        assert "bucket.pad_rows" in snap["histograms"]
        assert snap["gauges"]["compile_cache.size"]["value"] >= 1

    def test_cache_stats_and_clear(self):
        config.set_flag("BUCKETS", "")
        buckets.cache_clear()
        n = 1000
        k, v, _ = _int_cols(n)
        _wire(
            {"op": "cast", "column": 0, "type_id": int(dt.TypeId.INT32)},
            [(I64, k.tobytes(), None), (I64, v.tobytes(), None)],
            n,
        )
        assert buckets.cache_stats()["size"] >= 1
        buckets.cache_clear()
        assert buckets.cache_stats()["size"] == 0
