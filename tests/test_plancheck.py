"""Plan-time static analyzer (the GpuOverrides tagging-pass analog).

The tentpole contract: ``plancheck`` walks a plan's JSON op list against
an input schema signature BEFORE any upload, compile, or scheduler
admission and produces a tagged report — per-op inferred output
schema/dtypes, a support tier with a human-readable reason, predicted
fusion segmentation, and a static HBM footprint bound. Three invariants
pin it to the runtime so the two can never drift:

* one registry — rules, tiers and the runtime read ``planops.OPS``
  (``tests/test_planops.py`` holds the tier to the path the runtime
  takes, op by op);
* segmentation parity — ``predict_segments`` agrees exactly with
  ``plan.segment_plan`` over a fuzzed corpus, bucket edges included;
* inference parity — an analyzer-clean plan EXECUTES, and its executed
  wire schema matches the inferred one byte-for-byte (type ids and
  scale slots).

The acceptance half: a statically-invalid plan (unknown op,
dtype-mismatched cast, groupby on a missing column) is rejected at
every entry — ``table_plan_wire`` / ``table_stream_wire`` /
``table_plan_resident`` — with a typed error naming op index + reason
and ZERO uploads or compiles, asserted via the ``wire.*`` /
``compile_cache.*`` metrics counters.
"""

import json

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import plancheck as pc
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, metrics

I64 = int(dt.TypeId.INT64)
I32 = int(dt.TypeId.INT32)
F64 = int(dt.TypeId.FLOAT64)
B8 = int(dt.TypeId.BOOL8)
STR = int(dt.TypeId.STRING)

C = pc.ColType
T = dt.TypeId


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


def _string_wire(strings):
    payload = b"".join(s.encode() for s in strings)
    offs = np.zeros(len(strings) + 1, np.int32)
    np.cumsum([len(s.encode()) for s in strings], out=offs[1:])
    return offs.tobytes() + payload


def _cols(n: int):
    """The shared parity table: int64 key, int64 value with nulls, BOOL8
    mask, float64, and a low-cardinality STRING column."""
    rng = np.random.default_rng(n)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % 7 != 0).astype(np.uint8)
    mask = (v > 0).astype(np.uint8)
    f = rng.normal(size=n)
    strs = [f"w{int(x) % 5}ord" for x in k]
    return [
        (I64, 0, k.tobytes(), None),
        (I64, 0, v.tobytes(), valid.tobytes()),
        (B8, 0, mask.tobytes(), None),
        (F64, 0, f.tobytes(), None),
        (STR, 0, _string_wire(strs), None),
    ]


BASE_SCHEMA = [C(T.INT64), C(T.INT64), C(T.BOOL8), C(T.FLOAT64), C(T.STRING)]


def _run_wire(ops, cols, n):
    return rb.table_plan_wire(
        json.dumps(ops),
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n,
    )


def _ids_scales(schema):
    return [(c.id, c.scale, c.child) for c in schema]


# ---------------------------------------------------------------------------
# schema signatures
# ---------------------------------------------------------------------------


class TestSchemaSignatures:
    def test_wire_roundtrip_splits_list_child(self):
        sch = pc.schema_from_wire([I64, int(T.LIST), STR], [0, int(T.INT32), 0])
        assert sch[0] == C(T.INT64)
        assert sch[1] == C(T.LIST, 0, T.INT32)
        assert sch[1].pretty() == "LIST<INT32>"
        assert sch[2].is_string

    def test_schema_of_live_table(self):
        n = 16
        cols = _cols(n)
        tid = rb.table_upload_wire(
            [c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols], n,
        )
        try:
            sch = pc.schema_of_table(rb._resident_get(tid))
        finally:
            rb.table_free(tid)
        assert sch == BASE_SCHEMA

    def test_to_json_is_wire_shaped(self):
        d = C(T.DECIMAL64, -2).to_json()
        assert d == {
            "type_id": int(T.DECIMAL64), "scale": -2, "child": None,
            "pretty": "DECIMAL64(scale=-2)",
        }


# ---------------------------------------------------------------------------
# per-op inference rules
# ---------------------------------------------------------------------------


def _one(ops, schema=BASE_SCHEMA, rows=100, **kw):
    return pc.analyze(ops, schema=schema, rows=rows, **kw)


class TestInferenceRules:
    def test_cast_rewrites_column(self):
        rep = _one([{"op": "cast", "column": 1, "type_id": F64}])
        assert rep["ok"]
        out = rep["ops"][0]["out_schema"]
        assert out[1]["type_id"] == F64
        assert out[0]["type_id"] == I64

    def test_cast_float_to_decimal128_rejected(self):
        rep = _one([{"op": "cast", "column": 3,
                     "type_id": int(T.DECIMAL128)}])
        assert not rep["ok"]
        assert "DECIMAL128" in rep["ops"][0]["reason"]

    def test_cast_string_paths(self):
        ok = _one([{"op": "cast", "column": 4, "type_id": I64}])
        assert ok["ok"]
        ok = _one([{"op": "cast", "column": 0, "type_id": STR}])
        assert ok["ok"] and ok["ops"][0]["out_schema"][0]["type_id"] == STR

    def test_filter_drops_mask_column(self):
        rep = _one([{"op": "filter", "mask": 2}])
        assert rep["ok"]
        out = rep["ops"][0]["out_schema"]
        assert [c["type_id"] for c in out] == [I64, I64, F64, STR]

    def test_filter_non_bool_mask_rejected(self):
        rep = _one([{"op": "filter", "mask": 0}])
        assert not rep["ok"]
        assert "BOOL8" in rep["ops"][0]["reason"]

    def test_filter_zero_column_result_rejected(self):
        rep = _one([{"op": "filter", "mask": 0}], schema=[C(T.BOOL8)])
        assert not rep["ok"]
        assert "zero-column" in rep["ops"][0]["reason"]

    def test_groupby_agg_output_dtypes(self):
        rep = _one([{
            "op": "groupby", "by": [0],
            "aggs": [
                {"column": 1, "agg": "sum"},
                {"column": 1, "agg": "count"},
                {"column": 3, "agg": "sum"},
                {"column": 3, "agg": "mean"},
                {"column": 1, "agg": "min"},
                {"column": 1, "agg": "collect_list"},
            ],
        }])
        assert rep["ok"], rep["ops"][0]["reason"]
        out = rep["ops"][0]["out_schema"]
        # key, then: int sum->I64, count->I64, float sum->F64, mean->F64,
        # min->input, collect_list->LIST<INT64>
        assert [c["type_id"] for c in out[:6]] == [I64, I64, I64, F64, F64,
                                                   I64]
        assert out[6]["type_id"] == int(T.LIST)
        assert out[6]["child"] == I64

    def test_groupby_sum_on_string_rejected(self):
        rep = _one([{"op": "groupby", "by": [0],
                     "aggs": [{"column": 4, "agg": "sum"}]}])
        assert not rep["ok"]
        assert "STRING" in rep["ops"][0]["reason"]

    def test_groupby_collect_float64_rejected(self):
        # FLOAT64 is not a supported LIST child on the wire
        rep = _one([{"op": "groupby", "by": [0],
                     "aggs": [{"column": 3, "agg": "collect_list"}]}])
        assert not rep["ok"]
        assert "collect_list" in rep["ops"][0]["reason"]

    def test_groupby_missing_column_rejected(self):
        rep = _one([{"op": "groupby", "by": [17],
                     "aggs": [{"column": 0, "agg": "sum"}]}])
        assert not rep["ok"]
        assert "out of range" in rep["ops"][0]["reason"]

    def test_join_using_semantics(self):
        right = ([C(T.INT64), C(T.FLOAT64)], 10)
        rep = _one([{"op": "join", "on": [0], "how": "inner"}],
                   rest=[right])
        assert rep["ok"]
        out = rep["ops"][0]["out_schema"]
        # left cols + right cols minus the right join key
        assert [c["type_id"] for c in out] == [I64, I64, B8, F64, STR, F64]
        assert rep["ops"][0]["rows_bound"] == 100 * 10

    def test_semi_join_keeps_left_schema(self):
        rep = _one([{"op": "join", "on": [0], "how": "semi"}],
                   rest=[([C(T.INT64)], 10)])
        assert rep["ok"]
        assert len(rep["ops"][0]["out_schema"]) == len(BASE_SCHEMA)
        assert rep["ops"][0]["rows_bound"] == 100

    def test_outer_join_key_dtype_mismatch_rejected(self):
        rep = _one([{"op": "join", "on": [0], "how": "full"}],
                   rest=[([C(T.FLOAT64)], 10)])
        assert not rep["ok"]
        assert "outer-join key dtypes differ" in rep["ops"][0]["reason"]

    def test_join_without_rest_table_rejected(self):
        rep = _one([{"op": "join", "on": [0]}])
        assert not rep["ok"]
        assert "two input tables" in rep["ops"][0]["reason"]

    def test_concat_dtype_mismatch_rejected(self):
        rep = _one([{"op": "concat"}], rest=[([C(T.FLOAT64)] * 5, 10)])
        assert not rep["ok"]
        assert "dtype mismatch" in rep["ops"][0]["reason"]

    def test_concat_adds_rows(self):
        rep = _one([{"op": "concat"}], rest=[(list(BASE_SCHEMA), 10)])
        assert rep["ok"]
        assert rep["ops"][0]["rows_bound"] == 110

    def test_slice_row_clamping(self):
        rep = _one([{"op": "slice", "start": 10, "stop": 2000}])
        assert rep["ok"]
        assert rep["ops"][0]["rows_bound"] == 90

    def test_negative_slice_rejected(self):
        rep = _one([{"op": "slice", "start": -1}])
        assert not rep["ok"]
        assert "negative" in rep["ops"][0]["reason"]

    def test_explode_requires_list(self):
        rep = _one([{"op": "explode", "column": 0}])
        assert not rep["ok"]
        assert "LIST" in rep["ops"][0]["reason"]
        ok = _one([{"op": "explode", "column": 0}],
                  schema=[C(T.LIST, 0, T.INT32)])
        assert ok["ok"]
        assert ok["ops"][0]["out_schema"][0]["type_id"] == I32
        assert ok["ops"][0]["rows_bound"] is None  # data-dependent

    def test_rlike_requires_string(self):
        rep = _one([{"op": "rlike", "column": 0, "pattern": "x"}])
        assert not rep["ok"]
        assert "STRING" in rep["ops"][0]["reason"]

    def test_partition_schema_passthrough(self):
        rep = _one([{"op": "partition", "kind": "hash", "keys": [0],
                     "num": 8}])
        assert rep["ok"], rep["ops"][0]["reason"]
        out = rep["ops"][0]["out_schema"]
        # pure row redistribution: schema and rows pass through unchanged
        assert [c["type_id"] for c in out] == [I64, I64, B8, F64, STR]
        assert rep["ops"][0]["rows_bound"] == 100
        assert rep["ops"][0]["tier"] == "exact-only"
        assert "exchange boundary" in rep["ops"][0]["reason"]

    def test_partition_bad_kind_rejected(self):
        rep = _one([{"op": "partition", "kind": "zorder", "num": 8}])
        assert not rep["ok"]
        assert "unknown partition kind" in rep["ops"][0]["reason"]

    def test_partition_bad_num_rejected(self):
        for num in (0, -3, True, "8", None):
            rep = _one([{"op": "partition", "kind": "hash", "keys": [0],
                         "num": num}])
            assert not rep["ok"], num
            assert "partition num" in rep["ops"][0]["reason"]

    def test_partition_range_needs_keys(self):
        rep = _one([{"op": "partition", "kind": "range", "num": 8}])
        assert not rep["ok"]
        assert "non-empty 'keys'" in rep["ops"][0]["reason"]

    def test_partition_missing_key_rejected(self):
        rep = _one([{"op": "partition", "kind": "hash", "keys": [17],
                     "num": 8}])
        assert not rep["ok"]
        assert "out of range" in rep["ops"][0]["reason"]

    def test_to_rows_from_rows_roundtrip_schema(self):
        rep = _one([
            {"op": "to_rows"},
            {"op": "from_rows", "type_ids": [I64, I64], "scales": [0, 0]},
        ], schema=[C(T.INT64), C(T.INT64)])
        assert rep["ok"], rep["ops"]
        assert rep["ops"][0]["out_schema"][0]["pretty"] == "LIST<UINT8>"
        assert [c["type_id"] for c in rep["out_schema"]] == [I64, I64]

    def test_to_rows_refuses_strings(self):
        rep = _one([{"op": "to_rows"}])
        assert not rep["ok"]
        assert "fixed-width" in rep["ops"][0]["reason"]

    def test_unknown_op_mirrors_dispatch_message(self):
        rep = _one([{"op": "frobnicate"}])
        assert not rep["ok"]
        assert rep["ops"][0]["reason"] == "unknown table op 'frobnicate'"

    def test_schema_unknowable_downstream_of_reject(self):
        rep = _one([{"op": "frobnicate"},
                    {"op": "cast", "column": 99, "type_id": F64}])
        assert not rep["ok"]
        # the cast after the rejected op cannot be range-checked
        assert rep["ops"][1]["out_schema"] is None

    def test_structural_walk_without_schema(self):
        # schema=None degrades to structural validation: shape errors
        # still reject, dtype questions stay open
        rep = pc.analyze([{"op": "cast", "column": 5, "type_id": F64},
                          {"op": "groupby", "by": []}])
        assert not rep["ok"]
        assert "non-empty 'by' list" in rep["ops"][1]["reason"]
        ok = pc.analyze([{"op": "filter", "mask": 3},
                         {"op": "sort_by", "keys": [{"column": 0}]}])
        assert ok["ok"]

    def test_non_list_plan(self):
        rep = pc.analyze("nope")
        assert not rep["ok"]
        assert "JSON list" in rep["ops"][0]["reason"]

    def test_footprint_bound_is_populated(self):
        rep = _one([{"op": "filter", "mask": 2},
                    {"op": "sort_by", "keys": [{"column": 0}]}])
        assert rep["ok"]
        assert rep["est_hbm_peak_bytes"] is not None
        assert rep["est_hbm_peak_bytes"] > 0
        for seg in rep["segments"]:
            assert seg["est_hbm_bytes"] <= rep["est_hbm_peak_bytes"]

    def test_render_report_tags(self):
        txt = pc.render_report(_one([{"op": "cast", "column": 1,
                                      "type_id": F64},
                                     {"op": "frobnicate"}]))
        assert "REJECTED" in txt
        assert "unknown table op" in txt
        assert "* op[0]" in txt  # fusable glyph
        assert "! op[1]" in txt  # unsupported glyph


# ---------------------------------------------------------------------------
# the tiers of the one op table
# ---------------------------------------------------------------------------


OPS_CORPUS = [
    {"op": "cast", "column": 0, "type_id": F64},
    {"op": "filter", "mask": 2},
    {"op": "rlike", "column": 4, "pattern": "a+"},
    {"op": "distinct"},
    {"op": "distinct", "keys": [0, 1]},
    {"op": "sort_by", "keys": [{"column": 0}]},
    {"op": "slice", "start": 0, "stop": 10},
    {"op": "slice", "start": -1},
    {"op": "slice", "start": "x"},
    {"op": "slice"},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
    {"op": "groupby", "by": [0],
     "aggs": [{"column": 1, "agg": "collect_list"}]},
    {"op": "groupby", "by": [0],
     "aggs": [{"column": 1, "agg": "collect_set"}]},
    {"op": "join", "on": [0]},
    {"op": "join", "on": [0], "how": "full"},
    {"op": "cross_join"},
    {"op": "concat"},
    {"op": "explode", "column": 0},
    {"op": "repeat", "count": 2},
    {"op": "sample", "n": 5},
    {"op": "to_rows"},
    {"op": "from_rows", "type_ids": [I64], "scales": [0]},
    {"op": "frobnicate"},
    {"notanop": 1},
]


class TestRegistryParity:
    def test_every_dispatch_op_gets_a_tier_and_reason(self):
        for name in sorted(planops.OPS):
            tier, reason = pc._tier({"op": name})
            assert tier in ("fusable", "per-op", "exact-only"), name
            assert reason

    def test_tier_reflects_bucketed_join_hows(self):
        assert pc._tier({"op": "join", "how": "inner"})[0] == "per-op"
        assert pc._tier({"op": "join", "how": "full"})[0] == "exact-only"

    def test_collect_groupby_is_exact_only(self):
        op = {"op": "groupby", "by": [0],
              "aggs": [{"column": 1, "agg": "collect_list"}]}
        assert pc._tier(op)[0] == "exact-only"
        plain = {"op": "groupby", "by": [0],
                 "aggs": [{"column": 1, "agg": "sum"}]}
        assert pc._tier(plain)[0] == "fusable"


# ---------------------------------------------------------------------------
# segmentation-parity fuzz
# ---------------------------------------------------------------------------


def _assert_seg_parity(ops):
    pred = pc.predict_segments(ops)
    real = plan_mod.segment_plan(ops)
    assert [k for k, _ in pred] == [k for k, _ in real], ops
    assert [[ops[i] for i in idxs] for _, idxs in pred] == [
        seg for _, seg in real
    ], ops


def _rand_valid_op(rng, schema):
    """One candidate op valid against ``schema`` (fixed-width keys only,
    so every generated plan also EXECUTES on the CPU dispatch plane)."""
    fixed = [i for i, c in enumerate(schema) if c.is_fixed_width]
    bools = [i for i, c in enumerate(schema) if c.is_boolean]
    strs = [i for i, c in enumerate(schema) if c.is_string]
    ints = [i for i, c in enumerate(schema)
            if c.is_integer or c.is_floating]
    choices = [
        {"op": "slice", "start": int(rng.integers(0, 3)),
         "stop": int(rng.integers(8, 64))},
        {"op": "sort_by",
         "keys": [{"column": int(rng.choice(fixed))}]},
        {"op": "distinct", "keys": [int(rng.choice(fixed))]},
    ]
    if ints:
        tgt = int(rng.choice([F64, I64, I32]))
        choices.append(
            {"op": "cast", "column": int(rng.choice(ints)), "type_id": tgt}
        )
        choices.append({
            "op": "groupby", "by": [int(rng.choice(ints))],
            "aggs": [{
                "column": int(rng.choice(ints)),
                "agg": str(rng.choice(["sum", "count", "min", "max"])),
            }],
        })
    if bools and len(schema) > 1:
        choices.append({"op": "filter", "mask": int(rng.choice(bools))})
    if strs:
        choices.append(
            {"op": "rlike", "column": int(rng.choice(strs)),
             "pattern": "w[0-2]o"}
        )
    return choices[int(rng.integers(0, len(choices)))]


def _rand_plan(rng, max_len=6):
    """Random analyzer-clean plan over BASE_SCHEMA (accept-filtered: a
    candidate the analyzer rejects is discarded and redrawn)."""
    ops = []
    schema = list(BASE_SCHEMA)
    for _ in range(int(rng.integers(1, max_len + 1))):
        for _try in range(8):
            cand = _rand_valid_op(rng, schema)
            rep = pc.analyze(ops + [cand], schema=BASE_SCHEMA, rows=100)
            if rep["ok"]:
                ops.append(cand)
                out = rep["ops"][-1]["out_schema"]
                schema = [
                    pc.ColType(
                        dt.TypeId(c["type_id"]), c["scale"],
                        dt.TypeId(c["child"]) if c["child"] is not None
                        else None,
                    )
                    for c in out
                ]
                break
    return ops


class TestSegmentationFuzz:
    def test_200_random_plans_segment_identically(self):
        rng = np.random.default_rng(1234)
        wild = list(OPS_CORPUS)
        for trial in range(200):
            if trial % 2:
                # analyzer-clean plans
                ops = _rand_plan(rng)
            else:
                # unconstrained soup, malformed entries included —
                # segmentation must still agree op-for-op
                k = int(rng.integers(1, 8))
                ops = [wild[int(i)] for i in rng.integers(0, len(wild), k)]
            _assert_seg_parity(ops)

    def test_predicted_segments_match_report(self):
        ops = [
            {"op": "cast", "column": 1, "type_id": F64},
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "join", "on": [0]},
        ]
        rep = pc.analyze(ops, schema=BASE_SCHEMA, rows=10,
                         rest=[([C(T.INT64)], 5)])
        assert [(s["kind"], s["ops"]) for s in rep["segments"]] == [
            ("fused", [0, 1]), ("exact", [2]),
        ]


_GROUP = {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]}
_MASK = {"op": "filter", "mask": 2}
_JOIN = {"op": "join", "on": [0]}


@pytest.mark.parametrize("ops,static,riding", [
    ([_MASK, _JOIN, _GROUP],
     [("exact", [0]), ("exact", [1]), ("exact", [2])],
     [("fused", [0, 1, 2])]),
    ([_JOIN, _GROUP],
     [("exact", [0]), ("exact", [1])], [("fused", [0, 1])]),
    ([_MASK, _JOIN, {"op": "sort_by", "keys": [{"column": 0}]}],
     [("exact", [0]), ("exact", [1]), ("exact", [2])], None),
    ([_MASK, dict(_JOIN, how="full"), _GROUP],
     [("exact", [0]), ("exact", [1]), ("exact", [2])], None),
], ids=["filter_join_groupby", "join_groupby", "sort_tail", "full_join"])
def test_a_join_is_a_boundary_unless_its_build_side_is_known(
        ops, static, riding):
    """Without a build side (every static caller) the prediction is what
    it was; told that an inner join selects (``plan._run_segments`` reads
    that from the data, PR 38), the one segmenter lets it ride the run
    that a groupby closes, and no other."""
    assert pc.predict_segments(ops) == static
    _assert_seg_parity(ops)
    rep = pc.analyze(ops, schema=BASE_SCHEMA, rows=10,
                     rest=[([C(T.INT64)], 5)])
    assert [(s["kind"], s["ops"]) for s in rep["segments"]] == static
    inner = lambda i, op: op.get("how", "inner") == "inner"  # noqa: E731
    assert pc.predict_segments(ops, inner) == (riding or static)


# ---------------------------------------------------------------------------
# inference-vs-execution fuzz: analyzer-clean plans run, and the wire
# result's (type_ids, scales) match the inferred schema byte-for-byte
# ---------------------------------------------------------------------------


def _assert_executes_as_inferred(ops, n):
    cols = _cols(n)
    rep = pc.analyze(ops, schema=BASE_SCHEMA, rows=n)
    assert rep["ok"], (ops, [e["reason"] for e in rep["ops"]])
    _assert_seg_parity(ops)
    type_ids, scales, _datas, _valids, out_rows = _run_wire(ops, cols, n)
    inferred = rep["out_schema"]
    assert len(inferred) == len(type_ids), ops
    for got_tid, got_scale, want in zip(type_ids, scales, inferred):
        assert int(got_tid) == want["type_id"], ops
        # LIST wire convention: scale slot carries the child type id
        want_scale = (
            want["child"] if want["type_id"] == int(T.LIST)
            else want["scale"]
        )
        assert int(got_scale) == want_scale, ops
    if rep["rows_out_bound"] is not None:
        assert out_rows <= rep["rows_out_bound"], ops


class TestExecutionParityFuzz:
    def test_random_clean_plans_execute_with_inferred_schema(self):
        rng = np.random.default_rng(77)
        config.set_flag("BUCKETS", "off")  # eager exact: cheap fuzz path
        for _ in range(20):
            ops = _rand_plan(rng, max_len=4)
            _assert_executes_as_inferred(ops, n=48)

    @pytest.mark.parametrize("n", (1023, 1024, 1025))
    def test_bucket_edges_with_buckets_on(self, n):
        # the same chain test_plan.py pins byte-identical across paths,
        # now cross-checked against the static inference with the
        # bucketed plan path live at the 1024 bucket edges
        config.set_flag("BUCKETS", "")
        ops = [
            {"op": "filter", "mask": 2},
            {"op": "cast", "column": 1, "type_id": F64},
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "sum"},
                      {"column": 1, "agg": "count"}]},
        ]
        # BASE_SCHEMA here is the 5-col table; the test_plan chain uses
        # its 4-col cousin — drop the F64 column to match its shape
        cols = _cols(n)
        del cols[3]
        schema = [c for i, c in enumerate(BASE_SCHEMA) if i != 3]
        rep = pc.analyze(ops, schema=schema, rows=n)
        assert rep["ok"]
        _assert_seg_parity(ops)
        got = rb.table_plan_wire(
            json.dumps(ops),
            [c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols], n,
        )
        type_ids, scales, _d, _v, out_rows = got
        assert [int(t) for t in type_ids] == [
            c["type_id"] for c in rep["out_schema"]
        ]
        assert [int(s) for s in scales] == [
            c["scale"] for c in rep["out_schema"]
        ]
        assert out_rows <= rep["rows_out_bound"]


# ---------------------------------------------------------------------------
# acceptance: invalid plans die at every entry with ZERO device work
# ---------------------------------------------------------------------------


INVALID_PLANS = {
    "unknown_op": (
        [{"op": "frobnicate"}], "unknown table op 'frobnicate'"),
    "dtype_mismatched_cast": (
        [{"op": "cast", "column": 3, "type_id": int(T.DECIMAL128)}],
        "DECIMAL128"),
    "groupby_missing_column": (
        [{"op": "groupby", "by": [17],
          "aggs": [{"column": 0, "agg": "sum"}]}],
        "out of range"),
}


def _work_counters(snap=None):
    c = (snap or metrics.snapshot())["counters"]
    return {
        k: v for k, v in c.items()
        if k.startswith(("wire.", "compile_cache.", "serving.", "resident."))
    }


class TestRejectionZeroWork:
    @pytest.mark.parametrize("case", sorted(INVALID_PLANS))
    def test_wire_entry_rejects_before_any_upload(self, case):
        ops, needle = INVALID_PLANS[case]
        n = 32
        cols = _cols(n)
        config.set_flag("METRICS", True)
        metrics.reset()
        with pytest.raises(pc.PlanCheckError) as exc:
            _run_wire(ops, cols, n)
        assert "plancheck: op[0]" in str(exc.value)
        assert needle in str(exc.value)
        assert exc.value.index == 0
        assert exc.value.plan_report["ok"] is False
        assert _work_counters() == {}  # no upload, no compile

    @pytest.mark.parametrize("case", sorted(INVALID_PLANS))
    def test_stream_entry_rejects_before_any_upload(self, case):
        ops, needle = INVALID_PLANS[case]
        n = 32
        cols = _cols(n)
        batch = (
            [c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols], n,
        )
        config.set_flag("METRICS", True)
        metrics.reset()
        with pytest.raises(pc.PlanCheckError, match="plancheck: op\\[0\\]"):
            rb.table_stream_wire(json.dumps(ops), [batch, batch])
        assert _work_counters() == {}

    @pytest.mark.parametrize("case", sorted(INVALID_PLANS))
    def test_resident_entry_rejects_before_any_dispatch(self, case):
        ops, needle = INVALID_PLANS[case]
        n = 32
        cols = _cols(n)
        tid = rb.table_upload_wire(
            [c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols], n,
        )
        try:
            config.set_flag("METRICS", True)
            metrics.reset()
            with pytest.raises(pc.PlanCheckError) as exc:
                rb.table_plan_resident(json.dumps(ops), [tid])
            assert needle in str(exc.value)
            assert _work_counters() == {}
        finally:
            config.clear_flag("METRICS")
            rb.table_free(tid)

    def test_legacy_error_texts_still_reach_callers(self):
        # pre-existing callers match these substrings THROUGH the wire
        # entries; the static reject must carry the same text
        n = 8
        cols = _cols(n)
        with pytest.raises(ValueError, match="unknown table op"):
            _run_wire([{"op": "nope"}], cols, n)
        with pytest.raises(TypeError, match="JSON list"):
            _run_wire({"op": "nope"}, cols, n)
        with pytest.raises(ValueError, match="op objects"):
            _run_wire(["nope"], cols, n)

    def test_valid_plan_passes_through_unchanged(self):
        n = 64
        cols = _cols(n)
        config.set_flag("BUCKETS", "off")
        out = _run_wire(
            [{"op": "filter", "mask": 2},
             {"op": "sort_by", "keys": [{"column": 0}]}], cols, n,
        )
        assert out[4] <= n
        assert len(out[0]) == 4  # mask dropped

    def test_check_plan_returns_report_when_clean(self):
        rep = pc.check_plan(
            [{"op": "cast", "column": 0, "type_id": F64}],
            schema=BASE_SCHEMA, rows=10,
        )
        assert rep["ok"]
        assert rep["out_schema"][0]["type_id"] == F64
