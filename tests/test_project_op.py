"""The ``project`` plan op: expression trees evaluated in the daemon.

What is held here: every expression tree over every fixed-width dtype,
with and without nulls, gives the values of a plain numpy reference
(``perfbench/plugins/refop_project.py``, which imports nothing of the
program) on the exact path, through the bucketed per-op runner and
inside a fused segment; ``plancheck`` infers the schema the runtime
returns; a decimal ``mul`` keeps scale s1 + s2 inside ``project`` while
``binary_op``'s own default stays what it was; ``project -> filter ->
project -> groupby`` is ONE fused segment; the row-local half-batch
split and a 4-device mesh chain give the same bytes; and TPC-H Q1 whole,
on seeded ``lineitem`` data, equals the benchmark's reference on every
path up to ``serving.Client``, with the float32 control refused.
"""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import compare, datagen, reference
from perfbench.plugins import refop_project
from perfbench.wirefmt import NP_DTYPES, TYPE_IDS, Col, unwire, wire
from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import parallel, plancheck, planops, serving
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.ops import binaryop
from spark_rapids_jni_tpu.ops import project as project_mod
from spark_rapids_jni_tpu.utils import config, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
I8, I32, I64 = TYPE_IDS["INT8"], TYPE_IDS["INT32"], TYPE_IDS["INT64"]
F64, B8 = TYPE_IDS["FLOAT64"], TYPE_IDS["BOOL8"]
D32, D64 = TYPE_IDS["DECIMAL32"], TYPE_IDS["DECIMAL64"]
ROWS = 700


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


# ---------------------------------------------------------------------------
# the input table: one column a dtype, nulls on request
# ---------------------------------------------------------------------------

# index -> (type, scale, values from rng)
COLUMNS = [
    ("INT8", 0, lambda r, n: r.integers(-100, 100, n)),           # 0
    ("INT32", 0, lambda r, n: r.integers(-30000, 30000, n)),      # 1
    ("INT64", 0, lambda r, n: r.integers(-10**9, 10**9, n)),      # 2
    ("FLOAT64", 0, lambda r, n: r.standard_normal(n) * 100),      # 3
    ("BOOL8", 0, lambda r, n: r.integers(0, 2, n)),               # 4
    ("DECIMAL32", -2, lambda r, n: r.integers(-20000, 20000, n)),  # 5
    ("DECIMAL64", -3, lambda r, n: r.integers(-10**7, 10**7, n)),  # 6
    ("BOOL8", 0, lambda r, n: r.integers(0, 2, n)),               # 7
    ("INT64", 0, lambda r, n: r.integers(-3, 4, n)),              # 8 (zeros)
    ("DECIMAL64", -2, lambda r, n: r.integers(0, 11, n)),         # 9
    ("FLOAT64", 0, lambda r, n: r.integers(-2, 3, n) * 0.5),      # 10 (zeros)
]


def make_table(nulls: bool, rows: int = ROWS, seed: int = 7):
    rng = np.random.default_rng(seed)
    out = []
    for name, scale, gen in COLUMNS:
        vals = np.asarray(gen(rng, rows)).astype(NP_DTYPES[name])
        valid = rng.random(rows) >= 0.2 if nulls else None
        out.append(Col(name, scale, vals, valid))
    return out


def col(i):
    return {"col": i}


def lit(v, type_id, scale=0):
    return {"lit": v, "type_id": type_id, "scale": scale}


def binary(name, left, right, **named):
    return dict({"binary": name, "left": left, "right": right}, **named)


def unary(name, arg):
    return {"unary": name, "arg": arg}


def cast(e, type_id, scale=0):
    return {"cast": e, "type_id": type_id, "scale": scale}


ONE = lit(100, D64, -2)
DISC_PRICE = binary("mul", col(6), binary("sub", ONE, col(9)))

CASES = {
    "add_int8_int32": binary("add", col(0), col(1)),
    "sub_int64_int8": binary("sub", col(2), col(0)),
    "mul_int32_int64": binary("mul", col(1), col(2)),
    "div_int64_by_zeroes": binary("div", col(2), col(8)),
    "add_named_int64": binary("add", col(0), col(1), type_id=I64),
    "add_float64": binary("add", col(3), col(3)),
    "mul_float64_cast_int": binary("mul", col(3), cast(col(1), F64)),
    "div_float64_by_zeroes": binary("div", col(3), col(10)),
    "lt_int32_int64": binary("lt", col(1), col(2)),
    "ge_decimals_mixed_scale": binary("ge", col(5), col(6)),
    "eq_decimal_int": binary("eq", col(9), col(8)),
    "ne_float64": binary("ne", col(3), col(10)),
    "le_literal": binary("le", col(1), lit(10471, I32)),
    "gt_literal_decimal": binary("gt", col(6), lit(1500, D64, -2)),
    "and_three_valued": binary("and", col(4), col(7)),
    "or_three_valued": binary("or", col(4), col(7)),
    "not": unary("not", col(4)),
    "is_null": unary("is_null", col(2)),
    "is_not_null": unary("is_not_null", col(6)),
    "and_of_comparisons": binary(
        "and", binary("lt", col(0), col(1)),
        unary("not", binary("eq", col(8), lit(0, I64)))),
    "decimal_mul_s1_plus_s2": binary("mul", col(5), col(6)),
    "decimal_mul_named_scale": binary(
        "mul", col(5), col(6), type_id=D64, scale=-3),
    "decimal_mul_named_finer": binary(
        "mul", col(5), col(9), type_id=D64, scale=-6),
    "decimal_add_mixed_scale": binary("add", col(5), col(6)),
    "decimal_sub_named_coarser": binary(
        "sub", col(6), col(5), type_id=D64, scale=-1),
    "decimal_times_int": binary("mul", col(6), col(0)),
    "decimal_div_by_zeroes": binary("div", col(6), col(9)),
    "decimal32_stays_decimal32": binary("mul", col(5), lit(3, D32, 0)),
    "one_minus_discount": binary("sub", ONE, col(9)),
    "disc_price": DISC_PRICE,
    "charge": binary("mul", DISC_PRICE, binary("add", ONE, col(9))),
    "neg_decimal": unary("neg", col(6)),
    "abs_int32": unary("abs", col(1)),
    "cast_decimal_rescale": cast(col(6), D64, -1),
    "cast_int_to_decimal": cast(col(1), D64, -2),
    "cast_decimal_to_int": cast(col(6), I64),
    "literal_alone": lit(-7, I8),
    "null_literal_plus_column": binary("add", col(2), lit(None, I64)),
    "bool_literal": binary("or", col(4), lit(True, B8)),
    "float_literal": binary("mul", col(3), lit(0.25, F64)),
}


def run_wire(ops, table):
    return unwire(rb.table_plan_wire(json.dumps(ops), *wire(table)))


def same(got, want) -> bool:
    return compare.compare(got, want, {}, 0.0)["mismatched"] == 0


def schema_of(table):
    return plancheck.schema_from_wire(*wire(table)[:2])


def wire_schema(table):
    return [(TYPE_IDS[c.type], c.scale) for c in table]


# ---------------------------------------------------------------------------
# expressions x nulls x path, against the numpy reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["exact", "per_op", "fused"])
@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_expression_matches_the_numpy_reference(case, nulls, path):
    table = make_table(nulls)
    op = {"op": "project", "exprs": [CASES[case], col(2)]}
    want = refop_project.apply(op, [table], False)
    ops = [op]
    if path == "exact":
        config.set_flag("BUCKETS", "off")
    elif path == "fused":
        # a second project behind it: a run of two fusable ops
        ops = [op, {"op": "project", "exprs": [col(0), col(1)]}]
        assert [k for k, _ in plan_mod.segment_plan(ops)] == ["fused"]
    got = run_wire(ops, table)
    assert same(got, want), (case, [c.type for c in got])
    assert same([got[1]], [table[2]])  # the pass-through column


@pytest.mark.parametrize("case", sorted(CASES))
def test_plancheck_infers_the_runtime_schema(case):
    table = make_table(True, rows=64)
    ops = [{"op": "project", "exprs": [CASES[case], col(6), col(4)]}]
    report = plancheck.check_plan(ops, schema=schema_of(table), rows=64)
    inferred = [(c["type_id"], c["scale"]) for c in report["out_schema"]]
    assert inferred == wire_schema(run_wire(ops, table))
    assert report["ops"][0]["tier"] == "fusable"
    assert report["rows_out_bound"] == 64


# ---------------------------------------------------------------------------
# the decimal product's scale
# ---------------------------------------------------------------------------


def _decimal_columns():
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.column import Column

    price = Column(jnp.asarray([12345, 99999, -505], jnp.int64),
                   dt.decimal64(-2), None)
    factor = Column(jnp.asarray([95, 100, 91], jnp.int64),
                    dt.decimal64(-2), None)
    return price, factor


def test_binary_op_default_scale_is_unchanged():
    price, factor = _decimal_columns()
    out = binaryop.mul(price, factor)
    assert out.dtype == dt.decimal64(-2)  # cut to the finer input scale
    assert np.asarray(out.data).tolist() == [11727, 99999, -459]


@pytest.mark.parametrize("scale, want", [
    (-4, [1172775, 9999900, -45955]),     # Spark: s1 + s2, exact
    (-2, [11727, 99999, -459]),           # named coarser: toward zero
    (-6, [117277500, 999990000, -4595500]),
])
def test_binary_op_takes_its_output_type(scale, want):
    price, factor = _decimal_columns()
    out = binaryop.binary_op("mul", price, factor, dt.decimal64(scale))
    assert out.dtype == dt.decimal64(scale)
    assert np.asarray(out.data).tolist() == want


def test_result_dtype_rules():
    d2, d3, i32 = dt.decimal64(-2), dt.decimal32(-3), dt.INT32
    assert binaryop.result_dtype("mul", d2, d3) == dt.decimal64(-3)
    assert binaryop.result_dtype("mul", d2, d3, spark=True) == dt.decimal64(-5)
    assert binaryop.result_dtype("add", d2, d3, spark=True) == dt.decimal64(-3)
    assert binaryop.result_dtype("mul", d3, i32, spark=True) == dt.decimal32(-3)
    assert binaryop.result_dtype("le", d2, d3) == dt.BOOL8
    assert binaryop.result_dtype("add", i32, dt.INT64) == dt.INT64
    with pytest.raises(TypeError):
        binaryop.result_dtype("mul", d2, dt.FLOAT64)
    with pytest.raises(TypeError):
        binaryop.result_dtype("le", d2, d3, dt.INT32)
    with pytest.raises(TypeError):
        binaryop.result_dtype("add", i32, i32, d2)
    with pytest.raises(TypeError):
        binaryop.result_dtype("mul", d2, d2, dt.decimal128(-4))
    with pytest.raises(ValueError):
        binaryop.result_dtype("frobnicate", i32, i32)


# ---------------------------------------------------------------------------
# what plancheck refuses, the runtime refuses with the same words
# ---------------------------------------------------------------------------

REFUSED = {
    "no_exprs": {"op": "project", "exprs": []},
    "not_a_node": {"op": "project", "exprs": [{"column": 0}]},
    "two_kinds": {"op": "project", "exprs": [{"col": 0, "lit": 1}]},
    "column_out_of_range": {"op": "project", "exprs": [col(99)]},
    "unknown_binary": {"op": "project",
                       "exprs": [binary("frobnicate", col(0), col(1))]},
    "unknown_unary": {"op": "project", "exprs": [unary("frobnicate", col(0))]},
    "decimal_float_arithmetic": {"op": "project",
                                 "exprs": [binary("mul", col(6), col(3))]},
    "decimal_float_comparison": {"op": "project",
                                 "exprs": [binary("lt", col(6), col(3))]},
    "and_of_integers": {"op": "project",
                        "exprs": [binary("and", col(0), col(1))]},
    "not_of_integer": {"op": "project", "exprs": [unary("not", col(0))]},
    "literal_does_not_fit": {"op": "project", "exprs": [lit(300, I8)]},
    "literal_without_type": {"op": "project", "exprs": [{"lit": 3}]},
    "predicate_named_int": {"op": "project",
                            "exprs": [binary("le", col(0), col(1), type_id=I32)]},
    "decimal_named_plain": {"op": "project",
                            "exprs": [binary("mul", col(5), col(6), type_id=I64)]},
    "scale_on_plain_type": {"op": "project",
                            "exprs": [cast(col(1), I64, -2)]},
    "missing_operand": {"op": "project",
                        "exprs": [{"binary": "add", "left": col(0)}]},
    "log_of_decimal": {"op": "project", "exprs": [unary("log", col(6))]},
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refused_statically_and_at_dispatch(case):
    table = make_table(False, rows=16)
    op = REFUSED[case]
    with pytest.raises(plancheck.PlanCheckError) as static:
        plancheck.check_plan([op], schema=schema_of(table), rows=16)
    assert static.value.index == 0 and static.value.op_name == "project"
    device = rb._table_from_wire(*wire(table), None)
    with pytest.raises(project_mod.ExprError) as runtime:
        planops.OPS["project"].exact(op, device, [])
    assert str(runtime.value) == static.value.reason


def test_string_columns_pass_through_a_column_reference_only():
    strings = ["a", "bb", "", "dddd"]
    payload = b"".join(s.encode() for s in strings)
    offs = np.zeros(5, np.int32)
    np.cumsum([len(s) for s in strings], out=offs[1:])
    batch = ([int(dt.TypeId.STRING), I64], [0, 0],
             [offs.tobytes() + payload, np.arange(4, dtype=np.int64).tobytes()],
             [None, None], 4)
    ops = [{"op": "project", "exprs": [
        col(0), binary("add", col(1), lit(1, I64))]}]
    tids, _, datas, _, n = rb.table_plan_wire(json.dumps(ops), *batch)
    assert list(tids) == [int(dt.TypeId.STRING), I64] and n == 4
    assert bytes(datas[0]) == offs.tobytes() + payload
    assert np.frombuffer(datas[1], np.int64).tolist() == [1, 2, 3, 4]
    schema = plancheck.schema_from_wire(batch[0], batch[1])
    with pytest.raises(plancheck.PlanCheckError):
        plancheck.check_plan(
            [{"op": "project", "exprs": [binary("eq", col(0), col(0))]}],
            schema=schema, rows=4)


def test_structure_is_checked_without_a_schema():
    ok = [{"op": "project", "exprs": [binary("add", col(0), col(1))]}]
    assert plancheck.check_plan(ok)["ok"]
    with pytest.raises(plancheck.PlanCheckError):
        plancheck.check_plan([{"op": "project", "exprs": [{"nope": 1}]}])


# ---------------------------------------------------------------------------
# registries, segmentation, the counter
# ---------------------------------------------------------------------------


def test_project_lives_in_every_registry():
    """There is one registry: the entry and what it says of the op."""
    spec = planops.OPS["project"]
    assert spec.fusable is True and spec.bucketable is True
    assert spec.row_local and not spec.exchange and not spec.counts
    assert spec.program == "srt_bucketed_project" and spec.runner is None
    op = {"op": "project", "exprs": [col(0)]}
    assert planops.op_fusable(op) and planops.op_bucketable(op)
    assert plancheck.analyze([op, op])["segments"][0]["kind"] == "fused"


def q1_plan():
    with open(os.path.join(ROOT, "perfbench", "traffic", "q1-resident.json")) as f:
        (step,) = [s for s in json.load(f)["request"] if s["do"] == "plan"]
    return step["plan"]


def lineitem(rows: int, seed: int):
    with open(os.path.join(
            ROOT, "perfbench", "configs", "tpch-lineitem-8m.json")) as f:
        spec = json.load(f)["tables"]["lineitem"]
    return datagen.make_table(spec, rows, np.random.default_rng([seed, 0, 0]))


def test_q1_is_one_fused_segment_and_a_sort():
    ops = q1_plan()
    segs = plan_mod.segment_plan(ops)
    assert [(k, plan_mod.segment_sig(s)) for k, s in segs] == [
        ("fused", "project__filter__project__groupby"), ("exact", "sort_by")]
    assert [(k, len(i)) for k, i in plancheck.predict_segments(ops)] == [
        ("fused", 4), ("exact", 1)]
    report = plancheck.check_plan(
        ops, schema=schema_of(lineitem(8, 1)), rows=8)
    assert [(c["type_id"], c["scale"]) for c in report["out_schema"]] == [
        (I8, 0), (I8, 0), (D64, -2), (D64, -2), (D64, -4), (D64, -6),
        (D64, -2), (I64, 0)]


def _counters(*names):
    return metrics.counter_values(list(names))


@pytest.mark.parametrize("path", ["exact", "per_op", "fused", "served"])
def test_q1_equals_the_benchmark_reference(path):
    config.set_flag("METRICS", True)
    table = lineitem(20000, 2147483659)
    ops = q1_plan()
    want = reference.run_plan(ops, [table])
    assert len(want[0].values) == 4  # (A,F) (N,F) (N,O) (R,F)
    watched = ("project.calls", "plan.fused_segments", "plan.fallbacks",
               "bucket.fallback_errors", "kernel.fallbacks")
    before = _counters(*watched)
    if path == "served":
        with serving.Server(workers=2).start() as srv:
            with serving.Client(srv.port, timeout=600.0) as c:
                tid = c.upload(wire(table))
                out = c.plan(ops, [tid])
                got = unwire(c.download(out))
                c.free(out)
                c.free(tid)
    elif path == "per_op":
        batch = wire(table)
        for op in ops:
            batch = rb.table_op_wire(json.dumps(op), *batch)
        got = unwire(batch)
    else:
        if path == "exact":
            config.set_flag("BUCKETS", "off")
        got = run_wire(ops, table)
    moved = {k: v - before[k] for k, v in _counters(*watched).items()}
    assert same(got, want), [c.values.tolist() for c in got]
    assert [c.scale for c in got] == [0, 0, -2, -2, -4, -6, -2, 0]
    assert moved["project.calls"] == 2
    assert moved["plan.fused_segments"] == (path in ("fused", "served"))
    assert moved["plan.fallbacks"] == moved["bucket.fallback_errors"] == 0
    assert moved["kernel.fallbacks"] == 0


def test_q1_float32_control_is_refused():
    table = lineitem(20000, 5)
    ops = q1_plan()
    want = reference.run_plan(ops, [table])
    low = reference.run_plan(ops, [table], lowprec=True)
    r = compare.compare(low, want, {"order": "served"}, 0.0)
    # the two sums of products, and nothing else
    assert 0 < r["mismatched"] <= 8
    assert same(low[:4], want[:4]) and same(low[6:], want[6:])


def test_the_literal_is_part_of_the_executable():
    table = lineitem(3000, 11)
    ops = q1_plan()
    earlier = json.loads(json.dumps(ops).replace("10471", "9000"))
    a, b = run_wire(ops, table), run_wire(earlier, table)
    assert same(b, reference.run_plan(earlier, [table]))
    assert int(a[7].values.sum()) > int(b[7].values.sum())


# ---------------------------------------------------------------------------
# row-local: the half-batch split and the mesh chain
# ---------------------------------------------------------------------------

ROW_LOCAL_CHAIN = q1_plan()[:3]  # project -> filter -> project


def _bytes_of(t):
    n = int(t.logical_row_count)
    return [(str(c.dtype), np.asarray(c.data)[:n].tobytes(),
             None if c.validity is None
             else np.asarray(c.validity)[:n].tobytes()) for c in t.columns]


def test_half_batch_split_gives_the_same_bytes():
    config.set_flag("METRICS", True)
    device = rb._table_from_wire(*wire(lineitem(3001, 3)), None)
    whole = plan_mod._run_fused(ROW_LOCAL_CHAIN, device)
    before = _counters("plan.chunked_segments")["plan.chunked_segments"]
    halves = plan_mod._run_chunked(ROW_LOCAL_CHAIN, device)
    assert _bytes_of(halves) == _bytes_of(whole)
    assert _counters("plan.chunked_segments")[
        "plan.chunked_segments"] == before + 1


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_mesh4_chain_gives_the_same_bytes():
    config.set_flag("METRICS", True)
    device = rb._table_from_wire(*wire(lineitem(4099, 4)), None)
    want = _bytes_of(plan_mod.run_plan(ROW_LOCAL_CHAIN, device))
    before = _counters("plan.mesh_segments", "project.calls")
    runner = parallel.MeshRunner(4)
    got = plan_mod.run_plan(ROW_LOCAL_CHAIN, device, mesh_runner=runner)
    after = _counters("plan.mesh_segments", "project.calls")
    assert _bytes_of(got) == want
    assert after["plan.mesh_segments"] == before["plan.mesh_segments"] + 1
    assert after["project.calls"] == before["project.calls"] + 2
    assert runner.to_doc()["degraded"] is False
