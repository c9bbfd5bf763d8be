"""A filter that feeds a groupby inside one fused segment keeps its rows.

Inside a fused segment occupancy is a mask that flows from op to op
(``plan._run_segment_traced``): where every op from a ``filter`` /
``rlike`` to the segment's end is row-local and the tail is a
``groupby``, the selecting op ANDs its selection into the mask and the
groupby's sort, which already puts unoccupied rows last, does the rest.
Held here, a case each so each counts:

* the fused result of such a segment equals the per-op result and the
  exact path byte for byte on the wire, FLOAT64 sums included, over
  null keys, null values, a padded tail, zero rows kept and all kept;
* its lowered text holds no compaction, and the segments that need the
  prefix (``filter -> sort_by``, ``filter -> slice -> groupby``, a lone
  ``filter``) still hold one;
* ``filter.deferred`` / ``filter.compacted`` tick once a launch for
  each selecting op, the way its occupancy went.
"""

import re

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import bucketed, dtype as dt, parallel
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, metrics

from test_plan import _run_per_op_wire, _run_plan_wire, _string_wire

I8, I32, I64 = (int(dt.TypeId.INT8), int(dt.TypeId.INT32),
                int(dt.TypeId.INT64))
F64, B8 = int(dt.TypeId.FLOAT64), int(dt.TypeId.BOOL8)
STR, D64 = int(dt.TypeId.STRING), int(dt.TypeId.DECIMAL64)


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


# ---------------------------------------------------------------------------
# the tables: how many rows a selection keeps is the case's
# ---------------------------------------------------------------------------

KEEPS = ("some", "none", "all")
SIZES = (1500, 1024)  # a padded tail, and a whole bucket


def _bits(rng, n, keep):
    if keep == "some":
        return (rng.random(n) < 0.7).astype(np.uint8)
    return np.full(n, keep == "all", np.uint8)


def _valid(rng, n, share=0.1):
    return (rng.random(n) >= share).astype(np.uint8).tobytes()


def _cols(n, keep):
    """INT64 key with nulls, INT64 value with nulls, FLOAT64 value with
    nulls, a BOOL8 mask, a BOOL8 mask with nulls (a null selects
    nothing), a STRING."""
    rng = np.random.default_rng([n, KEEPS.index(keep)])
    k = rng.integers(0, 40, n, dtype=np.int64)
    v = rng.integers(-10**12, 10**12, n, dtype=np.int64)
    f = rng.standard_normal(n) * 10.0 ** rng.integers(-3, 9, n)
    strs = [f"w{int(x) % 5}ord" for x in k]
    return [
        (I64, 0, k.tobytes(), _valid(rng, n)),
        (I64, 0, v.tobytes(), _valid(rng, n)),
        (F64, 0, f.tobytes(), _valid(rng, n)),
        (B8, 0, _bits(rng, n, keep).tobytes(), None),
        (B8, 0, _bits(rng, n, keep).tobytes(),
         None if keep == "all" else _valid(rng, n, 0.2)),
        (STR, 0, _string_wire(strs), None),
    ]


def _lineitem_cols(n):
    """Q1's columns at small size: two INT8 keys, three DECIMAL64(-2)
    values (one with nulls), an INT32 date in 0..99."""
    rng = np.random.default_rng(n)
    dec = lambda lo, hi: rng.integers(lo, hi, n, dtype=np.int64).tobytes()
    return [
        (I8, 0, rng.integers(65, 68, n).astype(np.int8).tobytes(), None),
        (I8, 0, rng.integers(70, 72, n).astype(np.int8).tobytes(), None),
        (D64, -2, dec(100, 5100), None),
        (D64, -2, dec(90_000, 10_000_000), _valid(rng, n)),
        (D64, -2, dec(0, 11), None),
        (I32, 0, rng.integers(0, 100, n).astype(np.int32).tobytes(), None),
    ]


def _c(i):
    return {"col": i}


_AGGS = [{"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"},
         {"column": 2, "agg": "sum"}, {"column": 2, "agg": "min"}]
GROUP = {"op": "groupby", "by": [0], "aggs": _AGGS}
FILTER = {"op": "filter", "mask": 3}
_RLIKE = {"some": "w[0-2]o", "none": "zzz", "all": ".*"}
_SHIPDATE = {"some": 70, "none": -1, "all": 1000}


def _q1_shape(keep):
    price, disc = _c(3), _c(4)
    one = {"lit": 100, "type_id": D64, "scale": -2}
    return [
        {"op": "project", "exprs": [_c(0), _c(1), _c(2), price, disc, {
            "binary": "le", "left": _c(5),
            "right": {"lit": _SHIPDATE[keep], "type_id": I32}}]},
        {"op": "filter", "mask": 5},
        {"op": "project", "exprs": [_c(0), _c(1), _c(2), price, {
            "binary": "mul", "left": price,
            "right": {"binary": "sub", "left": one, "right": disc}}]},
        {"op": "groupby", "by": [0, 1], "aggs": [
            {"column": 2, "agg": "sum"}, {"column": 3, "agg": "sum"},
            {"column": 4, "agg": "sum"}, {"column": 2, "agg": "count"}]},
    ]


# chain -> keep -> ops. Column indices track the per-op semantics (a
# filter drops its mask column).
DEFERRED = {
    "filter_groupby": lambda keep: [FILTER, GROUP],
    "q1_shape": _q1_shape,
    "filter_filter_groupby": lambda keep: [FILTER, FILTER, GROUP],
    "rlike_groupby": lambda keep: [
        {"op": "rlike", "column": 5, "pattern": _RLIKE[keep]}, GROUP],
    "filter_cast_groupby": lambda keep: [
        FILTER, {"op": "cast", "column": 1, "type_id": F64}, GROUP],
}
COMPACTED = {
    "filter_sort": [FILTER, {"op": "sort_by", "keys": [{"column": 0}]}],
    "filter_slice_groupby": [
        FILTER, {"op": "slice", "start": 0, "stop": 1000}, GROUP],
    "filter_alone": [FILTER],
}


def _table_of(chain, n, keep):
    return _lineitem_cols(n) if chain == "q1_shape" else _cols(n, keep)


# ---------------------------------------------------------------------------
# the same bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("keep", KEEPS)
@pytest.mark.parametrize("chain", sorted(DEFERRED))
def test_deferred_equals_per_op_and_exact(chain, keep, n):
    ops, cols = DEFERRED[chain](keep), _table_of(chain, n, keep)
    config.set_flag("BUCKETS", "")
    config.set_flag("METRICS", True)
    metrics.reset()
    fused = _run_plan_wire(ops, cols, n)
    c = metrics.snapshot()["counters"]
    # parity means nothing if the segment fell back to per-op replay
    assert c["plan.fused_ops"] == len(ops) and "plan.fallbacks" not in c
    assert c["filter.deferred"] == sum(
        o["op"] in ("filter", "rlike") for o in ops)
    per_op = _run_per_op_wire(ops, cols, n)
    config.set_flag("BUCKETS", "off")
    exact = _run_per_op_wire(ops, cols, n)
    # byte-identical 5-tuples: group order, integer, decimal AND float64
    # sums (the same bits, not a tolerance), validity, row counts
    assert fused == per_op
    assert fused == exact
    groups = fused[4]
    assert (groups == 0) == (keep == "none")


# ---------------------------------------------------------------------------
# the lowered text: no compaction where the groupby takes the mask
# ---------------------------------------------------------------------------


def _device(cols, n):
    return rb._table_from_wire(
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n, None,
    )


def _lowered(ops, cols, n):
    pt = bucketed._padded_input(_device(cols, n))
    text = jax.jit(
        lambda t, k: plan_mod._run_segment_traced(ops, t, k)
    ).lower(bucketed._strip(pt), bucketed._n_dev(pt)).as_text()
    return text, pt.row_count


def _compactions(text):
    """The row-id scatters of ``ops.filter._compaction_indices`` (the
    groupby's own ``boundary.at[0].set`` is a sorted, unique one)."""
    return len(re.findall(
        r"stablehlo\.scatter.*indices_are_sorted = false", text))


def _wide_gathers(text, width):
    return len(re.findall(
        rf"stablehlo\.gather.*-> tensor<{width}x", text))


@pytest.mark.parametrize("chain", sorted(DEFERRED))
def test_deferred_program_holds_no_compaction(chain):
    n = 1500
    text, width = _lowered(
        DEFERRED[chain]("some"), _table_of(chain, n, "some"), n)
    assert "stablehlo.sort" in text
    assert _compactions(text) == 0
    if chain != "rlike_groupby":  # the pattern's automaton gathers
        assert _wide_gathers(text, width) == 0


@pytest.mark.parametrize("chain", sorted(COMPACTED))
def test_a_segment_that_needs_the_prefix_still_compacts(chain):
    n = 1500
    text, width = _lowered(COMPACTED[chain], _cols(n, "some"), n)
    assert _compactions(text) >= 1  # the slice compacts too
    assert _wide_gathers(text, width) >= 1


@pytest.mark.parametrize("ops,start", [
    ([FILTER, GROUP], 0),
    ([GROUP], 0),
    (_q1_shape("some"), 0),
    ([FILTER, COMPACTED["filter_sort"][1], FILTER, GROUP], 2),
    (COMPACTED["filter_slice_groupby"], 2),
    (COMPACTED["filter_sort"], 2),
    ([FILTER], 1),
    ([], 0),
], ids=["filter_groupby", "groupby", "q1", "behind_a_sort", "behind_a_slice",
        "sort_tail", "alone", "empty"])
def test_deferred_from(ops, start):
    assert planops.deferred_from(ops) == start


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------


def _moved(fn):
    config.set_flag("BUCKETS", "")
    config.set_flag("METRICS", True)
    metrics.reset()
    fn()
    c = metrics.snapshot()["counters"]
    return c.get("filter.deferred", 0), c.get("filter.compacted", 0)


@pytest.mark.parametrize("ops,want", [
    ([FILTER, GROUP], (1, 0)),
    ([FILTER, FILTER, GROUP], (2, 0)),
    ([FILTER, COMPACTED["filter_sort"][1], FILTER, GROUP], (1, 1)),
    (COMPACTED["filter_slice_groupby"], (0, 1)),
    (COMPACTED["filter_sort"], (0, 1)),
    ([FILTER], (0, 1)),
    ([FILTER, {"op": "join", "on": [0]}], (0, 1)),
], ids=["filter_groupby", "two_filters", "one_each", "behind_a_slice",
        "sort_tail", "alone", "before_a_join"])
def test_counters_say_which_way_each_filter_went(ops, want):
    n = 700
    table = _device(_cols(n, "some"), n)
    rest = [_device(_cols(8, "all")[:2], 8)]
    run = lambda: plan_mod.run_plan(ops, table, rest)
    assert _moved(run) == want
    # the executable is cached now: counted at launch, not at trace
    assert _moved(run) == want


def test_q1_counts_one_deferred_filter_and_two_projects():
    n = 900
    table = _device(_lineitem_cols(n), n)
    run = lambda: plan_mod.run_plan(_q1_shape("some"), table)
    assert _moved(run) == (1, 0)
    assert metrics.snapshot()["counters"]["project.calls"] == 2


def test_the_exact_path_counts_neither():
    n = 700
    table = _device(_cols(n, "some"), n)

    def run():
        config.set_flag("BUCKETS", "off")
        plan_mod.run_plan([FILTER, GROUP], table)

    assert _moved(run) == (0, 0)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_the_mesh_stage_pre_segment_compacts():
    # filter -> partition: the exchange needs the prefix
    n = 4099
    table = _device(_cols(n, "some")[:4], n)
    ops = [FILTER, {"op": "partition", "kind": "hash", "keys": [0], "num": 4}]
    want = rb._table_to_wire(plan_mod.run_plan(ops, table))
    runner = parallel.MeshRunner(4)
    out = []
    moved = _moved(lambda: out.append(
        plan_mod.run_plan(ops, table, mesh_runner=runner)))
    assert moved == (0, 1)
    assert metrics.snapshot()["counters"]["plan.mesh_segments"] == 1
    assert rb._table_to_wire(out[0]) == want
