"""The one op table (``planops.OPS``) and the paths it decides.

Three things, each a case per op so each counts:

* every entry is whole — a field a layer reads is never missing;
* the tier ``plancheck`` reports for an op is the path the runtime
  TAKES for it, read from the runtime's own counters (not from the
  table the tier was computed from);
* each simple op has ONE traced body: alone through the generic one-op
  runner, inside a fused segment, and on the exact path it gives
  byte-equal wire output, on a table with nulls and a re-padded tail.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import plancheck, planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import buckets, config, metrics

from test_plancheck import B8, F64, I64, OPS_CORPUS, STR, _string_wire

IDENTITY = {"op": "slice"}  # fusable, and changes nothing
N = 100


@pytest.fixture(autouse=True)
def _flags():
    config.set_flag("BUCKETS", "")
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


def _device(cols, n):
    return rb._table_from_wire(
        [c[0] for c in cols], [c[1] for c in cols],
        [c[2] for c in cols], [c[3] for c in cols], n, None,
    )


def _base_cols(n=N):
    """int64 key, int64 value with nulls, BOOL8 mask, float64, STRING,
    and a second BOOL8 mask (so a filtered table still has one)."""
    rng = np.random.default_rng(n)
    k = rng.integers(0, 9, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    valid = (np.arange(n) % 7 != 0).astype(np.uint8)
    strs = [f"w{int(x) % 5}ord" if x % 3 else "" for x in k]
    return [
        (I64, 0, k.tobytes(), None),
        (I64, 0, v.tobytes(), valid.tobytes()),
        (B8, 0, (v > 0).astype(np.uint8).tobytes(), None),
        (F64, 0, rng.normal(size=n).tobytes(), None),
        (STR, 0, _string_wire(strs), None),
        (B8, 0, (k % 2 == 0).astype(np.uint8).tobytes(), valid.tobytes()),
    ]


def _counted(fn):
    """``fn()`` -> (its result, the counters it moved)."""
    metrics.reset()
    out = fn()
    return out, metrics.snapshot()["counters"]


# ---------------------------------------------------------------------------
# (i) every entry is whole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(planops.OPS))
def test_spec_is_whole(name):
    spec = planops.OPS[name]
    assert callable(spec.infer) and callable(spec.exact)
    # a traced body exactly when the op can ride a fused segment
    assert (spec.traced is not None) == (spec.fusable is not False)
    if spec.bucketable is not False:
        # a one-op program needs a body to compile, or a runner of its own
        assert spec.runner is not None or (
            spec.traced is not None and spec.program
        )
    else:
        assert spec.runner is None and spec.program is None
    if spec.row_local:
        assert spec.traced is not None  # only a traced chain is chunked
    if spec.select is not None and spec.traced is not None:
        # a selection may stay a mask only among row-local ops, and the
        # compacting body built over it returns the new count
        assert spec.row_local and spec.counts
    if spec.select is not None and spec.traced is None:
        # the join: inside a segment it can only select (PR 38); alone
        # it is its runner's, and no chain may chunk or shard it
        assert name == "join" and spec.runner is not None
        assert not spec.row_local and spec.fusable is False
    for flag in (spec.fusable, spec.bucketable):
        assert isinstance(flag, bool) or callable(flag)


def test_the_table_has_the_seventeen_ops():
    assert len(planops.OPS) == 17
    assert {n for n, s in planops.OPS.items() if s.exchange} == {"partition"}
    assert sorted(n for n, s in planops.OPS.items() if s.row_local) == [
        "cast", "filter", "project", "rlike"]
    assert sorted(n for n, s in planops.OPS.items() if s.select) == [
        "filter", "join", "rlike"]


# ---------------------------------------------------------------------------
# (ii) the tier plancheck reports is the path the runtime takes
# ---------------------------------------------------------------------------


def _input_for(op):
    """An input the corpus op can run on -> (table, rest)."""
    name = op.get("op")
    base = _base_cols()
    if name in ("to_rows", "from_rows", "explode"):
        fixed = _device(base[:1] if name != "to_rows" else base[:4], N)
        if name == "to_rows":
            return fixed, []
        # a LIST<UINT8> column: the rows of a one-INT64-column table
        return planops.dispatch({"op": "to_rows"}, fixed), []
    table = _device(base, N)
    if name == "concat":
        return table, [_device(base, N)]
    if name in ("join", "cross_join"):
        return table, [_device(_base_cols(8)[:2], 8)]
    return table, []


def _schema_args(table, rest):
    return dict(
        schema=plancheck.schema_of_table(table),
        rows=int(table.logical_row_count),
        rest=[(plancheck.schema_of_table(t), int(t.logical_row_count))
              for t in rest],
    )


@pytest.mark.parametrize(
    "op", OPS_CORPUS, ids=lambda o: "-".join(f"{k}={v}" for k, v in o.items())[:40]
)
def test_reported_tier_is_the_path_taken(op):
    table, rest = _input_for(op)
    try:
        report = plancheck.check_plan([op], **_schema_args(table, rest))
    except plancheck.PlanCheckError:
        # unsupported: the runtime refuses it too
        with pytest.raises(Exception):
            planops.dispatch(op, table, rest)
        return
    tier = report["ops"][0]["tier"]
    _, alone = _counted(lambda: planops.dispatch(op, table, rest))
    _, paired = _counted(
        lambda: plan_mod.run_plan([IDENTITY, op], table, rest)
    )
    assert paired.get("plan.fallbacks", 0) == 0
    assert alone.get("bucket.fallback_errors", 0) == 0
    if paired.get("plan.fused_ops", 0) == 2:
        took = "fusable"
    elif alone.get("bucket.dispatched", 0) == 1:
        took = "per-op"
    else:
        took = "exact-only"
    assert tier == took
    if took == "exact-only":
        assert paired.get("plan.exact_ops") == 2
        assert alone.get("bucket.dispatched", 0) == 0


# ---------------------------------------------------------------------------
# (iii) one traced body: alone == fused == exact, byte for byte
# ---------------------------------------------------------------------------

SIMPLE = {
    "cast": {"op": "cast", "column": 1, "type_id": F64},
    "project": {"op": "project", "exprs": [
        {"col": 0},
        {"binary": "add", "left": {"col": 0}, "right": {"col": 1}},
        {"col": 3},
    ]},
    "filter": {"op": "filter", "mask": 4},
    # matches the empty string: the padding tail's zero-length strings
    # are selected unless the occupancy gate holds them back
    "rlike": {"op": "rlike", "column": 3, "pattern": ".*"},
    "distinct": {"op": "distinct", "keys": [0]},
    "sort_by": {"op": "sort_by", "keys": [
        {"column": 1, "ascending": False}, {"column": 0}]},
}


def _repadded():
    """A padded table whose tail is not zeros: what a capped filter
    leaves behind (it clones kept rows past the count)."""
    out = planops.dispatch({"op": "filter", "mask": 2}, _device(_base_cols(), N))
    assert out.logical_rows is not None
    assert 0 < out.logical_row_count < N < out.row_count
    return out


@pytest.mark.parametrize("name", sorted(SIMPLE))
def test_one_traced_body_three_paths(name):
    op = SIMPLE[name]
    spec = planops.OPS[name]
    assert spec.runner is None and spec.program  # the generic runner's
    pre = _repadded()
    buckets.cache_clear()
    alone, c = _counted(lambda: planops.dispatch(op, pre))
    assert c["bucket.dispatched"] == 1 and "bucket.fallback_errors" not in c
    assert c["compile_cache.miss"] == 1  # one program, the op's own
    fused, c = _counted(lambda: plan_mod.run_plan([op, IDENTITY], pre))
    assert c["plan.fused_ops"] == 2 and "plan.fallbacks" not in c
    exact = spec.exact(op, buckets.unpad_table(pre), [])
    want = rb._table_to_wire(exact)
    assert want[4] > 0
    assert rb._table_to_wire(alone) == want
    assert rb._table_to_wire(fused) == want


def test_project_calls_counts_launches_on_every_path():
    op = SIMPLE["project"]
    pre = _repadded()
    assert _counted(lambda: planops.dispatch(op, pre))[1]["project.calls"] == 1
    again = {"op": "project", "exprs": [{"col": 0}]}
    _, c = _counted(lambda: plan_mod.run_plan([op, IDENTITY, again], pre))
    assert c["project.calls"] == 2 and c["plan.fused_ops"] == 3
    _, c = _counted(lambda: planops.OPS["project"].exact(
        op, buckets.unpad_table(pre), []))
    assert c["project.calls"] == 1
