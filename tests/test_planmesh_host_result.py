"""A mesh stage's result stays in the host buffers the gather filled
(``parallel/planmesh._gather_prefix``).

The stage's output leaves the chips once: each shard's kept prefix is
written into ONE preallocated ``numpy`` buffer a leaf, in mesh order,
and the result table's leaves ARE those buffers, in the device storage
dtype. Held here, on four of the CPU's virtual devices, for a row-local
plan, ``filter -> partition(hash)`` and ``partition(range)``: no leaf is
a ``jax.Array``; the bytes are the exact single-device path's, column by
column, with a nullable column and a STRING column (``lengths``), with
shards that keep no row, with a batch that does not fill its last shard
and after a replay on a degraded mesh; the gather's two counters tick as
they did; a plan over a host-backed result answers as over its device
twin (the upload is implicit, at first use).
"""

import inspect

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import parallel
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu.column import Table
from spark_rapids_jni_tpu.parallel import planmesh
from spark_rapids_jni_tpu.utils import buckets, config, metrics

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four virtual devices"
)

FLAGS = ("METRICS", "FAULTS", "RETRY_MAX", "RETRY_BASE_MS", "BUCKETS")
F64 = int(dt.TypeId.FLOAT64)

FILTER = {"op": "filter", "mask": 4}
PLANS = {
    "rowlocal": [FILTER, {"op": "cast", "column": 1, "type_id": F64}],
    "hash": [FILTER, {"op": "partition", "kind": "hash", "keys": [0],
                      "num": 4}],
    "range": [{"op": "partition", "kind": "range", "keys": [0], "num": 4}],
}


@pytest.fixture(autouse=True)
def _clean():
    for f in FLAGS:
        config.clear_flag(f)
    config.set_flag("METRICS", True)
    yield
    for f in FLAGS:
        config.clear_flag(f)
    metrics.reset()


def _fact(n: int, seed: int, keys=64, mask=None) -> Table:
    """key INT64, value INT64 with nulls, price FLOAT64, a STRING with
    nulls, the BOOL8 mask."""
    rng = np.random.default_rng(seed)
    words = ["", "a", "bc", "def", "shuffle", "prefix-of-a-shard"]
    v = rng.integers(-50, 50, n).tolist()
    s = [words[i] for i in rng.integers(0, len(words), n)]
    for i in rng.integers(0, n, max(n // 7, 1)):
        v[i] = None
    for i in rng.integers(0, n, max(n // 5, 1)):
        s[i] = None
    if mask is None:
        mask = rng.integers(0, 3, n) > 0
    return Table.from_pydict({
        "k": rng.integers(0, keys, n, dtype=np.int64),
        "v": v,
        "p": rng.normal(size=n) * 1e3,
        "s": s,
        "m": np.asarray(mask, dtype=bool),
    })


def _shapes(name: str) -> Table:
    if name == "mixed":
        return _fact(3300, seed=1)
    if name == "shards_keep_nothing":
        # one key: three of four destinations receive nothing; and the
        # mask drops the whole of the second shard's block of 1,024
        mask = np.ones(3300, dtype=bool)
        mask[1024:2048] = False
        return _fact(3300, seed=2, keys=1, mask=mask)
    if name == "five_rows":
        # one short shard and three that hold no row at all
        return _fact(5, seed=3)
    if name == "last_shard_short":
        # 2,048 a shard: two full, one of four rows, one empty
        return _fact(4100, seed=4)
    raise KeyError(name)


SHAPES = ["mixed", "shards_keep_nothing", "five_rows", "last_shard_short"]


def _leaves(t: Table):
    for c in t.columns:
        for x in (c.data, c.validity, c.lengths):
            if x is not None:
                yield x


def _bytes(t: Table):
    """Byte-comparable logical view, leaf by leaf, the storage dtype
    beside the bytes (the exact path may hand back a padded table)."""
    n = int(t.logical_row_count)
    return n, [
        tuple(
            None if x is None
            else (str(x.dtype), x.shape[1:], np.asarray(x)[:n].tobytes())
            for x in (c.data, c.validity, c.lengths)
        )
        for c in t.columns
    ]


def _assert_host_backed(t: Table) -> None:
    leaves = list(_leaves(t))
    assert leaves
    for x in leaves:
        assert isinstance(x, np.ndarray) and not isinstance(x, jax.Array)
        assert x.flags.c_contiguous and x.shape[0] == t.row_count
    assert t.logical_rows is None  # exact: nothing to slice away


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_result_is_host_backed_and_the_exact_path_s_bytes(plan, shape):
    ops, t = PLANS[plan], _shapes(shape)
    want = _bytes(plan_mod.run_plan(ops, t))
    got = planmesh.run_plan_mesh(ops, t, parallel.MeshRunner(4))
    _assert_host_backed(got)
    assert _bytes(got) == want
    # FLOAT64 travels as its bits, a STRING with its lengths
    assert got.columns[2].data.dtype == np.uint64
    assert got.columns[3].lengths is not None
    assert got.columns[3].validity is not None


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_offered_through_run_plan_the_result_is_the_same_table(plan):
    ops, t = PLANS[plan], _shapes("mixed")
    got = plan_mod.run_plan(ops, t, mesh_runner=parallel.MeshRunner(4))
    _assert_host_backed(got)
    assert _bytes(got) == _bytes(plan_mod.run_plan(ops, t))


@pytest.mark.parametrize("plan", ["hash", "rowlocal"])
def test_stream_entry_point_returns_host_backed_tables(plan):
    ops = PLANS[plan]
    batches = [_fact(n, seed=s) for n, s in ((3300, 5), (3900, 6), (7, 7))]
    outs = planmesh.run_plan_mesh_stream(
        ops, batches, parallel.MeshRunner(4))
    for b, out in zip(batches, outs):
        _assert_host_backed(out)
        assert _bytes(out) == _bytes(plan_mod.run_plan(ops, b))


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_replay_on_a_degraded_mesh_is_host_backed_and_equal(plan):
    """``test_degraded_mesh_takes_a_new_key_and_answers_equally``'s
    set-up: a collective fault that persists takes the runner to two
    devices, and the replay gathers two shards."""
    ops, t = PLANS[plan], _shapes("mixed")
    config.set_flag("RETRY_MAX", "0")
    want = _bytes(plan_mod.run_plan(ops, t))
    runner = parallel.MeshRunner(4)
    assert _bytes(planmesh.run_plan_mesh(ops, t, runner)) == want
    config.set_flag("FAULTS", "seed=2,collective:transient:1:1")
    got = planmesh.run_plan_mesh(ops, t, runner)
    config.set_flag("FAULTS", "")
    doc = runner.to_doc()
    assert doc["degraded"] is True and doc["devices"] == 2
    _assert_host_backed(got)
    assert _bytes(got) == want


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_gather_counters_tick_as_before(plan):
    """``rows_read`` += devices x the shard's physical output rows (every
    shard is read whole), ``rows_kept`` += the prefixes."""
    ops, t = PLANS[plan], _shapes("mixed")
    runner = parallel.MeshRunner(4)
    names = ["mesh.gather.rows_read", "mesh.gather.rows_kept"]
    c0 = metrics.counter_values(names)
    got = planmesh.run_plan_mesh(ops, t, runner)
    c1 = metrics.counter_values(names)
    if plan == "rowlocal":
        width = buckets.bucket_for(-(-3300 // 4))
    else:
        _, width, _ = planmesh.take_exchange()  # the exchange's `cap`
    assert c1[names[0]] - c0[names[0]] == 4 * width
    assert c1[names[1]] - c0[names[1]] == got.row_count > 0


SECOND = {
    "cast": [{"op": "cast", "column": 0, "type_id": F64}],
    "slice": [{"op": "slice", "start": 10, "stop": 500}],
    "sort_by": [{"op": "sort_by", "keys": [{"column": 0}, {"column": 2}]}],
    "groupby": [{"op": "groupby", "by": [0], "aggs": [
        {"column": 1, "agg": "sum"}, {"column": 2, "agg": "sum"},
        {"column": 1, "agg": "count"}]}],
}


@pytest.mark.parametrize("second", sorted(SECOND))
def test_a_plan_over_a_host_backed_result_answers_as_over_its_twin(second):
    """No caller is asked to upload: a consumer that computes on a mesh
    result puts its leaves on the device at first use."""
    t = _shapes("mixed")
    host = planmesh.run_plan_mesh(PLANS["hash"], t, parallel.MeshRunner(4))
    _assert_host_backed(host)
    twin = jax.tree_util.tree_map(jax.device_put, host)
    assert all(isinstance(x, jax.Array) for x in _leaves(twin))
    got = plan_mod.run_plan(SECOND[second], host)
    want = plan_mod.run_plan(SECOND[second], twin)
    assert _bytes(got) == _bytes(want)


def test_gather_puts_nothing_back_on_a_device():
    src = inspect.getsource(planmesh._gather_prefix)
    for banned in ("jnp.asarray", "device_put", "np.concatenate"):
        assert banned not in src, banned
