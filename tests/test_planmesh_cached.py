"""The mesh stage is built once and launched many times
(parallel/planmesh.py over ``buckets.cached_jit``).

A mesh stage's device work is three cached, jitted ``shard_map``
programs — ``srt_mesh_rowlocal``, ``srt_mesh_counts``,
``srt_mesh_exchange`` — keyed by what their shape depends on: the op
lists, the packed table's schema and bucketed width, the mesh, the
exchange's capacities. The cases here hold, on four of the CPU's virtual
devices: a second request of the same shape builds nothing; the answers
are the exact path's bytes; data (a range partition's splitters) is an
argument and never a constant of the cached program; unequal batches of
one bucket share a program; a degraded mesh is another key; a replay
goes through the cached callable; the completion clock sees the
launches.
"""

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import parallel
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu.column import Table
from spark_rapids_jni_tpu.parallel import planmesh
from spark_rapids_jni_tpu.utils import buckets, config, faults, metrics

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four virtual devices"
)

F64 = int(dt.TypeId.FLOAT64)
FLAGS = ("METRICS", "FAULTS", "RETRY_MAX", "RETRY_BASE_MS", "BUCKETS")

FILTER = {"op": "filter", "mask": 2}
CAST = {"op": "cast", "column": 1, "type_id": F64}
HASH = {"op": "partition", "kind": "hash", "keys": [0], "num": 4}
RANGE = {"op": "partition", "kind": "range", "keys": [0], "num": 4}

# name -> (plan, the stage's launches a request)
PLANS = {
    "hash": ([HASH], 2),
    "hash_pre": ([FILTER, HASH], 2),
    "hash_post": ([HASH, CAST], 2),
    "hash_pre_post": ([FILTER, HASH, CAST], 2),
    "hash_16_parts": ([FILTER, dict(HASH, num=16), CAST], 2),
    "range": ([RANGE], 2),
    "range_post": ([RANGE, CAST, FILTER], 2),
    "rowlocal": ([FILTER, CAST], 1),
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


def _fact(n: int, seed: int = 0, lo: int = 0, hi: int = 64) -> Table:
    rng = np.random.default_rng(seed)
    return Table.from_pydict({
        "k": rng.integers(lo, hi, n, dtype=np.int64),
        "v": rng.integers(-50, 50, n, dtype=np.int64),
        "m": rng.integers(0, 3, n, dtype=np.int64) > 0,
    })


def _bytes(t: Table):
    """Byte-comparable logical view (the exact path may hand back a
    padded table; the mesh path gathers the exact prefix)."""
    n = int(t.logical_row_count)
    return n, [
        (
            str(c.data.dtype),
            np.asarray(c.data)[:n].tobytes(),
            None if c.validity is None
            else np.asarray(c.validity)[:n].tobytes(),
        )
        for c in t.columns
    ]


class _Window:
    """What one stretch of work added to the registry."""

    def __enter__(self):
        self.before = metrics.snapshot()
        return self

    def __exit__(self, *exc):
        self.after = metrics.snapshot()

    def counter(self, name: str) -> int:
        return (self.after["counters"].get(name, 0)
                - self.before["counters"].get(name, 0))

    def timer(self, name: str) -> dict:
        zero = {"count": 0, "total_s": 0.0}
        a = self.after["timers"].get(name, zero)
        b = self.before["timers"].get(name, zero)
        return {"count": a["count"] - b["count"],
                "total_s": a["total_s"] - b["total_s"]}


def _mesh_run(ops, table, runner) -> Table:
    """``run_plan_mesh`` itself: the stage alone, so that a window
    around it holds no launch of the exact path."""
    return planmesh.run_plan_mesh(ops, table, runner)


# (a) built once, launched many times ---------------------------------------


@pytest.mark.parametrize("name", ["hash_pre_post", "range_post", "rowlocal"])
def test_second_request_of_a_shape_builds_nothing(name):
    ops, launches = PLANS[name]
    runner = parallel.MeshRunner(4)
    with _Window() as cold:
        _mesh_run(ops, _fact(3300, seed=1), runner)
    assert cold.counter("compile_cache.miss") == launches
    assert cold.timer("jax.build")["total_s"] > 0.0
    # the same shape, other rows
    t = _fact(3300, seed=2)
    with _Window() as warm:
        got = _mesh_run(ops, t, runner)
    assert warm.counter("compile_cache.miss") == 0
    assert warm.counter("compile_cache.hit") == launches
    assert warm.timer("jax.build") == {"count": 0, "total_s": 0.0}
    assert _bytes(got) == _bytes(plan_mod.run_plan(ops, t))


# (b) the exact path's bytes --------------------------------------------------


@pytest.mark.parametrize("name", sorted(PLANS))
@pytest.mark.parametrize("n", [5, 1023, 4100])
def test_answers_are_the_exact_path_s_bytes(name, n):
    ops, _ = PLANS[name]
    t = _fact(n, seed=n)
    want = _bytes(plan_mod.run_plan(ops, t))
    got = _bytes(plan_mod.run_plan(
        ops, t, mesh_runner=parallel.MeshRunner(4)))
    assert got == want


def test_stream_entry_point_shares_the_programs():
    """``run_plan_mesh_stream`` / ``prepare_exchange`` launch the same
    two programs ``run_plan_mesh`` built."""
    ops, launches = PLANS["hash_pre_post"]
    runner = parallel.MeshRunner(4)
    batches = [_fact(3300, seed=s) for s in (3, 4, 5)]
    first = _mesh_run(ops, batches[0], runner)
    with _Window() as w:
        outs = planmesh.run_plan_mesh_stream(ops, batches, runner)
    assert w.counter("compile_cache.miss") == 0
    assert w.counter("compile_cache.hit") == launches * len(batches)
    assert _bytes(outs[0]) == _bytes(first)
    for b, out in zip(batches, outs):
        assert _bytes(out) == _bytes(plan_mod.run_plan(ops, b))


# (c) data is an argument, never a constant of the cached program ----------


def test_range_splitters_are_an_argument_of_the_cached_program():
    """Two tables of one shape whose key ranges do not overlap: under
    one cached program each is cut by its OWN splitters. (With the
    first table's splitters closed over, every row of the second would
    fall into the last partition's device and its capacity overflow.)"""
    ops, launches = PLANS["range_post"]
    runner = parallel.MeshRunner(4)
    low = _fact(3300, seed=6, lo=0, hi=1000)
    high = _fact(3300, seed=7, lo=10**6, hi=10**6 + 1000)
    assert _bytes(_mesh_run(ops, low, runner)) == _bytes(
        plan_mod.run_plan(ops, low))
    with _Window() as w:
        got = _mesh_run(ops, high, runner)
        recv, _, _ = planmesh.take_exchange()
    assert w.counter("compile_cache.miss") == 0
    assert w.counter("compile_cache.hit") == launches
    assert _bytes(got) == _bytes(plan_mod.run_plan(ops, high))
    # cut by its own sample: every device receives about a quarter
    assert recv.sum() == 3300 and recv.min() > 3300 // 8


# (d) one program a bucket ------------------------------------------------------


@pytest.mark.parametrize("name", ["hash_pre_post", "rowlocal"])
def test_unequal_batches_of_one_bucket_share_a_program(name):
    """3,300 and 3,900 rows are 825 and 975 a shard: both pack to the
    1,024 bucket, and the planned capacities round to the same powers of
    two, so the second batch is launched and not built."""
    ops, launches = PLANS[name]
    runner = parallel.MeshRunner(4)
    _mesh_run(ops, _fact(3300, seed=8), runner)
    t = _fact(3900, seed=9)
    with _Window() as w:
        got = _mesh_run(ops, t, runner)
    assert w.counter("compile_cache.miss") == 0
    assert w.counter("compile_cache.hit") == launches
    assert _bytes(got) == _bytes(plan_mod.run_plan(ops, t))


def test_shard_width_comes_from_the_bucket_ladder():
    mesh = parallel.make_mesh(4)
    for n, per in ((3300, 1024), (3900, 1024), (4097, 2048), (5, 1024)):
        pt, cnt = planmesh._pack_sharded(_fact(n), mesh, "shuffle", n)
        assert pt.row_count == 4 * per
        real = np.asarray(cnt)
        assert real.sum() == n and real.max() <= per
        # contiguous blocks: every shard before the last real one is full
        assert list(real) == sorted(real, reverse=True)
    config.set_flag("BUCKETS", "off")  # no ladder: the exact width
    pt, cnt = planmesh._pack_sharded(_fact(3300), mesh, "shuffle", 3300)
    assert pt.row_count == 3300 and list(np.asarray(cnt)) == [825] * 4


# (e) another mesh is another program; a replay is a launch -----------------


def test_degraded_mesh_takes_a_new_key_and_answers_equally():
    ops, launches = PLANS["hash_pre_post"]
    config.set_flag("RETRY_MAX", "0")
    t = _fact(3300, seed=10)
    want = _bytes(plan_mod.run_plan(ops, t))
    runner = parallel.MeshRunner(4)
    assert _bytes(_mesh_run(ops, t, runner)) == want
    config.set_flag("FAULTS", "seed=2,collective:transient:1:1")
    with _Window() as w:
        got = _mesh_run(ops, t, runner)
    config.set_flag("FAULTS", "")
    doc = runner.to_doc()
    assert doc["degraded"] is True and doc["devices"] == 2
    assert _bytes(got) == want
    # two devices: other programs, built once
    assert w.counter("compile_cache.miss") == launches
    with _Window() as again:
        assert _bytes(_mesh_run(ops, t, runner)) == want
    assert again.counter("compile_cache.miss") == 0
    assert again.counter("compile_cache.hit") == launches


def test_two_meshes_of_one_size_over_other_devices_do_not_share(monkeypatch):
    from jax.sharding import Mesh

    ops, _ = PLANS["rowlocal"]
    t = _fact(3300, seed=11)
    want = _bytes(plan_mod.run_plan(ops, t))
    keys = []
    real = buckets.cached_jit

    def spy(key, *a, **kw):
        keys.append(key)
        return real(key, *a, **kw)

    monkeypatch.setattr(buckets, "cached_jit", spy)
    devs = jax.devices()
    for chosen in (devs[:2], devs[2:4], devs[:2]):
        mesh = Mesh(np.array(chosen), ("shuffle",))
        stage = planmesh._rowlocal_stage(ops, t, 3300, "shuffle")
        assert _bytes(stage(mesh)) == want
    assert [k[0] for k in keys] == ["mesh.rowlocal"] * 3
    assert keys[0] != keys[1] and keys[0] == keys[2]


def test_transient_at_the_shuffle_site_replays_through_the_cached_callable():
    ops, launches = PLANS["hash_pre_post"]
    config.set_flag("RETRY_BASE_MS", "1")
    t = _fact(3300, seed=12)
    runner = parallel.MeshRunner(4)
    want = _bytes(_mesh_run(ops, t, runner))
    config.set_flag("FAULTS", "seed=11,shuffle:transient:1:1")
    with _Window() as w:
        got = _mesh_run(ops, t, runner)
    assert faults.injection_stats()["shuffle:transient"]["injected"] == 1
    config.set_flag("FAULTS", "")
    assert w.counter("shuffle.retries") == 1
    assert w.counter("compile_cache.miss") == 0
    assert runner.to_doc()["degraded"] is False
    assert _bytes(got) == want == _bytes(plan_mod.run_plan(ops, t))


# (f) the completion clock sees the stage ---------------------------------------


def test_completion_clock_sees_the_stage_s_launches():
    ops, _ = PLANS["hash_pre_post"]
    runner = parallel.MeshRunner(4)
    t = _fact(3300, seed=13)
    with _Window() as w:
        for _ in range(3):
            plan_mod.run_plan(ops, t, mesh_runner=runner)
        plan_mod.run_plan(PLANS["rowlocal"][0], t, mesh_runner=runner)
    for name, count in (
        ("device.srt_mesh_counts", 3),
        ("device.srt_mesh_exchange", 3),
        ("device.srt_mesh_rowlocal", 1),
        ("device.plan.segment.mesh", 7),
    ):
        got = w.timer(name)
        assert got["count"] == count and got["total_s"] > 0.0, name
    assert w.counter("device.lost") == 0
    assert w.counter("plan.mesh_segments") == 4
    assert w.counter("partition.rows_exchanged") == 3 * 3300


def test_planmesh_holds_no_eager_shard_map_and_one_count_body():
    import inspect
    import re

    src = inspect.getsource(planmesh)
    assert len(re.findall(r"def count_body\(", src)) == 1
    # every shard_map is wrapped by the one builder, inside cached_jit
    assert len(re.findall(r"\bshard_map\(", src)) == 1
    assert "shard_map(" in inspect.getsource(planmesh._stage_program)
