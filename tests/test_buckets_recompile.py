"""Recompile-regression: ragged streams compile O(#buckets), not O(N).

The tentpole claim of the shape-bucket plane, pinned with real compile
counts: dispatching a stream of 8 ragged-row-count batches through 3
representative ops (cast, sort_by, groupby) compiles at most
``#buckets x #ops`` executables with bucketing ON (every further call
is a ``compile_cache.hit``), while the exact-shape path compiles fresh
programs for every distinct batch size.

Compile counting is double-sourced: the cache's own hit/miss counters
(a miss == one ``jax.jit`` build, keyed so each key sees exactly one
shape signature) AND ``jax.log_compiles`` output filtered to the
``srt_bucketed_*`` executables the cache names.
"""

import json
import logging

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import buckets, config, metrics

I64 = int(dt.TypeId.INT64)

# 8 ragged sizes spanning exactly TWO buckets of the 1024 x2 ladder
SIZES = (911, 977, 1013, 1024, 1031, 1499, 1777, 2047)
N_BUCKETS = 2

OPS = (
    {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
    {"op": "sort_by", "keys": [{"column": 0}]},
    {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
)
# executables a dispatch launches: one per op, and the groupby's
# per-group half behind its sort half — keyed by the (input bucket,
# group-count bucket) pair, and 7 groups always land in the 1024 bucket
LAUNCHES = len(OPS) + 1


@pytest.fixture(autouse=True)
def _clean():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


class _CompileLog(logging.Handler):
    """Captures the WARNING-level compile lines jax.log_compiles emits."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


def _stream():
    for n in SIZES:
        rng = np.random.default_rng(n)
        k = rng.integers(0, 7, n, dtype=np.int64)
        v = rng.integers(-5, 5, n, dtype=np.int64)
        for op in OPS:
            out = rb.table_op_wire(
                json.dumps(op), [I64, I64], [0, 0],
                [k.tobytes(), v.tobytes()], [None, None], n,
            )
            assert out[4] > 0


def _captured_stream():
    handler = _CompileLog()
    jax_logger = logging.getLogger("jax")
    jax_logger.addHandler(handler)
    try:
        with jax.log_compiles():
            _stream()
    finally:
        jax_logger.removeHandler(handler)
    # one "Compiling <name> with global shapes..." line per executable
    return [m for m in handler.messages if m.startswith("Compiling ")]


def test_bucketed_stream_compiles_at_most_buckets_executables():
    config.set_flag("BUCKETS", "1024:2")
    jax.clear_caches()
    buckets.cache_clear()
    metrics.reset()
    compiles = _captured_stream()

    snap = metrics.snapshot()
    misses = snap["counters"]["compile_cache.miss"]
    hits = snap["counters"].get("compile_cache.hit", 0)
    total_calls = len(SIZES) * LAUNCHES
    budget = N_BUCKETS * LAUNCHES
    # the acceptance bound: <= #buckets executables per launch across
    # the whole ragged stream, every other dispatch a cache hit
    assert misses <= budget, f"{misses} compiles for {budget} budget"
    assert hits == total_calls - misses
    # cross-check against the ACTUAL XLA compile log
    bucketed = [
        m for m in compiles
        if "srt_bucketed" in m or "srt_groupby_reduce" in m
    ]
    assert len(bucketed) <= budget, bucketed
    # pad-waste accounting rode along
    assert snap["bytes"]["bucket.pad_waste_bytes"] > 0


def test_exact_stream_compiles_per_size():
    # the counterfactual: bucketing OFF compiles fresh programs for
    # every distinct batch size — at least one executable per size,
    # and none of them from the bucket plane
    config.set_flag("BUCKETS", "off")
    jax.clear_caches()
    buckets.cache_clear()
    metrics.reset()
    compiles = _captured_stream()

    assert len(compiles) >= len(SIZES)
    assert not [m for m in compiles if "srt_bucketed" in m]
    snap = metrics.snapshot()
    assert "compile_cache.miss" not in snap["counters"]


def test_second_stream_is_all_hits():
    # a second identical stream through a warm cache compiles NOTHING
    config.set_flag("BUCKETS", "1024:2")
    jax.clear_caches()
    buckets.cache_clear()
    _stream()  # warm
    metrics.reset()
    compiles = _captured_stream()
    snap = metrics.snapshot()
    assert not [m for m in compiles if "srt_bucketed" in m]
    assert not [m for m in compiles if "srt_groupby_reduce" in m]
    assert snap["counters"].get("compile_cache.miss", 0) == 0
    assert snap["counters"]["compile_cache.hit"] == len(SIZES) * LAUNCHES
