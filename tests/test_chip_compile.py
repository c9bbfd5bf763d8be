"""Compile the chip path for a described TPU v5e — no chip attached.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not present (on-chip-measurement guide §2, the third
rehearsal). Interpret mode cannot see what Mosaic refuses — a 64-bit
scalar leaking into a kernel body, an in-kernel gather, a program that
does not fit the device — so every kernel in ``kernels/registry.py``
``_REGISTRY`` has a case here, at the size ``chip_smoke.py`` runs it,
next to the capped join (at the smoke's 2^23 bucket), the capped groupby
and the four-chip exchange. A kernel the chip's compiler refuses does
not get registered.

This is the only file that describes a chip. The topology is described
inside a module-scoped fixture (never at import: only one process may
load libtpu, and every xdist worker imports every test file), the
compiles run in the test's own process, and the persistent compile
cache is off around them (an AOT entry cannot be read back without a
chip, and the next compile would warn).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import rows as rows_mod
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import buckets

#: chip_smoke.py's sizes: the row round trip, and the fact table's bucket
SMOKE_ROWS = 4_000_000
SMOKE_BUCKET = 1 << 23
#: v5e HBM per chip (bytes_limit is a little under this)
V5E_HBM = 16 << 30

ROW_SCHEMA = (
    dt.INT64, dt.FLOAT64, dt.INT32, dt.BOOL8, dt.FLOAT32, dt.INT8,
    dt.DType(dt.TypeId.DECIMAL32, -3), dt.DType(dt.TypeId.DECIMAL64, -8),
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer code that asks the backend at trace time (utils/ieee754.py,
    kernels.default_interpret) down its TPU branch: under
    JAX_PLATFORMS=cpu it would otherwise trace the CPU's."""
    from spark_rapids_jni_tpu import kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert total < V5E_HBM, f"program needs {total / 2**30:.1f} GiB"


def _table(one_chip, schema, n, nullable=False) -> Table:
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return Table([
        Column(
            s((n,), np.dtype(d.storage_dtype)), d,
            s((n,), jnp.bool_) if nullable else None,
        )
        for d in schema
    ])


# ---------------------------------------------------------------------------
# the Pallas row kernels (kernels/row_transpose.py)
# ---------------------------------------------------------------------------


def test_pack_rows_pallas_compiles(one_chip):
    from spark_rapids_jni_tpu.kernels import row_transpose as rt

    layout = rows_mod.compute_fixed_width_layout(ROW_SCHEMA)
    n = 1 << 20
    cols = tuple(
        jax.ShapeDtypeStruct((n, w), jnp.uint8, sharding=one_chip)
        for w in layout.column_widths
    )
    valid = jax.ShapeDtypeStruct(
        (n, len(ROW_SCHEMA)), jnp.uint8, sharding=one_chip
    )
    compiled = rt.pack_rows_pallas.lower(
        cols, valid, layout=layout, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unpack_rows_pallas_compiles(one_chip):
    from spark_rapids_jni_tpu.kernels import row_transpose as rt

    layout = rows_mod.compute_fixed_width_layout(ROW_SCHEMA)
    rows = jax.ShapeDtypeStruct(
        (1 << 20, layout.row_size), jnp.uint8, sharding=one_chip
    )
    compiled = rt.unpack_rows_pallas.lower(
        rows, layout=layout, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# every registered kernel, as its registry runner launches it
# ---------------------------------------------------------------------------


def _compile_row_pack(one_chip):
    layout = rows_mod.compute_fixed_width_layout(ROW_SCHEMA)
    cols = _table(one_chip, ROW_SCHEMA, SMOKE_ROWS, nullable=True).columns
    return jax.jit(
        lambda c: rows_mod._pack_batch_pallas(c, layout)
    ).lower(cols).compile()


def _compile_row_unpack(one_chip):
    layout = rows_mod.compute_fixed_width_layout(ROW_SCHEMA)
    data = jax.ShapeDtypeStruct(
        (SMOKE_ROWS, layout.row_size), jnp.uint8, sharding=one_chip
    )
    return jax.jit(
        lambda d: rows_mod._unpack_batch_pallas(d, layout)
    ).lower(data).compile()


#: kernel name -> compile of its device program at the largest shape its
#: applicability predicate admits (the row kernels have no row bound:
#: the smoke's 4,000,000 rows stand in). A new registry entry needs a
#: line here before it can ship.
REGISTRY_CASES = {
    "row_pack": _compile_row_pack,
    "row_unpack": _compile_row_unpack,
}


def test_every_registered_kernel_has_a_compile_case():
    from spark_rapids_jni_tpu.kernels import registry

    assert set(REGISTRY_CASES) == set(registry._REGISTRY)


@pytest.mark.parametrize("name", sorted(REGISTRY_CASES))
def test_registered_kernel_compiles_at_smoke_size(one_chip, as_tpu, name):
    compiled = REGISTRY_CASES[name](one_chip)
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel inside"
    _fits(compiled)


# ---------------------------------------------------------------------------
# the unregistered kernel the partition op runs on TPU
# ---------------------------------------------------------------------------


def test_fused_murmur3_kernel_compiles(one_chip):
    """ops/hashing.murmur3_table picks this kernel whenever it runs on
    a TPU — the hash `partition` op's partition ids."""
    from spark_rapids_jni_tpu.kernels import hashing as khash

    n = SMOKE_BUCKET // 4  # one of four shards of the smoke's fact
    w = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
    compiled = khash._hash_words_pallas.lower(
        (w, w), (v,), kinds=("long",), seed=42, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the capped join and groupby at the smoke's bucket (f64, memory)
# ---------------------------------------------------------------------------

FACT = (dt.INT64, dt.INT64, dt.INT64, dt.FLOAT64)


def test_capped_inner_join_compiles_at_smoke_bucket(one_chip, as_tpu):
    from spark_rapids_jni_tpu.ops import join as join_mod

    fact = _table(one_chip, FACT, SMOKE_BUCKET)
    dim = _table(one_chip, (dt.INT64, dt.INT64), 1 << 13)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fn(l, r, ln, rn):
        lv = buckets.tail_valid(l.row_count, ln)
        rv = buckets.tail_valid(r.row_count, rn)
        return join_mod.inner_join_capped(
            l, r, [0], SMOKE_BUCKET, left_valid=lv, right_valid=rv
        )

    _fits(jax.jit(fn).lower(fact, dim, n32, n32).compile())


def test_direct_join_probe_compiles_without_a_loop(one_chip, as_tpu):
    """The resident query's probe (PR 28): 2^23 fact keys against a
    2^13-row dimension through a 2^14-entry table, which one scatter of
    the dimension's rows fills (PR 41). As the chip's compiler leaves
    it, the program has no loop and gathers ONCE at the fact side's
    width, where the search gathers eight times in two."""
    import re

    from spark_rapids_jni_tpu.ops import join as join_mod

    fact = _table(one_chip, FACT, SMOKE_BUCKET)
    dim = _table(one_chip, (dt.INT64, dt.INT64), 1 << 13)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fn(l, r, ln, rn):
        lv = buckets.tail_valid(l.row_count, ln)
        rv = buckets.tail_valid(r.row_count, rn)
        perm_r, lo, counts, _ = join_mod._match_ranges(
            l, r, [0], [0], lv, rv, table_size=1 << 14
        )
        return perm_r, lo, counts, jnp.sum(counts)

    compiled = jax.jit(fn).lower(fact, dim, n32, n32).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert not re.search(r"\bwhile\(", hlo)
    wide = re.findall(rf"= \w+\[{SMOKE_BUCKET}\]\S* gather\(", hlo)
    assert 1 <= len(wide) <= 2, wide
    assert len(re.findall(rf"= \w+\[{1 << 14}\]\S* scatter\(", hlo)) == 1
    assert not re.findall(rf"= \w+\[{1 << 14}\]\S* gather\(", hlo)


@pytest.mark.parametrize("form,rows,groups", [
    ("sort_half", 1 << 13, None),
    ("reduce_half", 1 << 13, 1 << 10),
    pytest.param("one_trace", 1 << 13, None, marks=pytest.mark.slow),
    pytest.param("sort_half", SMOKE_BUCKET, None, marks=pytest.mark.slow),
    pytest.param(
        "reduce_half", SMOKE_BUCKET, 1 << 13, marks=pytest.mark.slow
    ),
])
def test_capped_groupby_compiles(one_chip, as_tpu, form, rows, groups):
    """sum / count / float64 sum, as the smoke's resident plan
    aggregates: the f64 path (utils/ieee754.py's TPU branch) and the
    64-bit variadic sort, for the chip's compiler — as the served
    runners launch them: the sort half at the input's bucket
    (``groupby_sort``) and the per-group half at the (input bucket,
    group-count bucket) pair (``groupby_reduce``). Tier-1 compiles the
    halves at small buckets; the smoke's own 2^23 -> 2^13 pair, where
    the question is memory, is the nightly tier's, and so is the one
    trace that ``parallel/distributed.py`` keeps (the two halves in one
    program: it compiles what they compile). The TPU compiler takes
    minutes on the reduce half at any size (my AOT runs here, PR 26:
    sort half 14 s, reduce half 137 s, one trace 127 s at 2^10-2^13
    rows; PR 23: ~400 s for the one trace at 2^23; a min/max
    aggregation adds ~230 s)."""
    from spark_rapids_jni_tpu.ops.groupby import (
        GroupbyAgg,
        groupby_aggregate_capped,
        groupby_reduce,
        groupby_sort,
    )

    fact = _table(one_chip, FACT, rows)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    aggs = [GroupbyAgg(2, "sum"), GroupbyAgg(2, "count"),
            GroupbyAgg(3, "sum")]

    def sort_half(t, n):
        rv = buckets.tail_valid(t.row_count, n)
        return groupby_sort(t, [0], aggs, row_valid=rv)

    def one_trace(t, n):
        rv = buckets.tail_valid(t.row_count, n)
        return groupby_aggregate_capped(
            t, [0], aggs, num_segments=t.row_count, row_valid=rv
        )

    if form == "reduce_half":
        state, _ = jax.eval_shape(sort_half, fact, n32)
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            state,
        )
        _fits(
            jax.jit(lambda st, g: groupby_reduce(st, g, groups))
            .lower(state, n32).compile()
        )
    else:
        fn = sort_half if form == "sort_half" else one_trace
        _fits(jax.jit(fn).lower(fact, n32).compile())


LINEITEM = (
    dt.INT64, dt.decimal64(-2), dt.decimal64(-2), dt.decimal64(-2),
    dt.decimal64(-2), dt.INT32, dt.INT32, dt.INT8, dt.INT8,
)


def test_q1_fused_segment_compiles(one_chip, as_tpu):
    """TPC-H Q1's ``project -> filter -> project -> groupby`` as the one
    ``srt_fused_plan`` program the served path launches (perfbench's
    ``q1-resident`` plan over the ``lineitem`` schema): the comparison,
    the 64-bit decimal products and the sort half (one folded u32 key
    word whose leading bit is occupancy AND the predicate, the
    permutation, five int64 payloads and their one shared mask) for the
    chip's compiler, at a small bucket; the filter keeps its rows, so
    the chip's own HLO holds no gather (PR 30). At the cell's 2^23 the
    question is minutes and memory, and the chip run answers it
    (PERF.md, PR 27)."""
    import json

    from spark_rapids_jni_tpu import plan as plan_mod

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "traffic", "q1-resident.json")) as f:
        (step,) = [s for s in json.load(f)["request"] if s["do"] == "plan"]
    (kind, seg), _ = plan_mod.segment_plan(step["plan"])
    assert kind == "fused" and len(seg) == 4
    table = _table(one_chip, LINEITEM, 1 << 13)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda t, n: plan_mod._run_segment_traced(seg, t, n))
        .lower(table, n32).compile()
    )
    _fits(compiled)
    assert " gather(" not in compiled.as_text()


def test_resident_query_fused_segment_compiles(one_chip, as_tpu):
    """The resident query's ``filter -> join -> groupby`` as the one
    ``srt_fused_plan`` program the served path launches when the
    dimension's key is unique and dense (perfbench's ``resident-query``
    plan over the ``ss-star-8m`` schemas, PR 38), for the chip's
    compiler at small buckets: 2^13 fact rows against a 2^10-row
    dimension through a 2^11-entry table. Neither the filter nor the
    join moves a row, so at the fact side's width the chip's own HLO
    gathers three times: each row's build row through the direct
    probe's address, and the two ``bits_to_f64`` table reads of the
    FLOAT64 sum (ROADMAP A9 (b)). ``cat``, which nothing reads, costs
    nothing."""
    import json
    import re

    from spark_rapids_jni_tpu import plan as plan_mod, plancheck

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "traffic", "resident-query.json")) as f:
        (step,) = [s for s in json.load(f)["request"] if s["do"] == "plan"]
    ops = step["plan"]
    (kind, idxs), (tail, _) = plancheck.predict_segments(
        ops, lambda i, op: True
    )
    assert (kind, idxs, tail) == ("fused", [0, 1, 2], "exact")
    width = 1 << 13
    fact = _table(one_chip, FACT + (dt.BOOL8,), width)
    dim = _table(one_chip, (dt.INT64, dt.INT64), 1 << 10)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(lambda t, n, r, rn: plan_mod._run_segment_traced(
            ops[:3], t, n, [(r, rn, 1 << 11)]))
        .lower(fact, n32, dim, n32).compile()
    )
    _fits(compiled)
    wide = re.findall(rf"= (\w+)\[{width}\]\S* gather\(", compiled.as_text())
    assert sorted(wide) == ["f32", "f32", "s32"], wide


# perfbench's ``tpch-q3-part.q3-resident`` cell: the lineitem join's two
# programs as ``bucketed._r_join`` builds them, at the cell's buckets
Q3_PROBE = (dt.INT64, dt.decimal64(-2), dt.decimal64(-2))  # 2^23: kept lines
Q3_BUILD = (dt.INT64, dt.INT32, dt.INT32)                  # 2^18: open orders
Q3_BUILD_BUCKET = 1 << 18
Q3_OUT_BUCKET = 1 << 16
#: dbgen's sparse order key, one shuffle partition of seven: the open
#: orders' keys span ~58 M values, 28 x the partition's orders
Q3_TABLE = 1 << 26


def _q3_probe(table_size=Q3_TABLE):
    """The probe program `bucketed._r_join` compiles for that join: its
    own builder, with the choice the runner makes for a unique INT64
    key whose span fits the device's share (a table as wide as the
    span, a row a key), or, with None, for one whose span is past it
    and under 2^32 (no table; the one-word search)."""
    from spark_rapids_jni_tpu import bucketed

    return bucketed.join_probe_program(
        [0], table_size, table_size is None, table_size is not None
    )


def _wide_gathers(compiled, width: int) -> list:
    import re

    return re.findall(rf"= \w+\[{width}\]\S* gather\(", compiled.as_text())


def _compile_q3_probe(one_chip, table_size):
    probe = _table(one_chip, Q3_PROBE, SMOKE_BUCKET)
    build = _table(one_chip, Q3_BUILD, Q3_BUILD_BUCKET)
    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(_q3_probe(table_size)).lower(probe, build, n32, n32).compile()
    )
    _fits(compiled)
    return compiled


def test_q3_table_probe_compiles_at_the_cell_s_buckets(one_chip, as_tpu):
    """The fact-to-fact join of TPC-H Q3 (2^23 kept ``lineitem`` rows
    against the 2^18-row bucket of the open orders, a unique INT64 key
    that spans 2^26 values: `direct_table_size` answers 2^26) takes the
    TABLE (PR 41): the build side's sort, 268 MB of fill, ONE scatter
    of the 2^18 build rows and ONE gather at the probe side's width; no
    loop, no search. The table is too large for the fast memory space,
    so that gather reads HBM (0.15-0.24 s where an `S(1)` one costs
    0.073, PERF.md §6), once."""
    import re

    compiled = _compile_q3_probe(one_chip, Q3_TABLE)
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert len(_wide_gathers(compiled, SMOKE_BUCKET)) == 1
    assert not _wide_gathers(compiled, Q3_TABLE)
    assert len(re.findall(rf"= \w+\[{Q3_TABLE}\]\S* scatter\(", text)) == 1


def test_q3_search_probe_compiles_past_the_table_s_bound(one_chip, as_tpu):
    """...and the path that stays for a span past the device's share
    (more shuffle partitions, a smaller chip) and under 2^32: the
    one-word SEARCH at the same buckets, `_lex_searchsorted` twice,
    each a loop of ``ceil(log2(2^18 + 1))`` = 19 steps of ONE u32 gather
    at the probe side's width, from a table the compiler keeps in the
    fast memory space (`S(1)`: what makes a step cost its 8.6 ns an
    element whatever the data, PERF.md §6)."""
    import re

    compiled = _compile_q3_probe(one_chip, None)
    assert len(re.findall(r"\bwhile\(", compiled.as_text())) == 2
    assert len(_wide_gathers(compiled, SMOKE_BUCKET)) == 2


def _compile_join_mat(one_chip, probe_fn, probe, build, cap):
    """`bucketed.join_mat_program` at output bucket ``cap``, over the
    ranges ``probe_fn`` (the join's own probe program) hands it."""
    from spark_rapids_jni_tpu import bucketed

    n32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    perm_r, lo, counts = (
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        for x in jax.eval_shape(probe_fn, probe, build, n32, n32)[:3]
    )
    fn = bucketed.join_mat_program([0], cap, False)
    compiled = (
        jax.jit(fn).lower(probe, build, perm_r, lo, counts, n32).compile()
    )
    _fits(compiled)
    return compiled


def test_q3_join_materialise_compiles_at_the_cell_s_buckets(one_chip, as_tpu):
    """...and its materialise: ~40,000 matched rows (bucket 2^16) of
    the 2^23-row probe side, each with the build side's date and
    priority, which the next op reads as group keys. Nothing in it is
    as wide as the probe side but the cumsum that places the rows: every
    gather is at the OUTPUT's bucket."""
    compiled = _compile_join_mat(
        one_chip, _q3_probe(),
        _table(one_chip, Q3_PROBE, SMOKE_BUCKET),
        _table(one_chip, Q3_BUILD, Q3_BUILD_BUCKET), Q3_OUT_BUCKET,
    )
    from spark_rapids_jni_tpu.ops.join import mat_spreads

    # an output narrower than its probe side: the gather form, the
    # parent's program (a spread would scatter 2^23 updates a word)
    assert not mat_spreads(Q3_OUT_BUCKET, SMOKE_BUCKET)
    assert not _wide_gathers(compiled, SMOKE_BUCKET)
    assert _wide_gathers(compiled, Q3_OUT_BUCKET)


# perfbench's ``tpcds-q95-wswh.selfjoin-resident`` cell: one table as both
# sides, a build key that repeats 8..16 times, an output 12.5 times the
# input (PR 50)
Q95_SIDE = (dt.INT64, dt.INT64)   # ws_order_number, ws_warehouse_sk
Q95_BUCKET = 1 << 20              # 648,000 rows
Q95_TABLE = 1 << 24               # the partition's order numbers span 10.8M


def _q95_probe():
    """The cell's probe program: the table for a key that REPEATS, at a
    build bucket of 2^16 rows or more, so the run's length is a second
    probe-wide gather. Only its output shapes are taken here. Compiled
    for the described chip (a scratch compile, PERF.md section 6, PR 50)
    it is two sorts, ONE scatter into `s32[2^24]`, six gathers at 2^20
    and no loop, in 167 s, most of them the build side's sort, which
    this file's other sort-bearing cases already pay for: the chip run
    holds the program itself."""
    from spark_rapids_jni_tpu import bucketed

    return bucketed.join_probe_program([0], Q95_TABLE, False, False)


def test_self_join_materialise_compiles_at_the_ladder_s_top(
    one_chip, as_tpu
):
    """The self-join's materialise at the ladder's top: 8,128,758 pairs
    in 2^23 slots from 2^20 counts, three INT64 columns out (the key and
    both sides' warehouse). The output is wider than the probe side, so
    `ops.join.mat_spreads` answers "spread" (PR 51): the probe side's
    two INT64 columns and each slot's place in the build side go out as
    scatters of 2^20 first differences and cumsums over the slots, and
    exactly THREE gathers at the output's width are left, all of the
    build side: the sort's permutation at each slot's place (`perm_r`,
    one `s32` word) and the two 32-bit words of the right warehouse by
    the row that gives. Ten before (PR 50), seven of them indexed by
    the slot's probe row; the three are what the cell's
    `dev_join_mat_ms` is made of now."""
    import re

    from spark_rapids_jni_tpu.ops.join import mat_spreads

    assert mat_spreads(SMOKE_BUCKET, Q95_BUCKET)
    side = _table(one_chip, Q95_SIDE, Q95_BUCKET)
    compiled = _compile_join_mat(
        one_chip, _q95_probe(), side, side, SMOKE_BUCKET
    )
    assert len(_wide_gathers(compiled, SMOKE_BUCKET)) == 3
    assert not _wide_gathers(compiled, Q95_BUCKET)
    # the spreads: every scatter adds u32 words into an operand that
    # has the output's slots on its minor dimension
    scatters = re.findall(
        r"= (\w+)\[([\d,]+)\]\S* scatter\(", compiled.as_text()
    )
    assert scatters and all(
        t == "u32" and shape.split(",")[-1] == str(SMOKE_BUCKET)
        for t, shape in scatters
    ), scatters


# ---------------------------------------------------------------------------
# four chips: the mesh partition stage (parallel/planmesh.py) as one
# program over the described 2x2 mesh
# ---------------------------------------------------------------------------


def test_mesh_partition_exchange_compiles_for_four_chips(
    topo, as_tpu, monkeypatch
):
    """filter -> hash partition over 4 chips: the two programs the stage
    launches (``planmesh._stage_program``: the cached callables
    themselves, lowered through their own ``.lower``) compile for the
    described 2x2 mesh. The exchange must lower to ``ragged-all-to-all``
    with 64-bit columns in the table (the TPU compiler has no X64
    rewrite for that collective — the exchange carries them as (n, 2)
    u32 words) and both keep the fused murmur3 kernel inside."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu import kernels
    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod
    from spark_rapids_jni_tpu.parallel import planmesh, shuffle

    axis = mesh_mod.SHUFFLE_AXIS
    mesh = Mesh(np.array(topo.devices), (axis,))
    size, per = 4, 1 << 18

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    fact = FACT + (dt.BOOL8,)
    pt = Table([
        Column(sds((per * size,), np.dtype(d.storage_dtype), P(axis)), d, None)
        for d in fact
    ])
    cnt = sds((size,), jnp.int32, P(axis))
    monkeypatch.setattr(shuffle, "_ragged_impl", lambda impl: "ragged")
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)

    ops = [
        {"op": "filter", "mask": 4},
        {"op": "partition", "kind": "hash", "keys": [0], "num": size},
    ]
    pre, part, post = planmesh._split_at_exchange(ops)
    counts_fn = planmesh._stage_program("counts", mesh, axis, pt, pre, part)
    assert counts_fn.__name__ == "srt_mesh_counts"
    counted = counts_fn.lower(pt, cnt, ()).compile()
    assert "tpu_custom_call" in counted.as_text()
    _fits(counted)

    # planned (src, dst) send counts, as the counts program returns them;
    # every destination receives `per` rows: the capacities of that plan
    counts = sds((size, size), jnp.int32, P(axis))
    exchange_fn = planmesh._stage_program(
        "exchange", mesh, axis, pt, pre, part, post, per, per // size
    )
    assert exchange_fn.__name__ == "srt_mesh_exchange"
    compiled = exchange_fn.lower(pt, cnt, counts, ()).compile()
    text = compiled.as_text()
    assert "ragged-all-to-all" in text
    assert "tpu_custom_call" in text
    _fits(compiled)


def test_mesh_groupby_stage_compiles_for_four_chips(topo, as_tpu, monkeypatch):
    """partition -> groupby -> project -> filter over 4 chips (TPC-H
    Q18's heavy stage, ISSUE 46): the exchange program whose merge side is the
    groupby's sort half hands its sorted state, sharded, to
    ``srt_mesh_groupby``, and both compile for the described 2x2 mesh.
    At an 8,000,000-row batch's size (2^21 rows a shard, 2^19 candidate groups) the
    pair compiled here in 199 s and 40 s and needs 4.1 GiB a chip (PR
    46); the suite compiles 2^13 rows a shard."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from spark_rapids_jni_tpu import kernels
    from spark_rapids_jni_tpu.parallel import mesh as mesh_mod
    from spark_rapids_jni_tpu.parallel import planmesh, shuffle

    axis = mesh_mod.SHUFFLE_AXIS
    mesh = Mesh(np.array(topo.devices), (axis,))
    size, per, group_cap = 4, 1 << 13, 1 << 11

    def sds(shape, dtype, spec=P(axis)):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    pt = Table([
        Column(sds((per * size,), np.dtype(d.storage_dtype)), d, None)
        for d in (dt.INT64, dt.DType(dt.TypeId.DECIMAL64, -2))
    ])
    cnt = sds((size,), jnp.int32)
    monkeypatch.setattr(shuffle, "_ragged_impl", lambda impl: "ragged")
    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    ops = [
        {"op": "partition", "kind": "hash", "keys": [0], "num": size},
        {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]},
        {"op": "project", "exprs": [
            {"col": 0}, {"col": 1},
            {"binary": "gt", "left": {"col": 1},
             "right": {"lit": 30000, "type_id": 26, "scale": -2}}]},
        {"op": "filter", "mask": 2},
    ]
    pre, part, group, tail = planmesh._check_supported(ops, pt, ())
    assert (pre, group.op, len(tail)) == ([], ops[1], 2)

    exchange_fn = planmesh._stage_program(
        "exchange", mesh, axis, pt, pre, part, [group.op], per, per // size
    )
    lowered = exchange_fn.lower(pt, cnt, sds((size, size), jnp.int32), ())
    compiled = lowered.compile()
    assert "ragged-all-to-all" in compiled.as_text()
    _fits(compiled)

    state = jax.tree_util.tree_map(
        lambda o: sds(o.shape, o.dtype), lowered.out_info[0]
    )
    groupby_fn = planmesh._stage_program(
        "groupby", mesh, axis, state, [group.op], group.again, tail,
        cap=group_cap,
    )
    assert groupby_fn.__name__ == "srt_mesh_groupby"
    reduced = groupby_fn.lower(state, cnt).compile()
    # the per-group half sorts nothing: searches and gathers at the
    # group bucket over the state the exchange program sorted
    assert " sort(" not in reduced.as_text()
    _fits(reduced)
