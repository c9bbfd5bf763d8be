#!/usr/bin/env python3
"""First-contact smoke: the served plan path on a real TPU, at real size.

Drives ``serving.Client`` -> ``serving.Server`` -> scheduler ->
``plancheck`` -> ``plan.run_plan`` -> device -> download in ONE process
(the server is threads, so one process holds the chip) and checks every
answer against pandas / the XLA row-conversion backend. It is a proof
that the system starts and answers correctly on the chip — the seconds
it prints are a smoke's, not a benchmark's.

    python chip_smoke.py                  # one chip, full size
    python chip_smoke.py --chips 4        # the mesh exchange and its twin only
    python chip_smoke.py --tiny --allow-cpu-rehearsal   # CPU rehearsal

Each phase prints one JSON line. The last line of a passing TPU run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
without a TPU the script exits non-zero and never prints it. Any wrong
answer, exception or non-zero fallback counter is a non-zero exit.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import kernels, serving
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config, metrics

FULL = {"fact": 8_000_000, "stream": 1_000_000, "rows": 4_000_000}
TINY = {"fact": 20_000, "stream": 3_000, "rows": 5_000}
ITEMS = 10_000
STREAM_BATCHES = 4
#: float64 sums are cumsum differences (ops/groupby._sorted_segment_sum):
#: their error grows with the running total of the whole column, not
#: with the group, so the tolerance is F64_TOL x sum(|price|).
F64_TOL = 1e-12

#: Counters that must stay zero: each one is a path that quietly gave
#: way to a slower or smaller one.
ZERO_COUNTERS = (
    "plan.fallbacks", "bucket.fallback_errors", "kernel.fallbacks",
    "mesh.degraded",
)


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def counters(names) -> dict:
    return {k: int(v) for k, v in metrics.counter_values(list(names)).items()}


def compile_misses() -> int:
    return counters(["compile_cache.miss"])["compile_cache.miss"]


# ---------------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------------


def wire(cols) -> tuple:
    """[(type_id, scale, ndarray, valid-or-None)] -> the 5-tuple wire batch."""
    n = len(cols[0][2])
    return (
        [int(c[0]) for c in cols], [int(c[1]) for c in cols],
        [np.ascontiguousarray(c[2]).tobytes() for c in cols],
        [None if c[3] is None else c[3].astype(np.uint8).tobytes()
         for c in cols],
        n,
    )


def unwire(batch, np_dtypes) -> list:
    """Wire batch -> [(values, valid-or-None)] for fixed-width columns."""
    _, _, datas, valids, n = batch
    out = []
    for d, v, npdt in zip(datas, valids, np_dtypes):
        vals = np.frombuffer(d, dtype=npdt, count=n)
        valid = None if v is None else np.frombuffer(v, np.uint8, n) != 0
        out.append((vals, valid))
    return out


def block(srv, client, table_id) -> None:
    """Wait until the served result is on the device (not just enqueued)."""
    with srv._lock:
        sess = srv._sessions[client.session]
    jax.block_until_ready(rb._resident_get(sess.rb_id(table_id)))


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def make_fact(rng, n: int) -> dict:
    """store_sales-shaped fact: item key, store key, quantity, price."""
    return {
        "item": rng.integers(0, ITEMS, n, dtype=np.int64),
        "store": rng.integers(0, 400, n, dtype=np.int64),
        "qty": rng.integers(1, 100, n, dtype=np.int64),
        "price": rng.integers(50, 30_000, n).astype(np.float64) / 100.0,
    }


def fact_wire(f: dict, with_store: bool = True) -> tuple:
    i64, f64, b8 = dt.TypeId.INT64, dt.TypeId.FLOAT64, dt.TypeId.BOOL8
    cols = [(i64, 0, f["item"], None)]
    if with_store:
        cols.append((i64, 0, f["store"], None))
    cols += [
        (i64, 0, f["qty"], None), (f64, 0, f["price"], None),
        # the predicate is the client's: WHERE quantity > 20
        (b8, 0, (f["qty"] > 20).astype(np.uint8), None),
    ]
    return wire(cols)


def check_groups(got, want_df, label: str) -> tuple:
    """Exact on integer columns, an absolute tolerance on the float64
    sum; returns (largest absolute error seen, the tolerance)."""
    (k, _), (sq, _), (cq, _), (sp, _) = got[:4]
    if len(k) != len(want_df):
        raise AssertionError(
            f"{label}: {len(k)} groups, pandas has {len(want_df)}"
        )
    for name, arr in (("item", k), ("sum_qty", sq), ("count_qty", cq)):
        if not np.array_equal(arr, want_df[name].to_numpy()):
            raise AssertionError(f"{label}: column {name} differs from pandas")
    want = want_df["sum_price"].to_numpy()
    tol = F64_TOL * float(np.abs(want).sum())
    err = float(np.max(np.abs(sp - want))) if len(want) else 0.0
    if not err <= tol:
        raise AssertionError(
            f"{label}: float64 sum off by {err:.3e} (> {tol:.3e})"
        )
    return err, tol


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

AGGS = [
    {"column": 2, "agg": "sum"}, {"column": 2, "agg": "count"},
    {"column": 3, "agg": "sum"},
]


def phase_resident(srv, rng, n: int):
    """Each phase is a generator: set-up and the cold pass run up to
    its first ``yield`` (main() runs those of all phases side by side,
    so their compiles overlap); the warm pass and the phase line come
    after it, one phase at a time."""
    import pandas as pd

    fact = make_fact(rng, n)
    # dimension: every third item is missing, so the join filters too
    dim_item = np.arange(0, ITEMS, dtype=np.int64)
    dim_item = dim_item[dim_item % 3 != 0]
    dim_cat = (dim_item * 7) % 100
    plan = [
        {"op": "filter", "mask": 4},
        {"op": "join", "on": [0]},
        # sums and counts only: a min/max aggregation adds minutes to
        # the TPU compile of this program (CHANGES.md PR 23)
        {"op": "groupby", "by": [0], "aggs": AGGS},
        # ORDER BY sum(quantity) DESC, item: runs at the bucket of the
        # 6,666 groups, not of the 8M-row input (bucketed._reduce_groups)
        {"op": "sort_by", "keys": [
            {"column": 1, "ascending": False}, {"column": 0}]},
    ]
    df = pd.DataFrame(fact)
    df = df[df["qty"] > 20].merge(
        pd.DataFrame({"item": dim_item, "cat": dim_cat}), on="item"
    )
    want = df.groupby("item", sort=True).agg(
        sum_qty=("qty", "sum"), count_qty=("qty", "count"),
        sum_price=("price", "sum"),
    ).reset_index().sort_values(
        ["sum_qty", "item"], ascending=[False, True], kind="stable"
    )

    i64 = dt.TypeId.INT64
    with serving.Client(srv.port, timeout=1200.0) as c:
        t0 = time.perf_counter()
        ft = c.upload(fact_wire(fact))
        dm = c.upload(
            wire([(i64, 0, dim_item, None), (i64, 0, dim_cat, None)])
        )
        block(srv, c, ft)
        upload_s = time.perf_counter() - t0

        def one_pass():
            t0 = time.perf_counter()
            out = c.plan(plan, [ft, dm])
            block(srv, c, out)
            took = time.perf_counter() - t0
            # rows are compared in the order sort_by left them in
            got = unwire(
                c.download(out), [np.int64, np.int64, np.int64, np.float64]
            )
            c.free(out)
            return (took, *check_groups(got, want, "resident plan"))

        cold_s, _, _ = one_pass()
        yield
        m0 = compile_misses()
        warm_s, err, tol = one_pass()
        warm_compiles = compile_misses() - m0
        c.free(ft)
        c.free(dm)
    if warm_compiles != 0:
        raise AssertionError(
            f"resident plan: second run compiled {warm_compiles} programs"
        )
    emit({
        "phase": "resident_plan", "rows": n, "dim_rows": len(dim_item),
        "groups": len(want), "upload_s": upload_s, "cold_s": cold_s,
        "warm_s": warm_s, "compiles_warm": warm_compiles,
        "f64_sum_max_abs_err": err, "f64_sum_abs_tol": tol,
        "integers": "exact",
    })


def phase_stream(srv, rng, n: int):
    import pandas as pd

    plan = [
        {"op": "filter", "mask": 3},
        {"op": "groupby", "by": [0], "aggs": [
            {"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"},
            {"column": 2, "agg": "sum"}]},
    ]
    facts = [make_fact(rng, n) for _ in range(STREAM_BATCHES)]
    batches = [fact_wire(f, with_store=False) for f in facts]
    wants = []
    for f in facts:
        df = pd.DataFrame(f)
        wants.append(df[df["qty"] > 20].groupby("item", sort=True).agg(
            sum_qty=("qty", "sum"), count_qty=("qty", "count"),
            sum_price=("price", "sum"),
        ).reset_index())
    with serving.Client(srv.port, timeout=1200.0) as c:

        def one_pass():
            t0 = time.perf_counter()
            # results come back as host bytes: the work is finished
            results = c.stream(plan, batches)
            took = time.perf_counter() - t0
            err = tol = 0.0
            for i, (want, res) in enumerate(zip(wants, results)):
                got = unwire(
                    res, [np.int64, np.int64, np.int64, np.float64]
                )
                order = np.argsort(got[0][0], kind="stable")
                got = [(v[order], None) for v, _ in got]
                e, tol = check_groups(got, want, f"stream batch {i}")
                err = max(err, e)
            return took, err, tol

        cold_s, _, _ = one_pass()
        yield
        m0 = compile_misses()
        warm_s, err, tol = one_pass()
        warm_compiles = compile_misses() - m0
    if warm_compiles != 0:
        raise AssertionError(
            f"stream: second run compiled {warm_compiles} programs"
        )
    emit({
        "phase": "stream", "batches": STREAM_BATCHES, "rows_per_batch": n,
        "cold_s": cold_s, "warm_s": warm_s, "compiles_warm": warm_compiles,
        "f64_sum_max_abs_err": err, "f64_sum_abs_tol": tol,
        "integers": "exact",
    })


def phase_rows(srv, rng, n: int):
    """RowConversionTest's 8-column schema through to_rows -> from_rows."""
    from spark_rapids_jni_tpu import rows as rows_mod
    from spark_rapids_jni_tpu.column import Column, Table

    T = dt.TypeId
    schema = [
        (T.INT64, 0, np.int64), (T.FLOAT64, 0, np.float64),
        (T.INT32, 0, np.int32), (T.BOOL8, 0, np.uint8),
        (T.FLOAT32, 0, np.float32), (T.INT8, 0, np.int8),
        (T.DECIMAL32, -3, np.int32), (T.DECIMAL64, -8, np.int64),
    ]
    cols = []
    for tid, scale, npdt in schema:
        if tid == T.BOOL8:
            vals = rng.integers(0, 2, n).astype(np.uint8)
        elif np.issubdtype(npdt, np.floating):
            vals = rng.standard_normal(n).astype(npdt)
        else:
            info = np.iinfo(npdt)
            vals = rng.integers(info.min // 2, info.max // 2, n).astype(npdt)
        cols.append((tid, scale, vals, rng.random(n) > 0.1))
    dtypes = [dt.DType(tid, scale) for tid, scale, _ in schema]
    # the reference bytes: the XLA backend on the same table
    table = Table([
        Column.from_numpy(
            v.astype(np.bool_) if tid == T.BOOL8 else v, valid, dtype=d
        )
        for (tid, _, v, valid), d in zip(cols, dtypes)
    ])
    want_rows = np.concatenate(
        [np.asarray(b.data) for b in rows_mod.to_rows(table, backend="xla")]
    )
    del table
    names = ("row_pack", "row_unpack")
    k0 = kernel_launches(names)
    with serving.Client(srv.port, timeout=1200.0) as c:
        src = c.upload(wire(cols))
        block(srv, c, src)

        def one_pass():
            t0 = time.perf_counter()
            packed = c.plan([{"op": "to_rows"}], [src])
            back = c.plan([{
                "op": "from_rows",
                "type_ids": [int(t) for t, _, _ in schema],
                "scales": [s for _, s, _ in schema],
            }], [packed])
            block(srv, c, back)
            took = time.perf_counter() - t0
            _, _, datas, _, got_n = c.download(packed)
            # LIST<UINT8> wire: int32 offsets[n+1] then the row bytes
            payload = np.frombuffer(datas[0], np.uint8, offset=4 * (got_n + 1))
            if got_n != n or not np.array_equal(
                payload, want_rows.reshape(-1)
            ):
                raise AssertionError(
                    "to_rows bytes differ from the XLA backend"
                )
            got = unwire(c.download(back), [s[2] for s in schema])
            for i, ((vals, valid), (_, _, src_vals, src_valid)) in enumerate(
                zip(got, cols)
            ):
                if valid is None or not np.array_equal(valid, src_valid):
                    raise AssertionError(f"from_rows: validity of column {i}")
                a, b = vals[src_valid], src_vals[src_valid]
                if a.tobytes() != b.tobytes():
                    raise AssertionError(f"from_rows: values of column {i}")
            c.free(packed)
            c.free(back)
            return took

        cold_s = one_pass()
        yield
        warm_s = one_pass()
        c.free(src)
    launches = {
        k: v - k0[k] for k, v in kernel_launches(names).items()
    }
    if min(launches.values()) <= 0:
        raise AssertionError(
            f"row kernels did not launch through the registry: {launches}"
        )
    emit({
        "phase": "row_conversion", "rows": n, "row_bytes": int(
            want_rows.shape[1]), "cold_s": cold_s, "warm_s": warm_s,
        "kernel_launches": launches,
        "interpret": kernels.default_interpret(),
    })


def kernel_launches(names) -> dict:
    """Per-kernel launch counts: the registry's span count per name."""
    timers = metrics.snapshot().get("timers", {})
    return {
        n: int((timers.get(f"kernel.{n}") or {}).get("count", 0))
        for n in names
    }


@contextlib.contextmanager
def watch_mesh_stage():
    """What the served mesh stage itself packs, exchanges and gathers.

    The stage records nothing about placement, so the smoke looks over
    its shoulder: the three functions every mesh partition stage calls
    (parallel/planmesh.py) are wrapped for the length of the session and
    note the sharded table the stage packed, the exchange implementation
    it traced, and the sharded result it gathered from."""
    from spark_rapids_jni_tpu.parallel import planmesh, shuffle

    seen = {"packed": [], "impl": [], "gathered": []}
    real = (planmesh._pack_sharded, shuffle.exchange_ragged,
            planmesh._gather_prefix)

    def pack(table, mesh, axis, n):
        pt, cnt = real[0](table, mesh, axis, n)
        seen["packed"].append(
            (shard_devices(pt), sorted(d.id for d in mesh.devices.flat))
        )
        return pt, cnt

    def exchange(local, dest, counts, out_size, axis, impl, **kw):
        seen["impl"].append(impl)
        return real[1](local, dest, counts, out_size, axis, impl, **kw)

    def gather(out_t, out_c, size):
        seen["gathered"].append(
            (shard_devices(out_t), np.asarray(out_c).tolist())
        )
        return real[2](out_t, out_c, size)

    planmesh._pack_sharded = pack
    shuffle.exchange_ragged = exchange
    planmesh._gather_prefix = gather
    try:
        yield seen
    finally:
        (planmesh._pack_sharded, shuffle.exchange_ragged,
         planmesh._gather_prefix) = real


def phase_mesh(srv, rng, n: int, chips: int) -> None:
    """The exchange on a mesh session against its single-device twin.

    The mesh path runs row-local chains around one ``partition``
    boundary (parallel/planmesh.py); the groupby that follows is a plan
    of its own over the exchanged table, as Spark's stage after a
    ShuffleExchangeExec is — a plan without a partition has no mesh
    path, so on BOTH sessions it runs on one device: its comparison
    shows the exchanged bytes aggregate to the right answer, not that a
    groupby ran on the mesh."""
    import pandas as pd

    fact = make_fact(rng, n)
    batch = fact_wire(fact)
    exchange = [
        {"op": "filter", "mask": 4},
        {"op": "partition", "kind": "hash", "keys": [0], "num": chips},
    ]
    groupby = [{"op": "groupby", "by": [0], "aggs": AGGS}]
    df = pd.DataFrame(fact)
    want = df[df["qty"] > 20].groupby("item", sort=True).agg(
        sum_qty=("qty", "sum"), count_qty=("qty", "count"),
        sum_price=("price", "sum"),
    ).reset_index()

    names = ["plan.mesh_segments", "plan.mesh_fallbacks", "mesh.degraded",
             "shuffle.retries", "partition.mesh_segments",
             "partition.rows_exchanged"]
    c0 = counters(names)
    outs, secs = {}, {}
    with watch_mesh_stage() as seen:
        for label, mesh in (("mesh", chips), ("single", None)):
            # mesh-backed execution is the stream command's: each
            # batch's plan is offered to the session's MeshRunner
            with serving.Client(srv.port, timeout=1200.0, mesh=mesh) as c:
                t0 = time.perf_counter()
                (part,) = c.stream(exchange, [batch])
                secs[label + "_exchange_s"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                (out,) = c.stream(groupby, [part])
                secs[label + "_groupby_s"] = time.perf_counter() - t0
                outs[label] = (part, out)
    delta = {k: v - c0[k] for k, v in counters(names).items()}
    # exactly one mesh stage was served: the mesh session's exchange
    if [len(v) for v in seen.values()] != [1, 1, 1]:
        raise AssertionError(f"served mesh stages: {seen}")
    (packed_on, mesh_ids), = seen["packed"]
    (impl,) = seen["impl"]
    (gathered_on, recv), = seen["gathered"]
    want_impl = "ragged" if kernels.on_tpu() else "dense_compact"
    if impl != want_impl:
        raise AssertionError(f"exchange ran as {impl!r}, not {want_impl!r}")
    if len(mesh_ids) != chips or not (
        sorted(packed_on) == sorted(gathered_on) == mesh_ids
    ):
        raise AssertionError(
            f"mesh is {mesh_ids}; the stage packed onto "
            f"{sorted(packed_on)} and gathered from {sorted(gathered_on)}"
        )
    kept = int((fact["qty"] > 20).sum())
    if len(recv) != chips or min(recv) <= 0 or sum(recv) != kept:
        raise AssertionError(
            f"devices received {recv} rows of {kept} exchanged"
        )
    for i, what in enumerate(("partition", "groupby")):
        if not same_wire(outs["mesh"][i], outs["single"][i]):
            raise AssertionError(
                f"mesh {what} bytes differ from the single-device plan"
            )
    got = unwire(outs["mesh"][1], [np.int64, np.int64, np.int64, np.float64])
    order = np.argsort(got[0][0], kind="stable")
    err, tol = check_groups(
        [(v[order], None) for v, _ in got], want, "mesh plan"
    )
    if delta["plan.mesh_segments"] != 1 or \
            delta["partition.mesh_segments"] != 1:
        raise AssertionError(f"the exchange did not run on the mesh: {delta}")
    for k in ("plan.mesh_fallbacks", "mesh.degraded", "shuffle.retries"):
        if delta[k]:
            raise AssertionError(f"mesh path degraded or retried: {delta}")
    emit({
        "phase": "mesh_plan", "rows": n, "chips": chips, "exchange": impl,
        **secs, "byte_identical": True, "packed_on_devices": sorted(packed_on),
        "gathered_from_devices": sorted(gathered_on),
        "received_rows": recv, "groupby_on": "one device, both sessions",
        "f64_sum_max_abs_err": err, "f64_sum_abs_tol": tol,
        "counters": delta,
    })


def same_wire(a, b) -> bool:
    def eq(x, y):
        return (x is None and y is None) or (
            x is not None and y is not None and bytes(x) == bytes(y)
        )

    return (
        (list(a[0]), list(a[1]), a[4]) == (list(b[0]), list(b[1]), b[4])
        and all(eq(x, y) for x, y in zip(a[2], b[2]))
        and all(eq(x, y) for x, y in zip(a[3], b[3]))
    )


def shard_devices(table) -> set:
    """Device ids that hold a non-empty shard of every buffer of a table."""
    ids = None
    for leaf in jax.tree_util.tree_leaves(table):
        got = {s.device.id for s in leaf.addressable_shards if s.data.size}
        ids = got if ids is None else ids & got
    return ids or set()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="shrink every phase (CPU rehearsal / tier-1 test)")
    ap.add_argument("--rows", type=int, default=None,
                    help="fact-table rows (other phases scale with it)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh exchange and its twin")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true",
                    help="run the phases without a TPU; never prints ok:true")
    args = ap.parse_args(argv)

    sizes = dict(TINY if args.tiny else FULL)
    if args.rows:
        scale = args.rows / sizes["fact"]
        sizes = {k: max(int(v * scale), 64) for k, v in sizes.items()}

    config.place_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    on_tpu = d0.platform == "tpu"
    stats = d0.memory_stats() if on_tpu else None
    emit({
        "phase": "device", "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices),
        "bytes_limit": None if stats is None else stats["bytes_limit"],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    })
    if not on_tpu and not args.allow_cpu_rehearsal:
        print(f"chip_smoke: no TPU (platform={d0.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    config.set_flag("METRICS", True)
    if not on_tpu:
        # the rehearsal runs the same kernels, interpreted
        config.set_flag("KERNELS", "on")

    def rng(k: int):
        return np.random.default_rng([args.seed, k])

    # one tenant at a time on the whole chip: its budget is the device's
    with serving.Server(session_hbm_fraction=1.0, workers=3).start() as srv:
        if args.chips == 4:
            phase_mesh(srv, rng(3), sizes["fact"], 4)
        else:
            phases = [
                phase_resident(srv, rng(0), sizes["fact"]),
                phase_stream(srv, rng(1), sizes["stream"]),
                phase_rows(srv, rng(2), sizes["rows"]),
            ]
            # set-up: every phase's first pass, side by side — what they
            # wait for is XLA compiling, which three sessions can do at
            # once; a phase that fails re-raises here
            t0 = time.perf_counter()
            with ThreadPoolExecutor(len(phases)) as pool:
                list(pool.map(next, phases))
            emit({
                "phase": "setup", "cold_passes_s": time.perf_counter() - t0,
                "compiles": compile_misses(),
            })
            # the warm passes, one phase at a time, and the phase lines
            for phase in phases:
                for _ in phase:
                    pass

    snap = metrics.snapshot()
    gate = counters(ZERO_COUNTERS)
    emit({
        "phase": "no_hidden_downgrade", "counters": gate,
        "kernel": {
            k: v for k, v in sorted(snap.get("counters", {}).items())
            if k.startswith("kernel.")
        },
        "kernel_launches": kernel_launches(("row_pack", "row_unpack")),
        "compile_cache": {
            k: int(snap.get("counters", {}).get(k, 0))
            for k in ("compile_cache.hit", "compile_cache.miss")
        },
    })
    bad = {k: v for k, v in gate.items() if v}
    if bad:
        print(f"chip_smoke: hidden downgrade: {bad}", file=sys.stderr)
        return 1
    # count: the devices this run used, not all the host has
    device = {
        "platform": d0.platform, "kind": d0.device_kind, "count": args.chips,
    }
    if not on_tpu:
        emit({"ok": False, "rehearsal": True, "device": device})
        return 0
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
