"""Benchmark ladder: configs 1-3 on the attached device.

Prints ONE JSON line whose primary metric is the 100M-row groupby-sum
(config 1 at scale) and whose `configs` array carries the full measured
ladder:

  config 1  hash groupby-sum at 1M / 16M / 100M int64 rows, vs CPU Arrow
  config 2  row<->columnar transpose + cast/binaryop round trip
  config 3  100M-row hash inner join (two-phase) + 100M-row sort

Methodology (hardened per round-2 review — and corrected):
  - SYNC BY HOST FETCH: every timed region ends with a one-element
    host fetch that forces the computation (``_sync``). The next
    benchmark replaces it with ``jax.block_until_ready``, as
    ``chip_smoke.py`` already times.
  - FRESH inputs per repetition where feasible (cycled tables), median +
    min + spread over all reps, not best-of-N alone.
  - every entry carries achieved bytes/s against the HBM peak
    (v5e ~819 GB/s) as a bandwidth sanity line.
  - numerical sanity asserts per config (sums match numpy oracles).
"""

import json
import statistics
import sys
import time

import numpy as np


def _progress(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _flight_tail(limit=40):
    """Last N flight-recorder events, or None when the recorder is off
    or the package is absent — the timeline of what the process was
    doing in the seconds before a failure."""
    try:
        from spark_rapids_jni_tpu.utils import flight

        if not flight.enabled():
            return None
        return flight.tail_records(limit) or None
    except Exception:
        return None


def _flight_note(name, arg=None):
    """One instant event on the flight recorder (lazy import, never
    raises): probe retries and fast-fail decisions must appear in the
    postmortem timeline next to the spans they interrupted."""
    try:
        from spark_rapids_jni_tpu.utils import flight

        flight.record("I", name, arg)
    except Exception:
        pass


def _classify_failure_text(type_name, message) -> str:
    """Taxonomy class name for a failure's (type, message) text via
    faults.classify_text — the shared classifier replacing this file's
    historical ad-hoc marker list."""
    try:
        from spark_rapids_jni_tpu.utils import faults

        return faults.classify_text(
            str(type_name or ""), str(message or "")
        ).__name__
    except Exception:
        return "PermanentError"


def _failure_record(
    name, error, exc_type=None, elapsed_s=None, retries=0, skipped=False,
    backoff_ms=0.0,
):
    """Structured failure entry: exception type, message, taxonomy
    class, elapsed time and retry/backoff counts, so a killed ladder is
    diagnosable from the JSON alone (rounds 1-5 died with bare
    '"error": "device unreachable"' strings and no telemetry). The flat
    "error" string stays for old readers; "failure" is the structured
    record. ``skipped=True`` marks a config that was never attempted
    (budget exhausted / fast-fail after the device went away) as
    opposed to one that ran and died.
    When the flight recorder is on, a record for a config that actually
    RAN and died also carries ``flight_tail`` — the last events before
    the failure, the input of ``tools/trace2chrome.py`` — so "device
    unreachable" is never again a bare string. Skip records
    (``skipped=True``) stay lean: a fast-fail batch would otherwise
    embed N byte-identical tails into the headline JSON; the config
    that triggered the fast-fail carries the one that matters."""
    msg = str(error)[:300]
    tname = exc_type or (
        type(error).__name__ if isinstance(error, BaseException)
        else "Error"
    )
    failure = {
        "type": tname,
        "message": msg,
        "class": _classify_failure_text(tname, msg),
        "elapsed_s": (
            round(float(elapsed_s), 3) if elapsed_s is not None else None
        ),
        "retries": int(retries),
        "backoff_ms": round(float(backoff_ms), 2),
        "skipped": bool(skipped),
    }
    if not skipped:
        tail = _flight_tail()
        if tail:
            failure["flight_tail"] = tail
    return {"name": name, "error": msg, "failure": failure}


def _unreachable_failure(entry) -> bool:
    """True when a failure entry smells like the device died
    (vs a genuine per-config crash) — i.e. it classifies transient
    under the shared fault taxonomy (faults.classify_text subsumes the
    marker list this file used to keep by hand)."""
    f = entry.get("failure") or {}
    return _classify_failure_text(
        f.get("type", ""),
        f"{f.get('message', '')} {entry.get('error', '')}",
    ) == "TransientDeviceError"


def _metrics_enable():
    """Turn the metrics AND flight-recorder planes on for this process
    (lazy import so the bench stays runnable from a checkout without
    the package installed). The flight recorder is the crash telemetry:
    its tail rides in every structured failure record and is flushed to
    SPARK_RAPIDS_TPU_FLIGHT_DUMP from the SIGTERM handler."""
    import os
    import tempfile

    try:
        from spark_rapids_jni_tpu.utils import config as _srt_config

        _srt_config.set_flag("METRICS", True)
        _srt_config.set_flag("FLIGHT", True)
        _srt_config.set_flag("PROFILE", "on")
        # plan-stats store: a per-run directory (inherited by the
        # config subprocesses through the environment) so every arm's
        # run_plan executions land drift-comparable records the
        # headline's "drift" block summarizes
        pdir = os.path.join(
            tempfile.gettempdir(), f"srt-bench-planstats-{os.getpid()}"
        )
        # srt: allow-env-read(dir must ride env into config subprocesses)
        pdir = os.environ.setdefault(
            "SPARK_RAPIDS_TPU_PLANSTATS_DIR", pdir
        )
        _srt_config.set_flag("PLANSTATS_DIR", pdir)
    except Exception:
        pass


def _drift_block():
    """Compact drift summary from this run's plan-stats store for the
    headline JSON (record/plan counts + findings by type), or None when
    the store is absent/empty — old readers never see the key change
    shape."""
    try:
        from spark_rapids_jni_tpu.utils import planstats as _srt_planstats

        return _srt_planstats.summary()
    except Exception:
        return None


def _flush_telemetry():
    """Write the metrics snapshot and flight-recorder tail to their
    configured dump paths NOW. Called from the SIGTERM handler (which
    os._exit's, skipping atexit) so an rc=124 run still leaves its
    telemetry behind; cheap and exception-free by construction."""
    try:
        from spark_rapids_jni_tpu.utils import flight as _srt_flight
        from spark_rapids_jni_tpu.utils import metrics as _srt_metrics
        from spark_rapids_jni_tpu.utils import profiler as _srt_profiler

        _srt_metrics.dump()
        _srt_flight.dump()
        _srt_profiler.dump()
    except Exception:
        pass


def _metrics_snapshot(reset=False):
    """Current metrics snapshot, or None when the package is absent.
    ``reset=True`` clears the registry afterward so consecutive
    in-process configs get per-config blocks, not cumulative ones."""
    try:
        from spark_rapids_jni_tpu.utils import metrics as _srt_metrics

        snap = _srt_metrics.snapshot()
        if reset:
            _srt_metrics.reset()
        return snap
    except Exception:
        return None


def _profile_block(reset=False):
    """Aggregated per-segment profiler summary for this config's
    sessions (utils/profiler.summarize), or None when the package is
    absent or no session ran. ``reset=True`` clears the session
    registry afterward — the _metrics_snapshot discipline, so
    consecutive in-process configs get per-config blocks."""
    try:
        from spark_rapids_jni_tpu.utils import profiler as _srt_profiler

        docs = _srt_profiler.sessions(reset=reset)
        if not docs:
            return None
        block = _srt_profiler.summarize(docs)
        # keep the LAST few full session docs for tools/explain.py;
        # the aggregate above is the compact per-config story
        block["sessions_tail"] = docs[-3:]
        return block
    except Exception:
        return None


HBM_PEAK_GBPS = {"tpu": 819.0}  # v5e HBM bandwidth


def _sync(out):
    """Force completion: fetch ONE element of the first array leaf.

    All outputs of a jitted call belong to one executable, so fetching
    any element of any output waits for the whole computation. A full
    np.asarray(out) would instead time the transfer of the entire
    result."""
    import jax

    leaves = [l for l in jax.tree.leaves(out) if hasattr(l, "dtype")]
    if leaves:
        np.asarray(leaves[0].ravel()[-1])
    return out


def _timeit(fn, inputs, reps_per_input=3):
    """Time fn over (cycled) inputs; returns (median, min, std, last_out)."""
    out = _sync(fn(*inputs[0]))  # compile/warmup
    times = []
    for _ in range(reps_per_input):
        for inp in inputs:
            t0 = time.perf_counter()
            out = _sync(fn(*inp))
            times.append(time.perf_counter() - t0)
    return (
        statistics.median(times),
        min(times),
        statistics.pstdev(times),
        out,
    )


def _entry(config, name, rows, med, mn, std, bytes_moved, platform):
    peak = HBM_PEAK_GBPS.get(platform)
    gbps = bytes_moved / med / 1e9
    e = {
        "config": config,
        "name": name,
        "rows": rows,
        "seconds_median": round(med, 6),
        "seconds_min": round(mn, 6),
        "spread": round(std / med, 3) if med else None,
        "rows_per_s": round(rows / med, 1),
        "achieved_gbps": round(gbps, 2),
    }
    if peak:
        e["hbm_peak_gbps"] = peak
        e["hbm_frac"] = round(gbps / peak, 4)
    return e


def _gen_groupby_inputs(n, n_inputs=2, n_keys=10_000):
    """Shared config-1 data generator: every groupby A/B rung MUST draw
    from this one (same seed, same shape) or the arms stop being
    comparable (the r3 shrink lesson)."""
    import jax

    from spark_rapids_jni_tpu.column import Column, Table

    rng = np.random.default_rng(42)
    hosts = []
    inputs = []
    for _ in range(n_inputs):
        k = rng.integers(0, n_keys, n, dtype=np.int64)
        v = rng.integers(-1000, 1000, n, dtype=np.int64)
        hosts.append((k, v))
        t = Table([Column.from_numpy(k), Column.from_numpy(v)], ["k", "v"])
        jax.block_until_ready(t.columns[0].data)
        inputs.append((t,))
    return hosts, inputs


def bench_groupby(platform, n, n_inputs=2, values_via="sort"):
    import jax

    from spark_rapids_jni_tpu.ops.groupby import (
        GroupbyAgg,
        groupby_aggregate_capped,
    )

    n_keys = 10_000
    hosts, inputs = _gen_groupby_inputs(n, n_inputs, n_keys)

    step = jax.jit(
        lambda t: groupby_aggregate_capped(
            t,
            ["k"],
            [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")],
            num_segments=n_keys,
            values_via=values_via,
        )
    )
    med, mn, std, out = _timeit(step, inputs)
    # sanity: last-run totals must match numpy on the last-cycled input
    agg, ngroups = out
    total = int(np.asarray(agg["sum_v"].data)[: int(ngroups)].sum())
    assert total == int(hosts[-1][1].sum()), "groupby-sum mismatch vs numpy"
    suffix = "" if values_via == "sort" else f"_{values_via}"
    return _entry(
        1, f"groupby_sum_{n // 1_000_000}M{suffix}", n, med, mn, std,
        n * 16, platform,
    ), med


def bench_groupby_chunked(platform, n=100_000_000, n_inputs=2):
    """Config 1 at scale via the two-level chunked design (round-4
    headline): C batched VMEM-sized sorts + a combine pass, vs the
    single giant variadic sort of ``bench_groupby``."""
    import jax

    from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
    from spark_rapids_jni_tpu.ops.groupby_chunked import (
        groupby_aggregate_capped_chunked,
    )

    n_keys = 10_000
    chunk_rows = 1 << 18
    chunk_segments = 1 << 15  # 10k keys/chunk worst case + headroom
    hosts, inputs = _gen_groupby_inputs(n, n_inputs)

    step = jax.jit(
        lambda t: groupby_aggregate_capped_chunked(
            t,
            ["k"],
            [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")],
            num_segments=n_keys,
            chunk_rows=chunk_rows,
            chunk_segments=chunk_segments,
        )
    )
    med, mn, std, out = _timeit(step, inputs)
    agg, ngroups, max_chunk = out
    assert int(max_chunk) <= chunk_segments, "chunk capacity overflow"
    total = int(np.asarray(agg["sum_v"].data)[: int(ngroups)].sum())
    assert total == int(hosts[-1][1].sum()), "groupby-sum mismatch vs numpy"
    return _entry(
        1, f"groupby_sum_{n // 1_000_000}M_chunked", n, med, mn, std,
        n * 16, platform,
    )


def bench_groupby_packed(platform, n=100_000_000, n_inputs=2,
                         engine="lax", chunk_rows=1 << 18,
                         chunk_segments=1 << 14):
    """Config 1 at scale via the packed-key formulation: ONE u64 sort
    word ((key-kmin)<<18 | iota) per row instead of (occupancy, key,
    iota, row_valid) — ~1.8x less sort traffic than the chunked path on
    the same shape, ties impossible so stability is free. The A/B vs
    groupby100m_chunked/groupby100m decides the headline formulation."""
    import jax

    from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
    from spark_rapids_jni_tpu.ops.groupby_packed import (
        groupby_aggregate_packed_chunked,
    )

    n_keys = 10_000
    hosts, inputs = _gen_groupby_inputs(n, n_inputs, n_keys)

    step = jax.jit(
        lambda t: groupby_aggregate_packed_chunked(
            t,
            ["k"],
            [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")],
            num_segments=n_keys,
            chunk_rows=chunk_rows,
            chunk_segments=chunk_segments,
            engine=engine,
        )
    )
    med, mn, std, out = _timeit(step, inputs)
    agg, ngroups, max_chunk, overflow = out
    assert not bool(overflow), "packed range overflow"
    assert int(max_chunk) <= chunk_segments, "chunk capacity overflow"
    total = int(np.asarray(agg["sum_v"].data)[: int(ngroups)].sum())
    assert total == int(hosts[-1][1].sum()), "groupby-sum mismatch vs numpy"
    suffix = "" if engine == "lax" else f"_{engine}"
    return _entry(
        1, f"groupby_sum_{n // 1_000_000}M_packed{suffix}", n, med, mn,
        std, n * 16, platform,
    )


def bench_groupby_flat(platform, n=16_000_000, values_via="sort",
                       n_inputs=2):
    """Single-level flat-packed groupby on the LOW-cardinality headline
    shape: one u64 word (key<<iota_bits | iota) through ONE full-column
    sort — no chunking, no combine. ``values_via`` A/Bs carrying values
    as sort payloads vs a word-only sort plus permutation gather."""
    import jax

    from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
    from spark_rapids_jni_tpu.ops.groupby_packed import (
        groupby_aggregate_packed_flat,
    )

    n_keys = 10_000
    hosts, inputs = _gen_groupby_inputs(n, n_inputs, n_keys)

    step = jax.jit(
        lambda t: groupby_aggregate_packed_flat(
            t,
            ["k"],
            [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")],
            num_segments=n_keys,
            values_via=values_via,
        )
    )
    med, mn, std, out = _timeit(step, inputs)
    agg, ngroups, overflow = out
    assert not bool(overflow), "flat packed overflow"
    total = int(np.asarray(agg["sum_v"].data)[: int(ngroups)].sum())
    assert total == int(hosts[-1][1].sum()), "groupby-sum mismatch vs numpy"
    return _entry(
        1, f"groupby_sum_{n // 1_000_000}M_flat_{values_via}", n, med,
        mn, std, n * 16, platform,
    )


def bench_groupby_highcard(platform, n=100_000_000, n_keys=50_000_000):
    """High-cardinality A/B in one config: the general single-pass
    capped groupby vs the FLAT packed formulation on the same 50M-key
    shape (per-chunk dedup can't win here; the question is whether the
    one-narrow-word sort beats the multi-word single-pass sort)."""
    import jax

    from spark_rapids_jni_tpu.column import Column, Table
    from spark_rapids_jni_tpu.ops.groupby import (
        GroupbyAgg,
        groupby_aggregate_capped,
    )
    from spark_rapids_jni_tpu.ops.groupby_packed import (
        groupby_aggregate_packed_flat,
    )

    rng = np.random.default_rng(44)
    k = rng.integers(0, n_keys, n, dtype=np.int64)
    v = rng.integers(-1000, 1000, n, dtype=np.int64)
    t = Table([Column.from_numpy(k), Column.from_numpy(v)], ["k", "v"])
    jax.block_until_ready(t.columns[0].data)
    aggs = [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")]
    want_total = int(v.sum())

    single = jax.jit(
        lambda tt: groupby_aggregate_capped(
            tt, ["k"], aggs, num_segments=n_keys
        )
    )
    med_s, mn_s, std_s, out_s = _timeit(single, [(t,)], reps_per_input=2)
    agg_s, ng_s = out_s
    tot = int(np.asarray(agg_s["sum_v"].data)[: int(ng_s)].sum())
    assert tot == want_total, "single-pass highcard sum mismatch"

    flat = jax.jit(
        lambda tt: groupby_aggregate_packed_flat(
            tt, ["k"], aggs, num_segments=n_keys
        )
    )
    med_f, mn_f, std_f, out_f = _timeit(flat, [(t,)], reps_per_input=2)
    agg_f, ng_f, ov = out_f
    assert not bool(ov), "flat packed overflow"
    tot = int(np.asarray(agg_f["sum_v"].data)[: int(ng_f)].sum())
    assert tot == want_total, "flat packed highcard sum mismatch"

    e1 = _entry(1, f"groupby_highcard_{n // 1_000_000}M_single", n,
                med_s, mn_s, std_s, n * 16, platform)
    e2 = _entry(1, f"groupby_highcard_{n // 1_000_000}M_packed_flat", n,
                med_f, mn_f, std_f, n * 16, platform)
    e2["vs_single"] = round(med_s / med_f, 2)
    return [e1, e2]


def arrow_baseline(n):
    """CPU Arrow groupby throughput (rows/s) on the config-1 shape."""
    try:
        import pyarrow as pa
    except ImportError:  # pragma: no cover
        return None
    rng = np.random.default_rng(7)
    k = rng.integers(0, 10_000, n, dtype=np.int64)
    v = rng.integers(-1000, 1000, n, dtype=np.int64)
    atbl = pa.table({"k": k, "v": v})
    atbl.group_by("k").aggregate([("v", "sum"), ("v", "count")])  # warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        atbl.group_by("k").aggregate([("v", "sum"), ("v", "count")])
        best = min(best, time.perf_counter() - t0)
    return n / best


def bench_transpose(platform, n=4_000_000, n_inputs=2, backend="xla"):
    """Config 2: to_rows -> from_rows -> cast+binaryop on the result.

    The CudfColumnVector round-trip shape: an 8-column fixed-width table
    (the reference round-trip test schema, RowConversionTest.java:30-39)
    packed to Spark UnsafeRow bytes and back, then a cast and an add to
    stand in for the CudfColumnVector compute step.
    """
    import jax

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import rows as rows_mod
    from spark_rapids_jni_tpu.column import Column, Table
    from spark_rapids_jni_tpu.ops import binaryop
    from spark_rapids_jni_tpu.ops.cast import cast as cast_fn

    rng = np.random.default_rng(3)
    schema = [
        dt.INT64, dt.FLOAT64, dt.INT32, dt.BOOL8,
        dt.FLOAT32, dt.INT8, dt.DType(dt.TypeId.DECIMAL32, -3),
        dt.DType(dt.TypeId.DECIMAL64, -8),
    ]
    layout = rows_mod.compute_fixed_width_layout(schema)

    def make_table():
        cols = []
        for d in schema:
            npdt = np.dtype(d.storage_dtype)
            if d.is_boolean:
                arr = rng.integers(0, 2, n).astype(np.bool_)
            elif d.is_floating:
                arr = rng.standard_normal(n).astype(npdt)
            else:
                info = np.iinfo(npdt)
                arr = rng.integers(
                    info.min // 2, info.max // 2, n, dtype=npdt
                )
            valid = rng.random(n) > 0.1
            cols.append(Column.from_numpy(arr, validity=valid, dtype=d))
        t = Table(cols)
        jax.block_until_ready(t.columns[0].data)
        return t

    inputs = [(make_table(),) for _ in range(n_inputs)]

    def round_trip(t):
        batches = rows_mod.to_rows(t, split=False, backend=backend)
        back = rows_mod.from_rows(batches, schema, backend=backend)
        c = cast_fn(back.columns[0], dt.FLOAT64)
        return binaryop.add(c, back.columns[1])

    med, mn, std, out = _timeit(round_trip, inputs)
    # pack writes + unpack reads the packed bytes, plus column reads/writes
    bytes_moved = n * layout.row_size * 2
    # default arm keeps the historical unsuffixed name (only the new
    # arm suffixes)
    name = (
        "transpose_cast_round_trip"
        if backend == "xla"
        else f"transpose_cast_round_trip_{backend}"
    )
    return _entry(2, name, n, med, mn, std, bytes_moved, platform)


def bench_transpose_pallas(platform, n=4_000_000, n_inputs=2):
    """Config 2 A/B arm: the explicit VMEM-tiled Pallas transpose pair
    (kernels/row_transpose.py) vs the XLA-fused default — r3 measured
    the XLA path at 1.54s/4M rows (~1 GB/s effective), far below what a
    tiled byte repack should do; this decides the default backend."""
    return bench_transpose(platform, n, n_inputs, backend="pallas")


def bench_sort(platform, n=100_000_000):
    """Config 3b: 100M-row single-chip sort (u64-normalized keys),
    payload formulation (what ``sort_table`` ships)."""
    return _bench_sort_formulation(platform, n, "payload")


def bench_sort_gather(platform, n=100_000_000):
    """Config 3b A/B arm: the argsort+gather formulation ``sort_table``
    used before 241d4b6 — measured so the payload-vs-gather switch rests
    on a direct on-chip number, not the round-3 indirect inference
    (groupby's payload sort at 1.08s vs this form's 5.71s)."""
    return _bench_sort_formulation(platform, n, "gather")


def bench_sort_packed_gather(platform, n=100_000_000):
    """Config 3b fourth arm: packed word-only sort + payload gather."""
    return _bench_sort_formulation(platform, n, "packed_gather")


def bench_sort_packed(platform, n=100_000_000):
    """Config 3b third arm: the packed formulation (sort_packed.py) —
    key word, iota AND the key column's payload in ONE u64 (16 B/row of
    operands vs the payload form's 24; bench keys span [0,1e8) < 2^37
    so the shape is eligible)."""
    return _bench_sort_formulation(platform, n, "packed")


def _bench_sort_formulation(platform, n, form):
    import jax

    from spark_rapids_jni_tpu.column import Column, Table
    from spark_rapids_jni_tpu.ops.gather import gather_table
    from spark_rapids_jni_tpu.ops.sort import (
        SortKey,
        argsort_table,
        sort_table,
    )

    rng = np.random.default_rng(13)
    k = rng.integers(0, n, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    t = Table([Column.from_numpy(k), Column.from_numpy(v)], ["k", "v"])
    jax.block_until_ready(t.columns[0].data)
    if form == "payload":
        sort_fn = jax.jit(lambda tt: sort_table(tt, [SortKey("k")]))
    elif form in ("packed", "packed_gather"):
        from spark_rapids_jni_tpu.ops.sort_packed import sort_table_packed

        via = "gather" if form.endswith("gather") else "sort"

        def sort_fn(tt):
            out = sort_table_packed(tt, [SortKey("k")], values_via=via)
            assert out is not None, "packed sort declined the bench shape"
            return out
    else:
        sort_fn = jax.jit(
            lambda tt: gather_table(tt, argsort_table(tt, [SortKey("k")]))
        )
    med, mn, std, out = _timeit(sort_fn, [(t,)], reps_per_input=2)
    head = np.asarray(out["k"].data[:1000])
    assert (np.diff(head) >= 0).all(), "sort output not ordered"
    return _entry(3, f"sort_{n // 1_000_000}M_int64_{form}", n, med, mn,
                  std, n * 16 * 2, platform)


def _join_inputs(n):
    """Shared config-3 join workload: both benches must measure the
    same data shape."""
    import jax

    from spark_rapids_jni_tpu.column import Column, Table

    rng = np.random.default_rng(11)
    kl = rng.integers(0, n, n, dtype=np.int64)
    kr = rng.integers(0, n, n, dtype=np.int64)
    vl = rng.integers(-100, 100, n, dtype=np.int64)
    vr = rng.integers(-100, 100, n, dtype=np.int64)
    left = Table(
        [Column.from_numpy(kl), Column.from_numpy(vl)], ["k", "lv"]
    )
    right = Table(
        [Column.from_numpy(kr), Column.from_numpy(vr)], ["k", "rv"]
    )
    jax.block_until_ready(left.columns[0].data)
    jax.block_until_ready(right.columns[0].data)
    return left, right


def bench_join(platform, n=None):
    """Config 3a: two-phase hash inner join at 100M rows (override
    via SRT_BENCH_JOIN_ROWS for crash triage)."""
    import os

    import jax

    if n is None:
        n = int(os.environ.get("SRT_BENCH_JOIN_ROWS", 100_000_000))

    from spark_rapids_jni_tpu.ops.join import (
        inner_join_capped,
        inner_join_count,
    )

    left, right = _join_inputs(n)

    count_fn = jax.jit(lambda l, r: inner_join_count(l, r, ["k"]))
    total = int(count_fn(left, right))
    # exact capacity rounded to 32 rows, not pow2: at ~100M matches the
    # pow2 rounding wastes ~2.5 GB of HBM across the 3 output columns,
    # which is the difference between fitting and crashing the worker
    cap = max(32, (total + 31) // 32 * 32)
    join_fn = jax.jit(
        lambda l, r: inner_join_capped(l, r, ["k"], capacity=cap)
    )

    def two_phase(l, r):
        c = int(count_fn(l, r))  # phase 1 + the real host sync it implies
        out, cnt = join_fn(l, r)
        return out

    med, mn, std, out = _timeit(
        two_phase, [(left, right)], reps_per_input=2
    )
    # both sides read (16B/row each) + output written (3 int64 cols)
    bytes_moved = 2 * n * 16 + total * 24
    e1 = _entry(
        3, f"inner_join_{n // 1_000_000}M_two_phase", 2 * n, med, mn,
        std, bytes_moved, platform,
    )
    e1["matches"] = total
    return e1


def bench_join_batched(platform, n=None):
    """Config 3a at 100M via the batched probe path. The single-shot
    two-phase join graph (lexsort + lex-searchsorted fused in one jit)
    hits a TPU worker kernel fault at >=32M rows with 64-bit keys
    (reproduced standalone; 16M probes and 100M sorts are fine), so the
    supported 100M path sorts the build side once and probes in 16M
    chunks — the reference's split discipline applied to joins."""
    import os

    from spark_rapids_jni_tpu.ops.join import inner_join_batched

    if n is None:
        n = int(os.environ.get("SRT_BENCH_JOIN_ROWS", 100_000_000))
    left, right = _join_inputs(n)

    def run(l, r):
        return inner_join_batched(l, r, ["k"], probe_rows=16_000_000)

    med, mn, std, out = _timeit(run, [(left, right)], reps_per_input=2)
    matches = out.row_count
    bytes_moved = 2 * n * 16 + matches * 24
    e = _entry(
        3, f"inner_join_{n // 1_000_000}M_batched_probe", 2 * n, med,
        mn, std, bytes_moved, platform,
    )
    e["matches"] = matches
    return e


def bench_join_batched_packed(platform, n=None):
    """Config 3a A/B arm: the packed-key batched join (join_packed.py)
    — one-u64-word build sort (8 B/row vs 20) with the permutation in
    the low bits, native searchsorted probe. Eligible because the bench
    keys span [0, n) and n < 2^37."""
    import os

    from spark_rapids_jni_tpu.ops.join_packed import (
        inner_join_batched_packed,
    )

    if n is None:
        n = int(os.environ.get("SRT_BENCH_JOIN_ROWS", 100_000_000))
    left, right = _join_inputs(n)

    def run(l, r):
        out = inner_join_batched_packed(l, r, ["k"], probe_rows=16_000_000)
        assert out is not None, "packed join declined the bench shape"
        return out

    med, mn, std, out = _timeit(run, [(left, right)], reps_per_input=2)
    matches = out.row_count
    bytes_moved = 2 * n * 16 + matches * 24
    e = _entry(
        3, f"inner_join_{n // 1_000_000}M_batched_packed", 2 * n, med,
        mn, std, bytes_moved, platform,
    )
    e["matches"] = matches
    return e


def bench_bucketed_stream(platform, n_batches=12):
    """Shape-bucket dispatch bench: a ragged stream of ColumnarBatch-
    shaped wire calls (filter -> sort -> groupby per batch, every batch
    a different row count) with pad-to-bucket batching + the compiled-
    executable cache ON vs OFF. COLD timings are the story: the exact
    path compiles every op for every distinct size, the bucketed path
    compiles once per (op, bucket) and then streams on cache hits."""
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu.utils import buckets as buckets_mod
    from spark_rapids_jni_tpu.utils import config as srt_config
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics

    _metrics_enable()  # the cache/pad counters ARE this config's story
    rng = np.random.default_rng(31)
    sizes = sorted(
        int(s) for s in rng.integers(50_000, 140_000, n_batches)
    )
    i64 = int(dt.TypeId.INT64)
    b8 = int(dt.TypeId.BOOL8)
    op_filter = json.dumps({"op": "filter", "mask": 2})
    op_sort = json.dumps({"op": "sort_by", "keys": [{"column": 0}]})
    op_group = json.dumps(
        {"op": "groupby", "by": [0], "aggs": [{"column": 1, "agg": "sum"}]}
    )
    batches = []
    for nn in sizes:
        kk = rng.integers(0, 1000, nn, dtype=np.int64)
        vv = rng.integers(-100, 100, nn, dtype=np.int64)
        mm = (vv > 0).astype(np.uint8)
        batches.append((nn, kk.tobytes(), vv.tobytes(), mm.tobytes()))

    def stream():
        t0 = _time.perf_counter()
        total = 0
        for nn, kb, vb, mb in batches:
            t1 = rb.table_op_wire(
                op_filter, [i64, i64, b8], [0, 0, 0], [kb, vb, mb],
                [None, None, None], nn,
            )
            t2 = rb.table_op_wire(op_sort, t1[0], t1[1], t1[2], t1[3], t1[4])
            t3 = rb.table_op_wire(op_group, t2[0], t2[1], t2[2], t2[3], t2[4])
            total += t3[4]
        return _time.perf_counter() - t0, total

    try:
        srt_config.set_flag("BUCKETS", "off")
        exact_cold_s, exact_total = stream()
        exact_warm_s, _ = stream()
        srt_config.set_flag("BUCKETS", "")
        buckets_mod.cache_clear()
        srt_metrics.reset()  # the entry's metrics block = the ON arm
        on_cold_s, on_total = stream()
        on_warm_s, _ = stream()
    finally:
        srt_config.clear_flag("BUCKETS")
    assert exact_total == on_total, "bucketed stream changed results"
    snap = _metrics_snapshot() or {}
    ctr = snap.get("counters", {})
    hits = int(ctr.get("compile_cache.hit", 0))
    misses = int(ctr.get("compile_cache.miss", 0))
    rows = sum(s[0] for s in batches)
    return {
        "config": "dispatch",
        "name": f"bucketed_dispatch_stream_{n_batches}x3op",
        "rows": rows,
        "distinct_batch_sizes": len(set(sizes)),
        "exact_cold_seconds": round(exact_cold_s, 4),
        "exact_warm_seconds": round(exact_warm_s, 4),
        "bucketed_cold_seconds": round(on_cold_s, 4),
        "bucketed_warm_seconds": round(on_warm_s, 4),
        "cold_speedup": round(exact_cold_s / on_cold_s, 2),
        "compile_cache_hits": hits,
        "compile_cache_misses": misses,
        "pad_waste_bytes": int(
            snap.get("bytes", {}).get("bucket.pad_waste_bytes", 0)
        ),
        "platform": platform,
    }


def bench_fused_plan(platform, n_batches=16):
    """Plan-fusion bench (ISSUE 4 tentpole): the SAME 4-op chain
    (filter -> cast -> sort_by -> groupby) over a ragged stream of
    device-resident tables, dispatched per-op (four executable launches
    + three materialized intermediate tables per batch) vs through
    ``table_plan_resident`` (ONE fused executable launch per batch once
    the cache is warm). Launch counts come from the compile cache's
    hit+miss counters — every cached_jit call is one executable launch
    — and the ``plan.*`` counters ride along in a structured ``fusion``
    block. SRT_BENCH_PLAN_ROWS shrinks the shape for smoke runs
    (ci/smoke-observability.sh drives this config)."""
    import os as _os
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu.utils import buckets as buckets_mod
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics

    _metrics_enable()  # the launch/fusion counters ARE this config's story
    # default shape sits in the launch-overhead-sensitive regime (the
    # regime fusion targets — many small ragged ColumnarBatches);
    # SRT_BENCH_PLAN_ROWS scales it up/down
    base = int(_os.environ.get("SRT_BENCH_PLAN_ROWS", 8_000))
    rng = np.random.default_rng(37)
    sizes = sorted(
        int(s)
        for s in rng.integers(base // 2, base * 3 // 2 + 2, n_batches)
    )
    i64 = int(dt.TypeId.INT64)
    b8 = int(dt.TypeId.BOOL8)
    chain = [
        {"op": "filter", "mask": 2},
        {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
        {"op": "sort_by", "keys": [{"column": 0}]},
        {"op": "groupby", "by": [0],
         "aggs": [{"column": 1, "agg": "sum"},
                  {"column": 1, "agg": "count"}]},
    ]
    batches = []
    for nn in sizes:
        kk = rng.integers(0, 1000, nn, dtype=np.int64)
        vv = rng.integers(-100, 100, nn, dtype=np.int64)
        mm = (vv > 0).astype(np.uint8)
        batches.append((nn, kk.tobytes(), vv.tobytes(), mm.tobytes()))

    def upload(nn, kb, vb, mb):
        return rb.table_upload_wire(
            [i64, i64, b8], [0, 0, 0], [kb, vb, mb],
            [None, None, None], nn,
        )

    def per_op_stream():
        t0 = _time.perf_counter()
        total = 0
        for nn, kb, vb, mb in batches:
            cur = upload(nn, kb, vb, mb)
            for op in chain:
                nxt = rb.table_op_resident(json.dumps(op), [cur])
                rb.table_free(cur)
                cur = nxt
            out = rb.table_download_wire(cur)
            rb.table_free(cur)
            total += out[4]
        return _time.perf_counter() - t0, total

    def fused_stream():
        t0 = _time.perf_counter()
        total = 0
        for nn, kb, vb, mb in batches:
            tid = upload(nn, kb, vb, mb)
            res = rb.table_plan_resident(json.dumps(chain), [tid])
            rb.table_free(tid)
            out = rb.table_download_wire(res)
            rb.table_free(res)
            total += out[4]
        return _time.perf_counter() - t0, total

    def launches(snap):
        c = (snap or {}).get("counters", {})
        return int(c.get("compile_cache.hit", 0)) + int(
            c.get("compile_cache.miss", 0)
        )

    warm_reps = 3  # best-of: one warm pass is scheduler-noise-bound

    buckets_mod.cache_clear()
    srt_metrics.reset()
    per_cold_s, per_total = per_op_stream()
    srt_metrics.reset()
    per_warm_s, _ = per_op_stream()
    per_launches = launches(_metrics_snapshot())
    for _ in range(warm_reps - 1):
        per_warm_s = min(per_warm_s, per_op_stream()[0])
    buckets_mod.cache_clear()
    srt_metrics.reset()
    fused_cold_s, fused_total = fused_stream()
    # reset so the launch count and the entry's metrics block cover
    # only WARM fused passes (no compile-phase noise)
    srt_metrics.reset()
    fused_warm_s, _ = fused_stream()
    snap = _metrics_snapshot() or {}
    fused_launches = launches(snap)
    for _ in range(warm_reps - 1):
        fused_warm_s = min(fused_warm_s, fused_stream()[0])
    ctr = snap.get("counters", {})
    assert per_total == fused_total, "fused plan changed results"
    return {
        "config": "dispatch",
        "name": f"fused_plan_{n_batches}x{len(chain)}op",
        "rows": sum(s[0] for s in batches),
        "distinct_batch_sizes": len(set(sizes)),
        "per_op_cold_seconds": round(per_cold_s, 4),
        "per_op_warm_seconds": round(per_warm_s, 4),
        "fused_cold_seconds": round(fused_cold_s, 4),
        "fused_warm_seconds": round(fused_warm_s, 4),
        "cold_speedup": round(per_cold_s / fused_cold_s, 2),
        "warm_speedup": round(per_warm_s / fused_warm_s, 2),
        "fusion": {
            "chain_ops": len(chain),
            "batches": n_batches,
            "plan_calls": int(ctr.get("plan.calls", 0)),
            "segments": int(ctr.get("plan.segments", 0)),
            "fused_segments": int(ctr.get("plan.fused_segments", 0)),
            "fused_ops": int(ctr.get("plan.fused_ops", 0)),
            "exact_ops": int(ctr.get("plan.exact_ops", 0)),
            "fallbacks": int(ctr.get("plan.fallbacks", 0)),
            "fused_launches": fused_launches,
            "per_op_launches": per_launches,
            "launches_saved": per_launches - fused_launches,
        },
        "platform": platform,
    }


def bench_pipelined_stream(platform, n_batches=12, depth=None):
    """Pipelined-dispatch bench (ISSUE 5 tentpole): the SAME fusable
    3-op chain (filter -> cast -> cast, one fused segment, donation
    eligible) over a ragged stream of wire batches, three ways:

      sync per-op   the repo's SYNCHRONOUS resident-stream idiom
                    (bench_resident_chain / the fused_plan bench's
                    per-op arm): upload -> one ``table_op_resident``
                    per op, each blocking, registry round-trips
                    between ops -> download. The baseline the
                    ``warm_speedup`` headline is measured against.
      sync plan     the PR-4 fused flavor of the same synchronous
                    stream (upload -> ``table_plan_resident`` ->
                    download), reported as ``sync_plan_warm_seconds``
                    / ``vs_plan_sync`` so the fusion and pipelining
                    contributions stay separable.
      pipelined     one ``table_stream_wire`` call with the pipeline
                    on: batch N+1's wire decode and batch N-1's wire
                    encode on background workers while batch N's fused
                    executable (input donated) runs on the caller.

    WARM throughput is the story (every arm reuses cached
    executables); byte parity across all three arms is asserted. The
    structured ``pipeline`` block carries the overlap fraction, stall
    totals and donated bytes. A wide STRING payload column gives the
    serde stages the weight they have on real ColumnarBatches (the
    chain deliberately has no multi-operand sort: serde and compute
    are then comparable, the regime pipelining targets — a
    compute-bound stream pins its ceiling at the compute time either
    way). NOTE on single-core hosts the pipelined margin over the
    PLAN-sync arm is bounded by the amortized per-batch overhead, not
    by overlap — there is no second core to overlap onto; the
    ``host_cpus`` field records what the numbers mean.
    SRT_BENCH_STREAM_ROWS / SRT_BENCH_PIPELINE_DEPTH shrink/tune it
    for smoke runs (ci/smoke-observability.sh drives this config)."""
    import os as _os
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import pipeline as pipeline_mod
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu.utils import config as srt_config
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics

    _metrics_enable()  # the overlap/stall/donation counters ARE the story
    if depth is None:
        depth = int(_os.environ.get("SRT_BENCH_PIPELINE_DEPTH", 2))
    base = int(_os.environ.get("SRT_BENCH_STREAM_ROWS", 120_000))
    rng = np.random.default_rng(41)
    sizes = sorted(
        int(s)
        for s in rng.integers(base // 2, base * 3 // 2 + 2, n_batches)
    )
    i64 = int(dt.TypeId.INT64)
    b8 = int(dt.TypeId.BOOL8)
    s_t = int(dt.TypeId.STRING)
    chain = [
        {"op": "filter", "mask": 2},
        {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)},
        {"op": "cast", "column": 0, "type_id": int(dt.TypeId.INT32)},
    ]
    plan_json = json.dumps(chain)
    op_jsons = [json.dumps(op) for op in chain]
    str_width = 24

    def string_wire(ids):
        # constant-width payload rows, vectorized (python-str loops
        # would dominate setup at bench scale)
        mat = np.full((ids.size, str_width), ord("x"), np.uint8)
        mat[:, 1] = ord("0") + (ids % 8)
        offs = np.arange(ids.size + 1, dtype=np.int32) * str_width
        return offs.tobytes() + mat.tobytes()

    batches = []
    for nn in sizes:
        kk = rng.integers(0, 1000, nn, dtype=np.int64)
        vv = rng.integers(-100, 100, nn, dtype=np.int64)
        mm = (vv > 0).astype(np.uint8)
        batches.append((
            [i64, i64, b8, s_t], [0, 0, 0, 0],
            [kk.tobytes(), vv.tobytes(), mm.tobytes(), string_wire(kk)],
            [None, None, None, None], nn,
        ))

    def per_op_stream():
        t0 = _time.perf_counter()
        outs = []
        for b in batches:
            cur = rb.table_upload_wire(*b)
            for oj in op_jsons:
                nxt = rb.table_op_resident(oj, [cur])
                rb.table_free(cur)
                cur = nxt
            outs.append(rb.table_download_wire(cur))
            rb.table_free(cur)
        return _time.perf_counter() - t0, outs

    def plan_stream():
        t0 = _time.perf_counter()
        outs = []
        for b in batches:
            tid = rb.table_upload_wire(*b)
            res = rb.table_plan_resident(plan_json, [tid])
            rb.table_free(tid)
            outs.append(rb.table_download_wire(res))
            rb.table_free(res)
        return _time.perf_counter() - t0, outs

    def piped_stream():
        t0 = _time.perf_counter()
        outs = rb.table_stream_wire(plan_json, batches)
        return _time.perf_counter() - t0, outs

    warm_reps = 3  # best-of: one warm pass is scheduler-noise-bound
    try:
        srt_config.set_flag("PIPELINE", "off")
        sync_cold_s, sync_outs = per_op_stream()
        sync_warm_s = min(per_op_stream()[0] for _ in range(warm_reps))
        plan_stream()
        plan_warm_s = min(plan_stream()[0] for _ in range(warm_reps))
        off_outs = piped_stream()[1]  # PIPELINE=off == today's sync path
        srt_config.set_flag("PIPELINE", str(depth))
        piped_cold_s, piped_outs = piped_stream()
        # reset so the entry's metrics block and the pipeline numbers
        # cover only WARM pipelined passes (no compile-phase noise);
        # the snapshot is taken AFTER all warm reps so overlap_ms and
        # the wall clock it is divided by cover the same passes
        srt_metrics.reset()
        warm_times = [piped_stream()[0] for _ in range(warm_reps)]
        pipeline_mod.drain()
        snap = _metrics_snapshot() or {}
        piped_warm_s = min(warm_times)
        piped_total_s = sum(warm_times)
    finally:
        srt_config.clear_flag("PIPELINE")
    assert off_outs == sync_outs, "stream entry changed sync results"
    assert piped_outs == sync_outs, "pipelined stream changed results"
    ctr = snap.get("counters", {})
    hists = snap.get("histograms", {})
    overlap_ms = float(hists.get("pipeline.overlap_ms", {}).get("sum", 0))
    stall_ms = float(hists.get("pipeline.stall_ms", {}).get("sum", 0))
    rows = sum(b[4] for b in batches)
    return {
        "config": "dispatch",
        "name": f"pipelined_stream_{n_batches}x{len(chain)}op_d{depth}",
        "string_width": str_width,
        "rows": rows,
        "distinct_batch_sizes": len(set(sizes)),
        "host_cpus": _os.cpu_count(),
        "sync_cold_seconds": round(sync_cold_s, 4),
        "sync_warm_seconds": round(sync_warm_s, 4),
        "sync_plan_warm_seconds": round(plan_warm_s, 4),
        "pipelined_cold_seconds": round(piped_cold_s, 4),
        "pipelined_warm_seconds": round(piped_warm_s, 4),
        "warm_speedup": round(sync_warm_s / piped_warm_s, 2),
        "vs_plan_sync": round(plan_warm_s / piped_warm_s, 2),
        "rows_per_s": round(rows / piped_warm_s, 1),
        "pipeline": {
            "depth": depth,
            "batches": n_batches,
            "overlap_ms": round(overlap_ms, 2),
            # overlap and wall cover the SAME warm passes (all of them)
            "overlap_fraction": round(
                overlap_ms / max(piped_total_s * 1e3, 1e-9), 3
            ),
            "stall_ms": round(stall_ms, 2),
            "stalls": int(ctr.get("pipeline.stalls", 0)),
            "replays": int(ctr.get("pipeline.replays", 0)),
            "enqueued": int(ctr.get("pipeline.enqueued", 0)),
            "donated_bytes": int(
                snap.get("bytes", {}).get("hbm.donated_bytes", 0)
            ),
            "donations": int(ctr.get("hbm.donations", 0)),
            "uploads_batched": int(
                ctr.get("wire.upload.batched", 0)
            ),
        },
        "platform": platform,
    }


def bench_serving_multiquery(platform, n_sessions=3, n_batches=5):
    """Serving-daemon bench (ISSUE 9 tentpole): TPC-DS-shaped plan
    mixes (the q5 / q23 / q64 silhouettes: filter->agg,
    filter->sort->agg, filter->cast->sort->agg) served as CONCURRENT
    tenant sessions through one long-lived daemon.

    Three phases:

      serial    every mix over its batch stream via ``table_plan_wire``
                — the parity reference and the no-daemon baseline.
      warm      ONE daemon session streams all mixes against a cleared
                compile cache: it pays every compile (the recorded
                ``warm_misses``).
      served    ``n_sessions`` NEW sessions stream the same mixes
                concurrently. Their compiled-executable lookups land in
                the process-global ``buckets.cached_jit`` the warm
                session populated — the ``cross_session_hits`` /
                ``hit_rate`` headline (misses here stay ~0: tenant B
                never re-pays tenant A's compiles).

    Byte parity of every served result against the serial reference is
    asserted, as is zero leaked resident tables after shutdown. The
    structured ``serving`` block carries sessions, shed count, merged
    p50/p95 queue wait, and the cross-session cache-hit rate.
    SRT_BENCH_SERVE_ROWS shrinks the shape for smoke runs
    (ci/smoke-observability.sh drives this config)."""
    import os as _os
    import threading as _threading
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu import serving
    from spark_rapids_jni_tpu.utils import buckets as srt_buckets
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics

    _metrics_enable()  # the cache/shed/wait counters ARE the story
    base = int(_os.environ.get("SRT_BENCH_SERVE_ROWS", 60_000))
    rng = np.random.default_rng(59)
    sizes = sorted(
        int(s)
        for s in rng.integers(base // 2, base * 3 // 2 + 2, n_batches)
    )
    i64 = int(dt.TypeId.INT64)
    b8 = int(dt.TypeId.BOOL8)
    mixes = {
        # q5 silhouette: scan -> filter -> aggregate
        "q5": [
            {"op": "filter", "mask": 2},
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "sum"}]},
        ],
        # q23 silhouette: filter -> order -> aggregate
        "q23": [
            {"op": "filter", "mask": 2},
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "sum"}]},
        ],
        # q64 silhouette: filter -> project(cast) -> order -> aggregate
        "q64": [
            {"op": "filter", "mask": 2},
            {"op": "cast", "column": 1,
             "type_id": int(dt.TypeId.FLOAT64)},
            {"op": "sort_by", "keys": [{"column": 0}]},
            {"op": "groupby", "by": [0],
             "aggs": [{"column": 1, "agg": "sum"}]},
        ],
    }
    batches = []
    for nn in sizes:
        kk = rng.integers(0, 1000, nn, dtype=np.int64)
        vv = rng.integers(-100, 100, nn, dtype=np.int64)
        mm = (vv > 0).astype(np.uint8)
        batches.append((
            [i64, i64, b8], [0, 0, 0],
            [kk.tobytes(), vv.tobytes(), mm.tobytes()],
            [None, None, None], nn,
        ))

    def serial_pass():
        t0 = _time.perf_counter()
        outs = {
            name: [
                rb.table_plan_wire(json.dumps(ops), *b) for b in batches
            ]
            for name, ops in mixes.items()
        }
        return _time.perf_counter() - t0, outs

    serial_cold_s, serial_outs = serial_pass()
    serial_warm_s = serial_pass()[0]

    got = {}
    errs = []
    with serving.serve() as srv:
        # warm phase: ONE session pays every compile against a cleared
        # cache, so the served phase's hits are strictly CROSS-session
        srt_buckets.cache_clear()
        srt_metrics.reset()
        with serving.Client(srv.port, name="warm") as w:
            for name, ops in mixes.items():
                w.stream(ops, batches)
        warm_snap = _metrics_snapshot() or {}
        warm_misses = int(
            warm_snap.get("counters", {}).get("compile_cache.miss", 0)
        )

        srt_metrics.reset()
        clients = [
            serving.Client(
                srv.port, name=f"tenant-{i}-{list(mixes)[i % 3]}"
            ).connect()
            for i in range(n_sessions)
        ]

        def run(i):
            try:
                got[i] = {
                    name: clients[i].stream(ops, batches)
                    for name, ops in mixes.items()
                }
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        t0 = _time.perf_counter()
        threads = [
            _threading.Thread(target=run, args=(i,))
            for i in range(n_sessions)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        served_s = _time.perf_counter() - t0
        snap = _metrics_snapshot() or {}
        # merged queue-wait percentiles over every live tenant session
        # (in-process peek at the raw wait samples: exact, not a
        # percentile-of-percentiles)
        waits = sorted(
            wt
            for s in srv._sessions.values()
            for wt in list(s._waits)
        )
        stats_doc = srv.stats()
        docs = stats_doc["sessions"]
        durability = stats_doc.get("durability", {})
        for c in clients:
            c.close()
    if errs:
        raise errs[0]
    for i in range(n_sessions):
        assert got[i] == serial_outs, (
            f"served results for tenant {i} diverge from serial"
        )
    leaked = rb.resident_table_count()
    assert leaked == 0, f"{leaked} resident table(s) leaked"

    def pct(p):
        if not waits:
            return 0.0
        i = min(int(p * (len(waits) - 1) + 0.5), len(waits) - 1)
        return round(waits[i] * 1e3, 3)

    ctr = snap.get("counters", {})
    hits = int(ctr.get("compile_cache.hit", 0))
    misses = int(ctr.get("compile_cache.miss", 0))
    rows = sum(b[4] for b in batches) * len(mixes)
    return {
        "config": "serving",
        "name": f"serving_multiquery_{n_sessions}x{len(mixes)}mix",
        "rows": rows,
        "host_cpus": _os.cpu_count(),
        "serial_cold_seconds": round(serial_cold_s, 4),
        "serial_warm_seconds": round(serial_warm_s, 4),
        "served_seconds": round(served_s, 4),
        "rows_per_s": round(rows * n_sessions / served_s, 1),
        "serving": {
            "sessions": n_sessions,
            "mixes": sorted(mixes),
            "batches_per_mix": n_batches,
            "requests": int(ctr.get("serving.requests", 0)),
            "shed": int(ctr.get("serving.shed", 0)),
            "queue_wait_ms_p50": pct(0.50),
            "queue_wait_ms_p95": pct(0.95),
            "warm_misses": warm_misses,
            "cross_session_hits": hits,
            "cross_session_misses": misses,
            "cross_session_hit_rate": round(
                hits / max(hits + misses, 1), 3
            ),
            "sessions_detail": [
                {
                    "name": d["name"],
                    "requests": d["requests"],
                    "shed": d["shed"],
                    "queue_wait": d["queue_wait"],
                    "donated_credit_bytes": d["donated_credit_bytes"],
                }
                for d in docs
            ],
            "leaked_tables": leaked,
            # the durable-plane doc (ISSUE 14): checkpoint/restore
            # counters when SPARK_RAPIDS_TPU_DURABLE=on, and proof the
            # default run carries no journaling cost (enabled: False)
            "durability": durability,
        },
        "platform": platform,
    }


def bench_resident_chain(platform, n=None):
    """VERDICT item 4 bench: a 3-op chain (filter -> sort -> groupby)
    through device-RESIDENT table handles vs the bytes-wire path that
    round-trips every op's inputs/outputs through host memory.
    SRT_BENCH_RESIDENT_ROWS shrinks the shape for smoke runs
    (ci/smoke-observability.sh drives this config to produce trace +
    flight artifacts in seconds, not minutes)."""
    import os as _os
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb

    if n is None:
        n = int(_os.environ.get("SRT_BENCH_RESIDENT_ROWS", 4_000_000))

    rng = np.random.default_rng(9)
    k = rng.integers(0, 1000, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    mask = (v > 0).astype(np.uint8)
    i64 = int(dt.TypeId.INT64)
    b8 = int(dt.TypeId.BOOL8)
    op_filter = json.dumps({"op": "filter", "mask": 2})
    op_sort = json.dumps({"op": "sort_by", "keys": [{"column": 0}]})
    op_group = json.dumps(
        {"op": "groupby", "by": [0],
         "aggs": [{"column": 1, "agg": "sum"}]}
    )

    def wire_chain():
        t1 = rb.table_op_wire(
            op_filter, [i64, i64, b8], [0, 0, 0],
            [k.tobytes(), v.tobytes(), mask.tobytes()],
            [None, None, None], n,
        )
        t2 = rb.table_op_wire(op_sort, t1[0], t1[1], t1[2], t1[3], t1[4])
        t3 = rb.table_op_wire(op_group, t2[0], t2[1], t2[2], t2[3], t2[4])
        return t3

    def resident_chain():
        tid = rb.table_upload_wire(
            [i64, i64, b8], [0, 0, 0],
            [k.tobytes(), v.tobytes(), mask.tobytes()],
            [None, None, None], n,
        )
        f = rb.table_op_resident(op_filter, [tid])
        s = rb.table_op_resident(op_sort, [f])
        g = rb.table_op_resident(op_group, [s])
        out = rb.table_download_wire(g)
        for t in (tid, f, s, g):
            rb.table_free(t)
        return out

    def best_of(fn, reps=3):
        out = fn()  # warm/compile
        best = float("inf")
        for _ in range(reps):
            t0 = _time.perf_counter()
            out = fn()
            best = min(best, _time.perf_counter() - t0)
        return best, out

    wire_s, wire_out = best_of(wire_chain)
    res_s, res_out = best_of(resident_chain)
    assert wire_out[4] == res_out[4], "chain row counts differ"
    assert wire_out[2][1] == res_out[2][1], "chain sums differ"
    return {
        "config": "resident-chain",
        "name": "filter_sort_groupby_3op_chain",
        "rows": n,
        "wire_seconds": round(wire_s, 4),
        "resident_seconds": round(res_s, 4),
        "speedup": round(wire_s / res_s, 2),
        "platform": platform,
    }


def bench_parquet_pipeline(platform, n_groups=4, rows_per_group=1_500_000):
    """Config-5 shape: Parquet scan -> predicate pushdown -> filter ->
    groupby-agg, streamed per row group, with and without the
    decode/compute prefetch overlap (round-3 VERDICT item 10)."""
    import tempfile
    import time as _time

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.io.parquet import scan_parquet
    from spark_rapids_jni_tpu.io.predicates import col as pred_col
    from spark_rapids_jni_tpu.ops.groupby import (
        GroupbyAgg,
        groupby_aggregate,
    )

    rng = np.random.default_rng(21)
    n = n_groups * rows_per_group
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/bench.parquet"
        pq.write_table(
            pa.table({
                "k": rng.integers(0, 1000, n),
                "v": rng.standard_normal(n),
                "q": rng.integers(0, 100, n),
            }),
            path,
            row_group_size=rows_per_group,
        )
        predicate = pred_col("q") > 19  # ~80% selectivity

        def pipeline(prefetch):
            t0 = _time.perf_counter()
            total = 0
            for batch in scan_parquet(
                path, filters=predicate, prefetch=prefetch
            ):
                agg = groupby_aggregate(
                    batch, ["k"], [GroupbyAgg("v", "sum")]
                )
                total += int(agg.row_count)
            return _time.perf_counter() - t0, total

        pipeline(0)  # compile warmup: both timed runs reuse the cache
        serial_s, t1 = pipeline(0)
        overlap_s, t2 = pipeline(2)
        assert t1 == t2
    return {
        "config": 5,
        # workload size in the name: the r3 shrink from 6x2M to 4x1.5M
        # silently broke round-over-round comparability (ADVICE r3)
        "name": f"parquet_scan_filter_agg_{n_groups}x{rows_per_group // 1000}k",
        "rows": n,
        "serial_seconds": round(serial_s, 3),
        "prefetch_seconds": round(overlap_s, 3),
        "overlap_speedup": round(serial_s / overlap_s, 2),
        "rows_per_s": round(n / overlap_s, 1),
        "platform": platform,
    }


def bench_chunk_sort_ab(platform, total_rows=16_777_216, t=8192):
    """Pallas VMEM bitonic sort vs XLA batched lax.sort on the chunked-
    groupby phase-1 shape — the measurement that decides whether the
    chunked design's 'batched small sorts stay in VMEM' bet needs the
    explicit kernel (kernels/bitonic_sort.py) or XLA already delivers."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.kernels.bitonic_sort import batched_sort_u64

    c = total_rows // t
    rng = np.random.default_rng(29)
    key = jnp.asarray(rng.integers(0, 1 << 40, (c, t)).astype(np.uint64))
    val = jnp.asarray(rng.integers(-1000, 1000, (c, t)))
    jax.block_until_ready(key)

    def xla_sort(k, v):
        iota = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (c, t))
        return jax.lax.sort((k, iota, v), num_keys=1, is_stable=True)

    xla_fn = jax.jit(xla_sort)
    med_x, mn_x, std_x, out_x = _timeit(xla_fn, [(key, val)], reps_per_input=3)

    # Mosaic on the chip; the interpreter tier only exists so a CPU
    # smoke of this config runs the same code (its timing is meaningless)
    interp = platform == "cpu"
    pl_fn = jax.jit(
        lambda k, v: batched_sort_u64(k, v, interpret=interp)
    )
    med_p, mn_p, std_p, out_p = _timeit(pl_fn, [(key, val)], reps_per_input=3)
    # equality spot check on one chunk
    assert np.array_equal(
        np.asarray(out_x[0][0]), np.asarray(out_p[0][0])
    ), "pallas sort diverges from lax.sort"
    bytes_moved = total_rows * 20 * 2
    e1 = _entry("chunk-sort", f"lax_sort_{c}x{t}", total_rows, med_x,
                mn_x, std_x, bytes_moved, platform)
    e2 = _entry("chunk-sort", f"pallas_bitonic_{c}x{t}", total_rows,
                med_p, mn_p, std_p, bytes_moved, platform)
    e2["vs_lax"] = round(med_x / med_p, 2)

    # u32 single-word arm: the packed-word contract (distinct keys,
    # permutation in the embedded iota, values follow by gather)
    from spark_rapids_jni_tpu.kernels.bitonic_sort import batched_sort_u32

    iota_bits = (t - 1).bit_length()
    key32 = jnp.asarray(
        (
            (rng.integers(0, 1 << (32 - iota_bits), (c, t),
                          dtype=np.uint64) << iota_bits)
            | np.arange(t, dtype=np.uint64)[None, :]
        ).astype(np.uint32)
    )
    jax.block_until_ready(key32)

    def u32_sort(k, v):
        s = batched_sort_u32(k, interpret=interp)[0]
        perm = (s & jnp.uint32(t - 1)).astype(jnp.int32)
        return s, jnp.take_along_axis(v, perm, axis=1)

    u32_fn = jax.jit(u32_sort)
    med_u, mn_u, std_u, out_u = _timeit(
        u32_fn, [(key32, val)], reps_per_input=3
    )
    assert np.array_equal(
        np.asarray(out_u[0][0]), np.sort(np.asarray(key32[0]))
    ), "u32 pallas sort diverges from np.sort"
    bytes_u32 = total_rows * 12 * 2  # u32 word + i64 value in/out
    e3 = _entry("chunk-sort", f"pallas_u32_gather_{c}x{t}", total_rows,
                med_u, mn_u, std_u, bytes_u32, platform)
    e3["vs_lax"] = round(med_x / med_u, 2)
    return [e1, e2, e3]


def bench_strings(platform, n=10_000_000, pad=128):
    """Round-4 VERDICT item 5 bench: literal contains at pad=128 via the
    shift-or scan, and a 10M x 10M string-key join through automatic
    dictionary encoding."""
    import jax

    from spark_rapids_jni_tpu.column import Column, Table
    from spark_rapids_jni_tpu.ops import strings as strings_mod
    from spark_rapids_jni_tpu.ops.join import inner_join

    from spark_rapids_jni_tpu import dtype as dt_mod

    rng = np.random.default_rng(17)
    # contains: random a-z bytes, lengths ~uniform(0, pad)
    lens = rng.integers(0, pad + 1, n).astype(np.int32)
    mat = rng.integers(97, 123, (n, pad), dtype=np.uint8)
    mat[np.arange(pad)[None, :] >= lens[:, None]] = 0
    col = Column(
        jax.numpy.asarray(mat), dt_mod.STRING, None,
        jax.numpy.asarray(lens),
    )
    jax.block_until_ready(col.data)
    fn = jax.jit(lambda c: strings_mod.contains(c, "qzx"))
    med, mn, std, out = _timeit(fn, [(col,)], reps_per_input=3)
    e1 = _entry(
        "strings", f"contains_{n // 1_000_000}M_pad{pad}", n, med, mn,
        std, n * pad, platform,
    )

    # string-key join: nj distinct 12-byte keys, each side drawing nj
    # rows from them, so the expected output is ~nj rows (~1 match/row).
    # The previous 100k-unique pool made E[matches] ~ nj^2/100k ~ 1e9
    # rows — a 30-50 GB materialization that would OOM the 16 GiB chip
    # (ADVICE r4, medium). Byte matrix built vectorized host-side: 10M
    # python strings would dominate the setup.
    nj = n
    klen = 12

    def key_matrix(ids):
        m = np.empty((ids.size, klen), np.uint8)
        m[:, 0] = ord("k")
        x = ids.astype(np.int64)
        for j in range(klen - 1, 0, -1):
            m[:, j] = ord("0") + (x % 10)
            x //= 10
        return m

    def str_table(idx, name):
        return Table(
            [
                Column(
                    jax.numpy.asarray(key_matrix(idx)), dt_mod.STRING,
                    None,
                    jax.numpy.full((nj,), klen, jax.numpy.int32),
                ),
                Column.from_numpy(np.arange(nj, dtype=np.int64)),
            ],
            ["k", name],
        )

    lt = str_table(rng.integers(0, nj, nj), "lv")
    rt = str_table(rng.integers(0, nj, nj), "rv")
    jax.block_until_ready(lt.columns[0].data)
    t0 = time.perf_counter()
    out = inner_join(lt, rt, ["k"])
    np.asarray(out.columns[1].data.ravel()[-1:])
    join_s = time.perf_counter() - t0
    e2 = {
        "config": "strings",
        # uniques pool in the name: changing it changes E[matches]
        "name": (
            f"string_key_join_{nj // 1_000_000}Mx{nj // 1_000_000}M"
            f"_u{nj // 1_000_000}M"
        ),
        "rows": 2 * nj,
        "seconds_median": round(join_s, 4),
        "matches": out.row_count,
        "platform": platform,
    }
    return [e1, e2]


def bench_parquet_device(platform, n_groups=4, rows_per_group=1_500_000):
    """Round-4 VERDICT item 4 A/B: scan throughput of the device page
    decoder (host parses headers, uploads ENCODED bytes, chip expands)
    vs the host-Arrow-decode + upload path, on the config-5 shape."""
    import tempfile
    import time as _time

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu.io.parquet import scan_parquet

    rng = np.random.default_rng(23)
    n = n_groups * rows_per_group
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/bench_dev.parquet"
        pq.write_table(
            pa.table({
                "k": rng.integers(0, 1000, n),          # dict-encodable
                "v": rng.standard_normal(n),            # PLAIN doubles
                "q": rng.integers(0, 100, n).astype(np.int32),
            }),
            path,
            row_group_size=rows_per_group,
        )

        def scan(device):
            t0 = _time.perf_counter()
            total = 0
            checksum = 0.0
            for batch in scan_parquet(path, device_decode=device):
                # force materialization on device: a reduction + fetch
                total += batch.row_count
                checksum += float(
                    np.asarray(batch["q"].data.astype(np.int64).sum())
                )
            return _time.perf_counter() - t0, total, checksum

        scan(False)  # warm compile + page cache
        scan(True)
        host_s, t1, c1 = scan(False)
        dev_s, t2, c2 = scan(True)
        assert t1 == t2 and c1 == c2, "device decode changed the data"
    return {
        "config": 5,
        "name": f"parquet_device_decode_{n_groups}x{rows_per_group // 1000}k",
        "rows": n,
        "host_decode_seconds": round(host_s, 3),
        "device_decode_seconds": round(dev_s, 3),
        "speedup": round(host_s / dev_s, 2),
        "platform": platform,
    }


def bench_tpcds(platform, scale=None):
    """Configs 4-5 with REAL data (round-4 VERDICT item 6): seeded
    Parquet star schema at SRT_TPCDS_SCALE (default SF1: 2.88M
    store_sales rows), streamed scan->join->agg q5/q23/q64 with pandas
    oracle verdicts recorded per query."""
    import os

    from benchmarks import tpcds

    if scale is None:
        scale = float(os.environ.get("SRT_TPCDS_SCALE", "1.0"))
    cache = f"/tmp/srt_tpcds_sf{scale}"
    if not os.path.exists(os.path.join(cache, "store_sales.parquet")):
        _progress(f"generating TPC-DS parquet at scale {scale} -> {cache}")
        tpcds.generate_parquet(cache, scale=scale, seed=0)
    entries = tpcds.run_all(cache, prefetch=2)
    for e in entries:
        e.update({"config": 5, "scale": scale, "platform": platform})
    return entries


def bench_tpcds_distributed(devices: int = 8, scale: float = 0.05,
                            timeout_s: float = 1800.0):
    """Config 4: the same Parquet files through the mesh-distributed
    q5/q23/q64 DAGs on the virtual CPU mesh (simulation wall-clock).

    ``timeout_s`` bounds the WHOLE arm (parquet generation + the mesh
    subprocess); overrunning raises subprocess.TimeoutExpired, which
    the ``_guard`` caller turns into a structured ``{type:"timeout"}``
    failure record — the r04 rc=124 postmortem: this arm used to start
    with minutes of budget left and run unbounded to the driver's
    kill."""
    import os
    import subprocess

    t0 = time.time()
    cache = f"/tmp/srt_tpcds_sf{scale}"
    if not os.path.exists(os.path.join(cache, "store_sales.parquet")):
        from benchmarks import tpcds

        tpcds.generate_parquet(cache, scale=scale, seed=0)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    code = (
        "import jax, json; jax.config.update('jax_platforms','cpu'); "
        "from benchmarks import tpcds; "
        f"print('TPCDS_DIST ' + json.dumps(tpcds.run_distributed({cache!r}, {devices})))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=max(timeout_s - (time.time() - t0), 60.0), env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in out.stdout.splitlines():
        if line.startswith("TPCDS_DIST "):
            got = json.loads(line[len("TPCDS_DIST "):])
            for e in got:
                e.update({"config": 4, "scale": scale, "platform": "cpu-mesh"})
            return got
    _progress(f"tpcds distributed produced no JSON: {out.stderr[-400:]}")
    return None


def _arm_cap(default_s: float) -> float:
    """Per-arm wall-clock slice for the CPU-mesh tail stages.

    SRT_BENCH_ARM_TIMEOUT_S overrides the default so a smoke run can
    bound every tail arm tightly — the arm dies to its own subprocess
    timeout (a structured {type:"timeout"} entry) instead of running
    into the driver's rc=124 kill and eating the headline emit."""
    raw = os.environ.get("SRT_BENCH_ARM_TIMEOUT_S", "").strip()
    if raw:
        try:
            return float(raw)
        except ValueError:
            _progress(f"ignoring bad SRT_BENCH_ARM_TIMEOUT_S={raw!r}")
    return default_s


def _skew_child(timeout_s: float, rows: int = 10_000_000,
                skew_split=None):
    """One benchmarks.run zipf-skew child on the 8-device CPU mesh;
    returns its parsed JSON entry (or None). ``skew_split`` pins the
    adaptive splitter via the child's env for the A/B arm."""
    import subprocess

    env = dict(os.environ)
    # benchmarks.run sees the host-device-count flag + --devices and
    # forces jax_platforms=cpu through the config API itself
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    if skew_split is not None:
        env["SPARK_RAPIDS_TPU_SKEW_SPLIT"] = "1" if skew_split else "0"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--configs", "skew",
         "--devices", "8", "--rows", str(rows)],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    _progress(f"skew run produced no JSON: {out.stderr[-500:]}")
    return None


def bench_distributed_skew(timeout_s: float = 900.0):
    """Config 4 shape at 1e7 rows: zipf-skew distributed groupby through
    the ragged-compact exchange on the virtual 8-device CPU mesh (the
    multi-chip path; numbers are CPU-simulation, labeled as such).

    An overrun of ``timeout_s`` raises subprocess.TimeoutExpired out to
    ``_guard``'s structured ``{type:"timeout"}`` record — this used to
    be swallowed into a bare progress line, leaving the headline JSON
    with no trace of the arm at all."""
    import subprocess

    try:
        return _skew_child(timeout_s)
    except subprocess.TimeoutExpired:
        raise
    except Exception as e:  # pragma: no cover
        _progress(f"skew run failed: {e}")
    return None


def bench_mesh_skew_adaptive(timeout_s: float = 900.0):
    """The adaptive-skew A/B (ISSUE 17): the BENCH_r04 zipf config run
    twice on the 8-device CPU mesh — splitting off (the r04 behaviour:
    exchange capacity sized from the raw hot-destination counts) vs on
    (hot keys salted across sub-partitions with partial-agg before the
    exchange). Emits one entry whose structured ``skew`` block carries
    both arms' seconds / recv_buffer_rows / peak_rss plus the deltas.

    Each child gets half the slice; an overrun raises TimeoutExpired
    out to _guard's typed record so the headline line survives."""
    half = max(timeout_s / 2.0, 1.0)
    t0 = time.time()
    off = _skew_child(half, skew_split=False)
    rest = max(timeout_s - (time.time() - t0), 1.0)
    on = _skew_child(min(half, rest), skew_split=True)
    if off is None or on is None:
        _progress("skew A/B incomplete: "
                  f"off={'ok' if off else 'lost'} "
                  f"on={'ok' if on else 'lost'}")
        return None

    def _arm(e):
        return {
            "seconds": e.get("seconds"),
            "recv_buffer_rows": e.get("recv_buffer_rows_per_device"),
            "peak_rss_mb": e.get("peak_rss_mb"),
            "max_over_mean": e.get("max_over_mean"),
            "skew_splits": e.get("skew_splits", 0),
        }

    def _delta(key):
        a, b = off.get(key), on.get(key)
        if a is None or b is None:
            return None
        return round(a - b, 4)

    from spark_rapids_jni_tpu.utils import config as srt_config

    return {
        "config": "4-skew-adaptive",
        "name": "mesh_skew_adaptive",
        "rows": on.get("rows"),
        "devices": on.get("devices"),
        "platform": on.get("platform"),
        "skew": {
            "factor": float(srt_config.get_flag("SKEW_SPLIT_FACTOR")),
            "splits": on.get("skew_splits", 0),
            "off": _arm(off),
            "on": _arm(on),
            "deltas": {
                "seconds": _delta("seconds"),
                "recv_buffer_rows": _delta(
                    "recv_buffer_rows_per_device"),
                "peak_rss_mb": _delta("peak_rss_mb"),
            },
        },
    }


def _guard(entries, name, fn):
    """Run one config; a failure records a structured failure entry
    instead of killing the whole ladder (the driver needs the JSON
    line). An arm that overruns its own wall-clock slice
    (subprocess.TimeoutExpired) records the typed ``{type:"timeout"}``
    failure — the arm is sacrificed, the headline line survives."""
    import subprocess

    _progress(name)
    t0 = time.time()
    try:
        out = fn()
    except subprocess.TimeoutExpired as e:
        slice_s = float(e.timeout or 0.0)
        _progress(f"  TIMEOUT after {slice_s:.0f}s")
        entries.append(_failure_record(
            name, f"timeout {slice_s:.0f}s", exc_type="timeout",
            elapsed_s=time.time() - t0,
        ))
        return None
    except Exception as e:  # pragma: no cover
        _progress(f"  FAILED: {e}")
        entries.append(
            _failure_record(name, e, elapsed_s=time.time() - t0)
        )
        return None
    if out is None:
        return None
    got = out if isinstance(out, list) else [out]
    # snapshot-then-RESET: the registry is process-wide, so without the
    # reset a second in-process config's block would also carry the
    # first config's counters (the subprocess path is per-config by
    # virtue of the fresh process)
    snap = _metrics_snapshot(reset=True)
    prof = _profile_block(reset=True)
    for g in got:
        _progress(f"  {g}")  # progress line WITHOUT the bulky block
        if snap is not None:
            g.setdefault("metrics", snap)
        if prof is not None:
            g.setdefault("profile", prof)
    entries.extend(got)
    return out


def bench_spill_stream(platform, tables=12, rows=1 << 15):
    """Config: tiered-memory degradation (utils/spill.py). A resident
    working set ~2x an artificially SHRUNK HBM budget streams a sort
    over every table for two full passes — the second pass repages what
    the first pass spilled, so the LRU cycles the whole set through
    host/disk — and must come back byte-identical to the unconstrained
    run: the RAPIDS plugin's spill-instead-of-die contract, priced.
    Reported: slowdown vs unconstrained plus the spill counters that
    prove the constrained run actually spilled."""
    import time as _time

    import numpy as np

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu.utils import config as srt_config
    from spark_rapids_jni_tpu.utils import hbm as hbm_mod
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics
    from spark_rapids_jni_tpu.utils import spill as spill_mod

    _metrics_enable()
    rng = np.random.default_rng(53)
    i64 = int(dt.TypeId.INT64)
    op_sort = json.dumps({"op": "sort_by", "keys": [{"column": 0}]})
    batches = [
        rng.integers(-(1 << 40), 1 << 40, rows, dtype=np.int64)
        for _ in range(tables)
    ]

    def upload(arr):
        return rb.table_upload_wire(
            [i64], [0], [arr.tobytes()], [None], rows
        )

    def run_stream():
        """Upload the whole working set, then two round-robin sort
        passes over it (each keeps its input resident); returns
        (seconds, downloads) with everything freed again."""
        ids = [upload(a) for a in batches]
        t0 = _time.perf_counter()
        outs = []
        for _ in range(2):
            for tid in ids:
                res = rb.table_op_resident(op_sort, [tid])
                outs.append(rb.table_download_wire(res))
                rb.table_free(res)
        dt_s = _time.perf_counter() - t0
        for tid in ids:
            rb.table_free(tid)
        return dt_s, outs

    def norm(outs):
        return [
            tuple(bytes(d) for d in o[2] if d is not None) for o in outs
        ]

    # unconstrained reference first (spill off, default budget)
    srt_config.set_flag("SPILL", False)
    srt_metrics.reset()
    base_s, base_outs = run_stream()
    base_s = min(base_s, run_stream()[0])

    # shrink the budget to HALF the resident working set and turn the
    # spill tier on: the stream must now degrade, not die
    working_set = tables * rows * 8
    gib = 1 << 30
    shrunk_gb = (working_set / 2) / (1.0 - hbm_mod.RESERVE_FRACTION) / gib
    srt_config.set_flag("HBM_BUDGET_GB", shrunk_gb)
    srt_config.set_flag("SPILL", "on")
    try:
        srt_metrics.reset()
        spill_s, spill_outs = run_stream()
        snap = _metrics_snapshot() or {}
    finally:
        srt_config.set_flag("SPILL", False)
        srt_config.set_flag("HBM_BUDGET_GB", 0)
    ctr = snap.get("counters", {})
    byt = snap.get("bytes", {})
    assert norm(spill_outs) == norm(base_outs), (
        "spilled stream changed results"
    )
    assert rb.resident_table_count() == 0, "spill arm leaked tables"
    assert spill_mod.spill_file_count() == 0, "spill arm leaked files"
    evictions = int(ctr.get("spill.evictions", 0))
    assert evictions > 0, (
        f"working set {working_set} B under budget "
        f"{int(shrunk_gb * gib)} B never spilled"
    )
    return {
        "config": "spill",
        "name": f"spill_stream_{tables}x{rows}",
        "rows": tables * rows,
        "working_set_bytes": working_set,
        "budget_bytes": int(shrunk_gb * gib * (1.0 - hbm_mod.RESERVE_FRACTION)),
        "unconstrained_seconds": round(base_s, 4),
        "spill_seconds": round(spill_s, 4),
        "slowdown": round(spill_s / base_s, 2) if base_s else None,
        "byte_identical": True,
        "spill": {
            "evictions": evictions,
            "repages": int(ctr.get("spill.repages", 0)),
            "demotions": int(ctr.get("spill.demotions", 0)),
            "bytes_out": int(byt.get("spill.bytes_out", 0)),
            "bytes_in": int(byt.get("spill.bytes_in", 0)),
        },
        "platform": platform,
    }


def bench_kernel_ab(platform, workload, total_rows=2_097_152,
                    batch_rows=None):
    """Config: the Pallas kernel tier A/B (kernels/registry.py) — the
    SAME resident dispatch stream with SPARK_RAPIDS_TPU_KERNELS=on vs
    off. Batches sit inside the kernel predicates' envelope (pow2
    bucket, within the VMEM bounds) so the ON arm actually launches;
    the entry carries the kernel.launches/declines/fallbacks counters
    that prove it, and a clean run must report ZERO fallbacks (the
    tier's never-changes-bytes contract, byte-checked here on the last
    batch and exhaustively by tests/test_kernel_tier.py).
    SRT_BENCH_KERNEL_ROWS scales total_rows for smoke runs."""
    import os as _os
    import time as _time

    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import runtime_bridge as rb
    from spark_rapids_jni_tpu.utils import buckets as buckets_mod
    from spark_rapids_jni_tpu.utils import config as srt_config
    from spark_rapids_jni_tpu.utils import metrics as srt_metrics

    _metrics_enable()  # the kernel.* counters ARE this config's story
    if batch_rows is None:
        # each workload's largest pow2 batch inside its kernel's VMEM
        # predicate: packed_sort carries (3 + 4 payload) u32 words/row
        # against SORT_MAX_WORDS, the hash kernels bound rows directly
        batch_rows = (1 << 14) if workload == "sort" else (1 << 16)
    raw = _os.environ.get("SRT_BENCH_KERNEL_ROWS", "").strip()
    if raw:
        total_rows = max(batch_rows, int(raw))
    nb = max(1, total_rows // batch_rows)
    rng = np.random.default_rng(61)
    i64 = int(dt.TypeId.INT64)

    ids = []
    rest_ids = []
    if workload == "sort":
        chain = [{"op": "sort_by", "keys": [{"column": 0}]}]
        for _ in range(nb):
            k = rng.integers(-(1 << 40), 1 << 40, batch_rows,
                             dtype=np.int64)
            v = rng.integers(-1000, 1000, batch_rows, dtype=np.int64)
            ids.append(rb.table_upload_wire(
                [i64, i64], [0, 0], [k.tobytes(), v.tobytes()],
                [None, None], batch_rows,
            ))
    elif workload == "groupby":
        chain = [{"op": "groupby", "by": [0],
                  "aggs": [{"column": 1, "agg": "sum"},
                           {"column": 1, "agg": "count"}]}]
        for _ in range(nb):
            k = rng.integers(0, 50_000, batch_rows, dtype=np.int64)
            v = rng.integers(-1000, 1000, batch_rows, dtype=np.int64)
            ids.append(rb.table_upload_wire(
                [i64, i64], [0, 0], [k.tobytes(), v.tobytes()],
                [None, None], batch_rows,
            ))
    elif workload == "transpose":
        schema = [dt.INT64, dt.FLOAT64, dt.INT32, dt.BOOL8]
        chain = [
            {"op": "to_rows"},
            {"op": "from_rows",
             "type_ids": [int(d.id) for d in schema],
             "scales": [0] * len(schema)},
        ]
        for _ in range(nb):
            datas = [
                rng.integers(-(1 << 40), 1 << 40, batch_rows,
                             dtype=np.int64).tobytes(),
                rng.standard_normal(batch_rows).tobytes(),
                rng.integers(-(1 << 30), 1 << 30, batch_rows,
                             dtype=np.int32).tobytes(),
                rng.integers(0, 2, batch_rows).astype(np.bool_).tobytes(),
            ]
            ids.append(rb.table_upload_wire(
                [int(d.id) for d in schema], [0] * len(schema), datas,
                [None] * len(schema), batch_rows,
            ))
    elif workload == "join":
        # existing batched-join sizing: a resident unique-key build
        # side probed by every stream batch (the kernel's sweet spot —
        # duplicate build keys decline to the exact path)
        chain = [{"op": "join", "on": [0], "how": "inner"}]
        build_n = 1 << 16
        bk = rng.permutation(2 * build_n)[:build_n].astype(np.int64)
        bv = rng.integers(-1000, 1000, build_n, dtype=np.int64)
        rest_ids = [rb.table_upload_wire(
            [i64, i64], [0, 0], [bk.tobytes(), bv.tobytes()],
            [None, None], build_n,
        )]
        for _ in range(nb):
            k = rng.integers(0, 2 * build_n, batch_rows, dtype=np.int64)
            v = rng.integers(-1000, 1000, batch_rows, dtype=np.int64)
            ids.append(rb.table_upload_wire(
                [i64, i64], [0, 0], [k.tobytes(), v.tobytes()],
                [None, None], batch_rows,
            ))
    else:
        raise ValueError(f"unknown kernel A/B workload {workload!r}")

    def stream():
        """One full pass: every batch through the chain; the last
        output is downloaded (the completion barrier) and returned for
        the parity check."""
        t0 = _time.perf_counter()
        out = None
        for tid in ids:
            cur, owned = tid, False
            for op in chain:
                nxt = rb.table_op_resident(json.dumps(op),
                                           [cur] + rest_ids)
                if owned:
                    rb.table_free(cur)
                cur, owned = nxt, True
            out = rb.table_download_wire(cur)
            rb.table_free(cur)
        return _time.perf_counter() - t0, out

    warm_reps = 3

    def run_mode(mode):
        srt_config.set_flag("KERNELS", mode)
        try:
            buckets_mod.cache_clear()
            cold_s, _ = stream()
            srt_metrics.reset()
            warm_s, out = stream()
            for _ in range(warm_reps - 1):
                warm_s = min(warm_s, stream()[0])
            snap = _metrics_snapshot() or {}
        finally:
            srt_config.clear_flag("KERNELS")
        return cold_s, warm_s, out, snap

    try:
        off_cold_s, off_warm_s, off_out, _ = run_mode("off")
        on_cold_s, on_warm_s, on_out, snap = run_mode("on")
    finally:
        for tid in ids + rest_ids:
            rb.table_free(tid)
    assert off_out == on_out, (
        f"kernel tier changed bytes on {workload}"
    )
    ctr = snap.get("counters", {})
    launches = int(ctr.get("kernel.launches", 0))
    fallbacks = int(ctr.get("kernel.fallbacks", 0))
    assert launches > 0, f"kernel ON arm never launched ({workload})"
    assert fallbacks == 0, (
        f"clean kernel run reported {fallbacks} fallback(s) ({workload})"
    )
    return {
        "config": "kernel",
        "name": f"kernel_{workload}_ab_{nb}x{batch_rows}",
        "rows": nb * batch_rows,
        "batches": nb,
        "batch_rows": batch_rows,
        "kernel_off_cold_seconds": round(off_cold_s, 4),
        "kernel_off_warm_seconds": round(off_warm_s, 4),
        "kernel_on_cold_seconds": round(on_cold_s, 4),
        "kernel_on_warm_seconds": round(on_warm_s, 4),
        "warm_speedup": round(off_warm_s / on_warm_s, 3)
        if on_warm_s else None,
        "kernel": {
            "launches": launches,
            "declines": int(ctr.get("kernel.declines", 0)),
            "fallbacks": fallbacks,
        },
        "platform": platform,
    }


# Each device config runs in its OWN subprocess: a TPU worker crash or a
# device hang inside one config must cost that one entry, not every
# config after it (observed: the r3 100M-join crash killed the client
# and the three remaining configs all failed with UNAVAILABLE).
_SUBPROCESS_CONFIGS = {
    "groupby1m": lambda p: bench_groupby(p, 1_000_000)[0],
    "groupby16m": lambda p: bench_groupby(p, 16_000_000)[0],
    "groupby100m": lambda p: bench_groupby(p, 100_000_000)[0],
    "groupby100m_chunked": bench_groupby_chunked,
    "groupby100m_packed": bench_groupby_packed,
    "groupby_highcard": bench_groupby_highcard,
    "groupby16m_packed": lambda p: bench_groupby_packed(p, 16_000_000),
    "groupby16m_chunked": lambda p: bench_groupby_chunked(p, 16_000_000),
    # flat single-level packing: values as sort payloads vs word-only
    # sort + permutation gather
    "groupby16m_gather": lambda p: bench_groupby(
        p, 16_000_000, values_via="gather"
    )[0],
    "groupby100m_gather": lambda p: bench_groupby(
        p, 100_000_000, values_via="gather"
    )[0],
    "groupby16m_flat_sort": lambda p: bench_groupby_flat(
        p, 16_000_000, "sort"
    ),
    "groupby16m_flat_gather": lambda p: bench_groupby_flat(
        p, 16_000_000, "gather"
    ),
    "groupby100m_flat_gather": lambda p: bench_groupby_flat(
        p, 100_000_000, "gather"
    ),
    # VMEM bitonic phase-1 engines (u32 word + value gather): the A/B
    # that decides whether the packed formulation wins its sort back
    "groupby16m_packed_pallas32": lambda p: bench_groupby_packed(
        p, 16_000_000, engine="pallas32", chunk_rows=1 << 17,
        chunk_segments=1 << 14,
    ),
    "groupby100m_packed_pallas32": lambda p: bench_groupby_packed(
        p, 100_000_000, engine="pallas32", chunk_rows=1 << 17,
        chunk_segments=1 << 14,
    ),
    "transpose": bench_transpose,
    "transpose_pallas": bench_transpose_pallas,
    "join": bench_join,
    "join_batched": bench_join_batched,
    "join_batched_packed": bench_join_batched_packed,
    "sort": bench_sort,
    "sort_gather": bench_sort_gather,
    "sort_packed": bench_sort_packed,
    "sort_packed_gather": bench_sort_packed_gather,
    "chunk_sort_ab": bench_chunk_sort_ab,
    # kernel tier A/Bs (kernels/registry.py): dispatch stream with
    # SPARK_RAPIDS_TPU_KERNELS on vs off, byte-parity asserted
    "kernel_sort_ab": lambda p: bench_kernel_ab(p, "sort"),
    "kernel_groupby_ab": lambda p: bench_kernel_ab(p, "groupby"),
    "kernel_transpose_ab": lambda p: bench_kernel_ab(p, "transpose"),
    "kernel_join_ab": lambda p: bench_kernel_ab(p, "join", 8_388_608),
    "kernel_sort100m_ab": lambda p: bench_kernel_ab(p, "sort", 100_007_936),
    "kernel_groupby100m_ab": lambda p: bench_kernel_ab(
        p, "groupby", 100_007_936
    ),
    "kernel_transpose100m_ab": lambda p: bench_kernel_ab(
        p, "transpose", 100_007_936
    ),
    "strings": bench_strings,
    "resident": bench_resident_chain,
    "bucketed_stream": bench_bucketed_stream,
    "fused_plan": bench_fused_plan,
    "pipelined_stream": bench_pipelined_stream,
    "serving_multiquery": bench_serving_multiquery,
    "spill_stream": bench_spill_stream,
    "parquet": bench_parquet_pipeline,
    "parquet_device": bench_parquet_device,
    "tpcds": bench_tpcds,
    # SF10 rung (round-4 VERDICT item 5: scale past SF1): 28.8M-row
    # store_sales star schema, streamed q5/q23/q64 on the chip
    "tpcds10": lambda p: bench_tpcds(p, scale=10.0),
}

# Every arm declares its ladder tier HERE — one table, walk order
# preserved by dict insertion order, statically verified by srt-check
# SRT007 against _SUBPROCESS_CONFIGS (an un-tiered arm fails lint:
# r04/r05 postmortem — both rounds ended rc=124 with parsed=null
# because the flat cheap-first walk spent its whole budget on A/B arms
# before the headline 100M groupby ever ran).
#
#   headline — tier 1: the cheapest arm of each workload that feeds
#              the published line plus one proof arm per subsystem;
#              walks first under the full budget.
#   extended — tier 2: refinement A/Bs; each needs _EXTENDED_FLOOR_S
#              of budget left to start, so a slow extended arm can no
#              longer eat the flush/Arrow-baseline window at the end.
#   manual   — runnable via `--config <arm>` only; never in the
#              budgeted walk (superseded by a batched/packed variant
#              but kept for one-off comparison runs).
_ARM_TIERS = {
    "groupby1m": "headline",
    "groupby16m_packed": "headline",
    "groupby16m_chunked": "headline",
    # the headline metric itself (cheapest winning 100M formulation)
    "groupby100m_flat_gather": "headline",
    # one proof arm per subsystem: fusion, serving, tiered memory
    "fused_plan": "headline",
    "serving_multiquery": "headline",
    "spill_stream": "headline",
    # kernel tier: the three cheapest A/B pairs prove the headline
    # claim (on vs off wall time + launch counters); the 100M variants
    # and the join pair refine in the extended tier
    "kernel_sort_ab": "headline",
    "kernel_groupby_ab": "headline",
    "kernel_transpose_ab": "headline",
    "groupby16m": "extended",
    # decisive cheap A/Bs first: plain-XLA gather arms compile fast,
    # the Pallas engines (slow Mosaic compiles) right after
    "groupby16m_flat_gather": "extended",
    "groupby16m_flat_sort": "extended",
    "groupby16m_gather": "extended",
    "chunk_sort_ab": "extended",
    "kernel_join_ab": "extended",
    "strings": "extended",
    "transpose": "extended",
    "resident": "extended",
    "bucketed_stream": "extended",
    "pipelined_stream": "extended",
    "parquet": "extended",
    "parquet_device": "extended",
    # 100M tier: likely winners first
    "kernel_groupby100m_ab": "extended",
    "kernel_sort100m_ab": "extended",
    "kernel_transpose100m_ab": "extended",
    "groupby100m_gather": "extended",
    "groupby100m": "extended",
    "groupby_highcard": "extended",
    "sort": "extended",
    "sort_packed_gather": "extended",
    "sort_packed": "extended",
    "sort_gather": "extended",
    "join_batched": "extended",
    "join_batched_packed": "extended",
    "tpcds": "extended",
    "tpcds10": "extended",
    # unbatched join: superseded in the walk by join_batched[_packed]
    "join": "manual",
    # slow Mosaic-compile / superseded formulations: each lost its A/B
    # to the gather arms above and alone costs most of the budget tail
    # (rc=124 postmortem: the walk ran flush to the deadline and the
    # mesh+Arrow tail never got a window). `--config <arm>` still runs
    # them for one-off comparisons.
    "groupby16m_packed_pallas32": "manual",
    "groupby100m_packed_pallas32": "manual",
    "groupby100m_packed": "manual",
    "groupby100m_chunked": "manual",
    # superseded by kernel_transpose_ab: the kernel tier runs the same
    # Pallas transpose pair through the dispatch plane with counters
    # and byte parity; the ad-hoc arm stays for one-off comparisons
    "transpose_pallas": "manual",
}
_HEADLINE_LADDER = tuple(
    a for a, t in _ARM_TIERS.items() if t == "headline"
)
_EXTENDED_LADDER = tuple(
    a for a, t in _ARM_TIERS.items() if t == "extended"
)
_LADDER = _HEADLINE_LADDER + _EXTENDED_LADDER

# the static pass catches a missing tier at lint time; this catches it
# the moment someone runs the bench instead
assert set(_ARM_TIERS) == set(_SUBPROCESS_CONFIGS), (
    "bench arms and _ARM_TIERS disagree: "
    f"{set(_ARM_TIERS) ^ set(_SUBPROCESS_CONFIGS)}"
)

_CONFIG_TIMEOUT_S = 1800
_EXTENDED_FLOOR_S = 300.0  # budget an extended arm needs left to start
# The ladder walk stops _TAIL_RESERVE_S before the budget deadline so
# the post-walk tail (two CPU-mesh stages + the Arrow denominator)
# always has a window: those stages are unbounded once started, and a
# walk that ran flush to the deadline left the driver's kill to land
# mid-stage (rc=124 with the headline stuck on the pre-tail emit).
# Each tail stage additionally needs its own floor of budget left to
# start at all.
_TAIL_RESERVE_S = 480.0
_MESH_STAGE_FLOOR_S = 150.0  # a CPU-mesh stage needs this left to start
_ARROW_FLOOR_S = 120.0       # the Arrow 100M baseline likewise
# TPC-DS-from-parquet mesh arm: opt-in AND capped to the same slice as
# the skew arms (it previously ran ~30min worst case under an 1800s cap
# and ate the whole budget tail — the r04 rc=124 postmortem)
_TPCDS_ARM_CAP_S = 900.0

# the chosen budget split, published as headline JSON "budget" so a
# postmortem of a skipped/killed arm can see the split the run chose
# without reverse-engineering it from env + source; set once in main()
_BUDGET_DOC = None


def _budget_doc(budget_s: float, source: str) -> dict:
    return {
        "budget_s": budget_s,
        "source": source,
        "tail_reserve_s": _TAIL_RESERVE_S,
        "config_timeout_s": _CONFIG_TIMEOUT_S,
        "extended_floor_s": _EXTENDED_FLOOR_S,
        "mesh_stage_floor_s": _MESH_STAGE_FLOOR_S,
        "arrow_floor_s": _ARROW_FLOOR_S,
        "mesh_arm_caps_s": {
            "skew_adaptive_ab": _arm_cap(900.0),
            "skew_zipf": _arm_cap(900.0),
            "tpcds": _arm_cap(_TPCDS_ARM_CAP_S),
        },
        "tpcds_opt_in": os.environ.get(
            "SRT_BENCH_MESH_TPCDS", ""
        ).strip().lower() in ("1", "true", "yes", "on"),
    }


def _run_one(name: str) -> None:
    """Child-process entry: run one config, print its JSON entries.

    Metrics collection is forced on so every entry carries a
    per-config "metrics" block (op counts, wire bytes, timers) that
    tools/analyze_bench.py correlates with the throughput numbers."""
    import jax

    _metrics_enable()
    platform = jax.devices()[0].platform
    out = _SUBPROCESS_CONFIGS[name](platform)
    got = out if isinstance(out, list) else [out]
    snap = _metrics_snapshot()
    prof = _profile_block()
    for g in got:
        g.setdefault("platform", platform)
        if snap is not None:
            g["metrics"] = snap
        if prof is not None:
            g["profile"] = prof
        print("BENCH_ENTRY " + json.dumps(g), flush=True)


def _spawn_config(entries, name: str, timeout_s: float = None):
    """Run one config in a fresh process (fresh TPU client)."""
    import os
    import subprocess

    timeout_s = timeout_s or _CONFIG_TIMEOUT_S
    _progress(f"config subprocess: {name}")
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", name],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        _progress(f"  TIMEOUT after {timeout_s:.0f}s")
        entries.append(_failure_record(
            name, f"timeout {timeout_s:.0f}s", exc_type="timeout",
            elapsed_s=time.time() - t0, retries=_failure_count(name),
        ))
        return None
    got = []
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_ENTRY "):
            got.append(json.loads(line[len("BENCH_ENTRY "):]))
    if not got:
        tail = (proc.stderr or "")[-400:]
        _progress(f"  FAILED rc={proc.returncode}: {tail}")
        entries.append(_failure_record(
            name, tail or f"rc={proc.returncode}",
            exc_type="SubprocessFailed", elapsed_s=time.time() - t0,
            retries=_failure_count(name),
        ))
        return None
    for g in got:
        _progress(f"  {g}")
    entries.extend(got)
    return got


# ---------------------------------------------------------------------------
# Self-healing state (round-4 VERDICT item 2): every successful config
# run is merged into a state file the moment it finishes, and a daemon
# mode keeps re-probing the device until a deadline. One outage
# can then no longer blank a round: the round-end main() reuses any
# entry the daemon captured while the chip was up.
# ---------------------------------------------------------------------------

import os

_STATE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "benchmarks",
    "bench_state.json",
)
_DAEMON_PID_PATH = _STATE_PATH + ".pid"


def _load_state() -> dict:
    try:
        with open(_STATE_PATH) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {"entries": {}}


def _merge_state(config: str, got: list) -> None:
    """Merge one config's entries into the state file atomically
    (tmp+rename: a reader never sees a half-written file)."""
    state = _load_state()
    state["entries"][config] = {
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "results": got,
    }
    os.makedirs(os.path.dirname(_STATE_PATH), exist_ok=True)
    tmp = _STATE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(state, f, indent=1)
    os.replace(tmp, _STATE_PATH)


def _note_failure(config: str) -> None:
    state = _load_state()
    fails = state.setdefault("failures", {})
    fails[config] = fails.get(config, 0) + 1
    os.makedirs(os.path.dirname(_STATE_PATH), exist_ok=True)
    tmp = _STATE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(state, f, indent=1)
    os.replace(tmp, _STATE_PATH)


def _failure_count(config: str) -> int:
    return _load_state().get("failures", {}).get(config, 0)


def _state_results(config: str):
    got = _load_state()["entries"].get(config)
    if not got:
        return None
    results = [dict(r) for r in got["results"]]
    for r in results:
        r["source"] = "daemon_retry_loop"
        r["measured_at"] = got["measured_at"]
    return results


def _stop_daemon() -> None:
    """Kill a live daemon before a foreground ladder run: two processes
    contending for the single chip corrupt both timings."""
    import signal

    try:
        with open(_DAEMON_PID_PATH) as f:
            pid = int(f.read().strip())
        os.kill(pid, signal.SIGTERM)
        _progress(f"stopped bench daemon pid {pid}")
        time.sleep(2)
    except (OSError, ValueError):
        pass


def daemon(deadline_s: float, probe_every_s: float = 300.0) -> None:
    """Retry-until-deadline loop: probe the device, run every ladder
    config that has no successful state entry yet (one subprocess each,
    merged into the state file as it lands), sleep, repeat. Exits at the
    deadline or when the ladder is complete."""
    deadline = time.time() + deadline_s
    os.makedirs(os.path.dirname(_STATE_PATH), exist_ok=True)
    with open(_DAEMON_PID_PATH, "w") as f:
        f.write(str(os.getpid()))
    try:
        while time.time() < deadline:
            pending = [c for c in _LADDER if not _state_results(c)]
            if not pending:
                _progress("daemon: ladder complete")
                return
            if not _probe_device():
                _progress(
                    f"daemon: device down; {len(pending)} pending; "
                    f"sleeping {probe_every_s:.0f}s"
                )
                time.sleep(min(probe_every_s, max(deadline - time.time(), 0)))
                continue
            progressed = False
            for cfg in pending:
                if time.time() >= deadline:
                    return
                if _failure_count(cfg) >= 3:
                    continue  # deterministic failure: stop burning chip time
                entries: list = []
                got = _spawn_config(entries, cfg)
                if got:
                    _merge_state(cfg, got)
                    progressed = True
                else:
                    _note_failure(cfg)
                    # crash/timeout with the device up: re-probe before
                    # trying anything else (the worker may be poisoned)
                    break
            if not progressed:
                time.sleep(min(probe_every_s, max(deadline - time.time(), 0)))
    finally:
        try:
            os.remove(_DAEMON_PID_PATH)
        except OSError:
            pass


def _probe_log(level: str, msg: str, **fields) -> None:
    """Device-probe events on the observability plane (utils/log.py
    `probe` channel, gated by SPARK_RAPIDS_TPU_LOG_LEVEL) — lazy import so the
    bench stays runnable from a checkout without the package installed."""
    try:
        from spark_rapids_jni_tpu.utils import log as _srt_log

        _srt_log.log(level, "probe", msg, **fields)
    except Exception:
        pass


def _probe_device(timeout_s: int = 150) -> bool:
    """Cheap liveness check: probe jax.devices() in a killable
    subprocess before paying per-config timeouts."""
    import subprocess

    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=timeout_s,
        )
        up = out.returncode == 0 and bool(out.stdout.strip())
        _probe_log(
            "INFO" if up else "WARN",
            "probe_up" if up else "probe_failed",
            rc=out.returncode,
        )
        _flight_note(
            "probe.device_up" if up else "probe.device_failed",
            out.returncode,
        )
        return up
    except subprocess.TimeoutExpired:
        _probe_log("WARN", "probe_timeout", timeout_s=timeout_s)
        _flight_note("probe.device_timeout", timeout_s)
        return False


# last headline line printed: the SIGTERM handler re-prints it so the
# FINAL stdout line is parseable JSON even when the driver's timeout
# fires mid-config (rounds ended rc=124, parsed=null twice because the
# kill landed between a progress line and the next emit)
_LAST_LINE = None


def _install_exit_handlers():
    """`timeout -k` sends SIGTERM before SIGKILL: use the grace window
    to flush the telemetry dumps (METRICS_DUMP + FLIGHT_DUMP — atexit
    never runs past os._exit) and re-print the last headline JSON as
    the final stdout line."""
    import signal

    def _on_term(signum, frame):  # pragma: no cover - signal path
        _flight_note("bench.sigterm", signum)
        line = _LAST_LINE
        if not line:
            # killed before the first emit (daemon stop / state read /
            # device probe can all hang into the kill window): the
            # final stdout line must STILL be parseable JSON
            line = json.dumps({
                "metric": "groupby_sum_100M_int64", "value": None,
                "unit": "rows/s", "vs_baseline": None,
                "platform": "unreachable",
                "headline_source": "sigterm_before_first_emit",
                "configs": [],
            })
        # headline FIRST, telemetry second: the re-printed line is
        # the one deliverable the driver parses, so nothing that
        # could conceivably block (file IO, lock acquisition in the
        # dump path) may run before it. Leading newline: the kill
        # may land mid-write of a large emit, and appending to a
        # torn partial line would make the final line unparseable.
        print("\n" + line, flush=True)
        _flush_telemetry()
        os._exit(0)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # pragma: no cover - non-main thread
        pass


def _emit(entries, platform, arrow_rows_per_s=None):
    """Print the ONE headline JSON line, complete with everything
    measured so far, and flush. Called once up front and again after
    every config lands (round-4 postmortem: the r4 run was SIGKILLed
    before its single end-of-run print, publishing nothing although
    per-config results existed — a kill at any instant must still
    leave the last flushed line parseable)."""
    med_big = None
    big_entry = None
    for e in entries:
        if (
            str(e.get("name", "")).startswith("groupby_sum_100M")
            and "seconds_median" in e
        ):
            s = e["seconds_median"]
            if med_big is None or s < med_big:
                med_big, big_entry = s, e
    if med_big:
        rows_per_s = 100_000_000 / med_big
        vs = rows_per_s / arrow_rows_per_s if arrow_rows_per_s else float("nan")
        # provenance must distinguish a this-run measurement from a
        # daemon-state entry captured at an earlier (possibly stale) time
        if big_entry.get("source") == "daemon_retry_loop":
            source = f"daemon_retry_loop({big_entry.get('measured_at')})"
        else:
            source = "measured"
    else:
        rows_per_s = vs = float("nan")
        source = "none"

    def _num(x, nd):
        # null, not NaN: json.dumps would emit the bare token `NaN`,
        # which strict parsers (jq, JSON.parse) reject
        return round(x, nd) if x == x else None

    global _LAST_LINE
    _LAST_LINE = json.dumps(
        {
            "metric": "groupby_sum_100M_int64",
            "value": _num(rows_per_s, 1),
            "unit": "rows/s",
            "vs_baseline": _num(vs, 3),
            "platform": platform,
            "headline_source": source,
            "drift": _drift_block(),
            "budget": _BUDGET_DOC,
            "configs": entries,
            "note": (
                "Line re-printed after every config (take the LAST "
                "parseable line): a timeout kill mid-ladder must not "
                "blank already-measured work. headline_source=none "
                "means no 100M groupby landed this run and value is "
                "null. All device timings sync by host fetch; "
                "vs_baseline is CPU Arrow on "
                "the same 100M shape; configs[] carries the ladder "
                "with achieved GB/s vs HBM peak."
            ),
        }
    )
    print(_LAST_LINE, flush=True)


def main():
    # wall-clock budget (SRT_BENCH_BUDGET_S, default below the driver's
    # kill timeout; SRT_BENCH_DEADLINE_S kept as the legacy alias):
    # when exceeded, remaining configs are SKIPPED with structured
    # records and the headline line is still the last thing printed
    if "SRT_BENCH_BUDGET_S" in os.environ:
        budget_src = "env:SRT_BENCH_BUDGET_S"
    elif "SRT_BENCH_DEADLINE_S" in os.environ:
        budget_src = "env:SRT_BENCH_DEADLINE_S"
    else:
        budget_src = "default"
    budget_s = float(
        os.environ.get(
            "SRT_BENCH_BUDGET_S",
            os.environ.get("SRT_BENCH_DEADLINE_S", 3300),
        )
    )
    global _BUDGET_DOC
    _BUDGET_DOC = _budget_doc(budget_s, budget_src)
    t_start = time.time()
    deadline = t_start + budget_s
    # the arm walk's own deadline: earlier than the budget deadline by
    # the tail reserve, so the mesh stages and Arrow baseline always
    # get their window (see _TAIL_RESERVE_S)
    walk_deadline = deadline - _TAIL_RESERVE_S
    entries = []
    platform = "unreachable"
    _install_exit_handlers()  # SIGTERM re-prints the headline JSON
    _metrics_enable()  # every measured entry carries a "metrics" block
    # first emit BEFORE anything that can block (daemon stop sleeps,
    # state reads hit disk): from here on a kill at any instant leaves
    # a parseable headline as the last stdout line
    _emit(entries, platform)

    # Stop the daemon BEFORE reading state: a merge landing between the
    # prefill read and a later kill would otherwise be invisible here
    # while also suppressing the error entry for that config below.
    _stop_daemon()  # no chip contention with a live retry loop

    # Before anything that can hang (device probe, CPU-mesh subprocess,
    # Arrow baseline): publish the best line we can assemble from the
    # daemon state file.
    for key in _LADDER:
        got = _state_results(key)
        if got:
            entries.extend(got)
            if platform == "unreachable":
                platform = got[0].get("platform", platform)
    _emit(entries, platform)

    t_probe = time.time()
    probe_retries = 0
    probe_backoff_ms = 0.0
    alive = _probe_device()
    if not alive:
        # jittered backoff from the shared retry plane before the one
        # re-probe: a device mid-restart often answers a beat later
        try:
            from spark_rapids_jni_tpu.utils import faults as _faults

            probe_backoff_ms = _faults.backoff_ms(1, "bench.probe")
        except Exception:
            probe_backoff_ms = 0.0
        _progress(
            "device probe failed: retrying once "
            f"after {probe_backoff_ms:.0f}ms"
        )
        _flight_note("probe.device_retry")
        time.sleep(probe_backoff_ms / 1e3)
        probe_retries = 1
        alive = _probe_device()
    probe_elapsed = time.time() - t_probe
    if alive:
        for i, key in enumerate(_LADDER):
            # headline arms may run to the walk deadline; extended arms
            # need a further reserve so cheap arms behind them survive
            floor = (
                0.0 if key in _HEADLINE_LADDER else _EXTENDED_FLOOR_S
            )
            if time.time() > walk_deadline - floor:
                # budget exhausted: skip the rest with structured
                # records instead of letting each one eat its own
                # timeout past the driver's kill deadline
                _progress(
                    f"bench budget ({budget_s:.0f}s) exhausted at tier "
                    f"{'1' if floor == 0.0 else '2'}; "
                    f"skipping {len(_LADDER) - i} remaining configs"
                )
                for later in _LADDER[i:]:
                    if not _state_results(later):
                        entries.append(_failure_record(
                            later, f"skipped: budget {budget_s:.0f}s "
                            "exhausted", exc_type="BudgetExceeded",
                            elapsed_s=time.time() - t_start, skipped=True,
                        ))
                break
            # drop the daemon-captured entries for this CONFIG KEY (by
            # the state file's own names — a rename of the workload
            # must not let a stale-shape entry survive the supersede)
            stale_names = {
                e.get("name") for e in (_state_results(key) or [])
            }
            fresh: list = []
            got = _spawn_config(
                fresh, key,
                timeout_s=min(_CONFIG_TIMEOUT_S,
                              max(walk_deadline - time.time(), 60)),
            )
            if got:
                _merge_state(key, got)
                entries = [
                    e for e in entries
                    if e.get("source") != "daemon_retry_loop"
                    or e.get("name") not in stale_names
                ]
                entries.extend(got)
                platform = got[0].get("platform", platform)
            elif not _state_results(key):
                entries.extend(fresh)  # the error entry
                # fast-fail ladder: an unreachable-smelling failure +
                # a failed re-probe means the device is gone — mark
                # every remaining device config skipped-unreachable
                # instead of timing each one out serially
                if (
                    fresh
                    and _unreachable_failure(fresh[-1])
                    and not _probe_device()
                ):
                    _progress(
                        "device lost mid-ladder; fast-failing "
                        f"{len(_LADDER) - i - 1} remaining configs"
                    )
                    _flight_note("device.unreachable", key)
                    for later in _LADDER[i + 1:]:
                        if not _state_results(later):
                            entries.append(_failure_record(
                                later,
                                "skipped: device unreachable "
                                f"(fast-fail after {key})",
                                exc_type="DeviceUnreachable",
                                elapsed_s=time.time() - t_start,
                                skipped=True,
                            ))
                    _emit(entries, platform)
                    break
            _emit(entries, platform)
    else:
        for key in _LADDER:
            if not _state_results(key):
                entries.append(_failure_record(
                    key, "device unreachable",
                    exc_type="DeviceUnreachable",
                    elapsed_s=probe_elapsed, retries=probe_retries,
                    backoff_ms=probe_backoff_ms, skipped=True,
                ))
        _emit(entries, platform)

    # CPU-mesh configs. Each arm gets its OWN wall-clock slice, clamped
    # to the budget remaining minus the Arrow reserve: an arm that
    # overruns is killed by its subprocess timeout and recorded as a
    # structured {type:"timeout"} failure — never again the r04 rc=124
    # where a stage started with minutes left and ran unbounded past
    # the driver's kill, leaving parsed=null. The TPC-DS-from-parquet
    # arm is additionally opt-in (SRT_BENCH_MESH_TPCDS=1) AND trimmed
    # to the same 900s slice as the skew arms (_TPCDS_ARM_CAP_S): under
    # its old 1800s cap it could eat the whole tail even when opted in,
    # and the skew arm already exercises the distributed exchange for
    # the headline. The split the run chose is published as the
    # headline's "budget" block.
    mesh_arms = [
        # the adaptive-skew A/B first: it carries the headline skew
        # block (seconds / recv-buffer / RSS deltas, splitting on vs
        # off), so it must land before any budget-tail exhaustion
        ("config 4: adaptive skew split A/B, 8-device CPU mesh",
         bench_mesh_skew_adaptive, _arm_cap(900.0)),
        ("config 4: distributed zipf skew, 8-device CPU mesh",
         bench_distributed_skew, _arm_cap(900.0)),
    ]
    tpcds_name = "config 4: TPC-DS q5/q23/q64 from parquet, 8-dev mesh"
    if os.environ.get("SRT_BENCH_MESH_TPCDS", "").strip().lower() in (
        "1", "true", "yes", "on"
    ):
        mesh_arms.append((tpcds_name, bench_tpcds_distributed,
                          _arm_cap(_TPCDS_ARM_CAP_S)))
    else:
        _progress(
            f"skipping {tpcds_name}: opt-in arm "
            "(set SRT_BENCH_MESH_TPCDS=1)"
        )
        entries.append(_failure_record(
            tpcds_name,
            "skipped: opt-in arm (SRT_BENCH_MESH_TPCDS unset)",
            exc_type="OptInSkipped", skipped=True,
        ))
    for mesh_name, mesh_fn, arm_cap_s in mesh_arms:
        slice_s = min(arm_cap_s, deadline - time.time() - _ARROW_FLOOR_S)
        if slice_s < _MESH_STAGE_FLOOR_S:
            _progress(f"skipping {mesh_name}: budget tail exhausted")
            entries.append(_failure_record(
                mesh_name,
                f"skipped: budget {budget_s:.0f}s exhausted",
                exc_type="BudgetExceeded",
                elapsed_s=time.time() - t_start, skipped=True,
            ))
            _emit(entries, platform)
            continue
        _guard(
            entries, mesh_name,
            lambda fn=mesh_fn, s=slice_s: fn(timeout_s=s),
        )
        _emit(entries, platform)

    # fresh Arrow denominator last: it only refines vs_baseline
    arrow = None
    if time.time() < deadline - _ARROW_FLOOR_S:
        _progress("arrow baseline 100M")
        try:
            arrow = arrow_baseline(100_000_000)
        except Exception:  # pragma: no cover
            arrow = None
    else:
        _progress("skipping arrow baseline: budget tail exhausted")
    _emit(entries, platform, arrow_rows_per_s=arrow)
    if not alive:
        # a measurement path that finds no device fails
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--one":
        _run_one(sys.argv[2])
    elif len(sys.argv) >= 2 and sys.argv[1] == "--daemon":
        # python bench.py --daemon <deadline_seconds> [probe_every_s]
        dl = float(sys.argv[2]) if len(sys.argv) >= 3 else 6 * 3600
        every = float(sys.argv[3]) if len(sys.argv) >= 4 else 300.0
        daemon(dl, every)
    else:
        try:
            main()
        except Exception:
            # exit-clean guarantee: tracebacks go to stderr and the
            # FINAL stdout line stays the last headline JSON
            import traceback

            traceback.print_exc()
            if _LAST_LINE:
                print(_LAST_LINE, flush=True)
            sys.exit(1)
