"""Benchmark runner: one JSON line per configuration.

Usage:
  python -m benchmarks.run [--rows N] [--devices D] [--configs 3,4]

Config 3 (single-chip joins/queries) runs on the default device (the
real TPU under the driver). Config 4 (distributed q5/q23/q64) needs a
multi-device mesh — on a one-chip box, run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
to exercise the shuffle path; the numbers are then CPU-simulation
numbers and are labeled as such.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax

from spark_rapids_jni_tpu.utils import config

from . import datagen, queries


def _time(fn, *args, repeats=1):
    out = fn(*args)  # warmup/compile (eager queries cache per-shape)
    jax.block_until_ready(jax.tree.leaves(out))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(jax.tree.leaves(out))
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size for distributed configs (0 = skip)")
    ap.add_argument("--configs", default="3")
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args()
    configs = {c.strip() for c in args.configs.split(",")}
    unknown = configs - {"3", "4", "skew"}
    if unknown:
        raise SystemExit(
            f"unknown configs {sorted(unknown)}: this runner implements 3 "
            "(single-chip), 4 (distributed) and skew (distributed zipf "
            "groupby at 1e7 rows); config 5 is config 4 at full scale on "
            "real hardware"
        )
    if "skew" in configs and not args.devices:
        raise SystemExit("--configs skew needs --devices N")
    if "4" in configs and not args.devices:
        raise SystemExit("--configs 4 needs --devices N")

    # Platform forcing must happen after argparse (so abbreviations like
    # --device work) but before anything touches the backend: on a
    # one-chip box a multi-device run means the forced host platform.
    if args.devices and "xla_force_host_platform_device_count" in os.environ.get(
        "XLA_FLAGS", ""
    ):
        jax.config.update("jax_platforms", "cpu")

    # Persistent compilation cache: the eager query DAGs compile dozens
    # of per-shape executables; caching makes repeat runs start hot.
    config.place_compile_cache()

    tables = datagen.generate(args.rows)
    platform = jax.devices()[0].platform

    if "3" in configs:
        for name, fn in [("q5", queries.q5), ("q23", queries.q23),
                         ("q64", queries.q64)]:
            secs = _time(fn, tables, repeats=args.repeats)
            print(json.dumps({
                "config": 3, "query": name, "rows": args.rows,
                "seconds": round(secs, 4),
                "rows_per_sec": round(args.rows / secs),
                "platform": platform,
            }))

    if "skew" in configs:
        # Round-3 VERDICT item 5: the r2 skew-OOM shape at real size.
        # Zipf(1.3) keys over >=1e7 rows through the ragged-compact
        # exchange; records wall-clock, the per-device received-buffer
        # rows (must track the hot partition's REAL total, not
        # P x the hottest pair), and peak RSS.
        import resource

        import numpy as np

        from spark_rapids_jni_tpu.column import Table
        from spark_rapids_jni_tpu.ops.groupby import GroupbyAgg
        from spark_rapids_jni_tpu.parallel import distributed_groupby
        from spark_rapids_jni_tpu.parallel.mesh import make_mesh

        from spark_rapids_jni_tpu.utils import config as srt_config
        from spark_rapids_jni_tpu.utils import metrics as srt_metrics

        srt_config.set_flag("METRICS", "1")
        n = max(args.rows, 10_000_000)
        n -= n % args.devices
        rng = np.random.default_rng(5)
        k = np.minimum(rng.zipf(1.3, n), 100_000).astype(np.int64)
        v = rng.integers(-100, 100, n, dtype=np.int64)
        t = Table.from_pydict({"k": k, "v": v})
        mesh = make_mesh(args.devices)

        aggs = [GroupbyAgg("v", "sum"), GroupbyAgg("v", "count")]
        distributed_groupby(t, ["k"], aggs, mesh)  # compile warmup
        t0 = time.perf_counter()
        agg, ngroups, overflow = distributed_groupby(t, ["k"], aggs, mesh)
        total_groups = int(np.asarray(ngroups).sum())
        secs = time.perf_counter() - t0
        hot = int(np.asarray(agg["count_v"].data).max())
        buf_rows = int(agg["k"].data.shape[0]) // args.devices
        peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        assert int(np.asarray(overflow).max()) <= 0
        want_groups = len(np.unique(k))
        assert total_groups == want_groups, (total_groups, want_groups)
        # destination balance after planning: exact planned recv totals
        # when the adaptive splitter fired (gauges), else derived from
        # the raw key distribution (hash skew the planner saw)
        snap = srt_metrics.snapshot()
        gauges = snap.get("gauges") or {}
        splits = int((snap.get("counters") or {}).get(
            "shuffle.skew_splits", 0))

        def _gauge(name):
            g = gauges.get(name)
            return None if g is None else float(g.get("value", 0.0))

        post_ratio = _gauge("shuffle.skew_post_ratio_x100")
        recv_max = _gauge("shuffle.skew_recv_after")
        if splits and post_ratio is not None:
            max_over_mean = post_ratio / 100.0
        else:
            from spark_rapids_jni_tpu.ops.partition import (
                partition_ids_hash,
            )

            pids = np.asarray(partition_ids_hash(t, ["k"], args.devices))
            dest_rows = np.bincount(pids, minlength=args.devices)
            max_over_mean = float(dest_rows.max() / dest_rows.mean())
            recv_max = float(dest_rows.max())
        print(json.dumps({
            "config": "4-skew", "rows": n, "devices": args.devices,
            "seconds": round(secs, 3), "groups": total_groups,
            "hot_key_rows": hot, "recv_buffer_rows_per_device": buf_rows,
            "peak_rss_mb": peak_mb, "platform": platform,
            "skew_splits": splits,
            "max_recv_rows": None if recv_max is None else int(recv_max),
            "max_over_mean": round(max_over_mean, 3),
        }))

    if "4" in configs and args.devices:
        from spark_rapids_jni_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.devices)
        for name, fn in [
            ("q5", queries.q5_distributed),
            ("q23", queries.q23_distributed),
            ("q64", queries.q64_distributed),
        ]:
            secs = _time(fn, tables, mesh, repeats=args.repeats)
            print(json.dumps({
                "config": 4, "query": name, "rows": args.rows,
                "devices": args.devices, "seconds": round(secs, 4),
                "rows_per_sec": round(args.rows / secs),
                "platform": platform,
            }))


if __name__ == "__main__":
    main()
