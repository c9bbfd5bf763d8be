"""One cell, once: ``python3 -m perfbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

The server's threads and the load generator live in this one process,
which holds the chip. Set-up (data from the seed, uploads, one warm-up
request a session) is counted from the process's start; then closed-loop
sessions send requests until ``--seconds`` have passed and those in
flight finish. Once the window has closed the plain reference is run and
every kept answer compared. The last line of standard output is the
result. ``--rehearse`` runs tiny sizes on the CPU and prints no metric;
``--control 1`` also puts the lower-precision reference in the program's
place, which the check has to refuse.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "perfbench_out")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def load_cell(name: str):
    """The cell's entry in BENCHMARK.json with its configuration, its
    traffic and the per-layer metrics that list it (or list no cell)."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(files[cell["config"]])
    traffic = load_json("perfbench", "traffic", cell["traffic"] + ".json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [dict(m, **load_json("perfbench", "layer_metrics", reader_file(m["name"])))
             for m in bench["per_layer"] if name in m.get("workloads", [name])]
    return cell, config, traffic, e2e, layer


def reader_file(metric: str) -> str:
    """A quantity split by the end-to-end metric it moves (``x.convert``
    beside ``x``) is read one way: ``x.convert.json``, or ``x.json``."""
    for stem in (metric, metric.split(".")[0]):
        if os.path.exists(os.path.join(ROOT, "perfbench", "layer_metrics", stem + ".json")):
            return stem + ".json"
    raise SystemExit(f"perfbench: no reader file for per-layer metric {metric!r}")


def timer_deltas(before: dict, after: dict) -> dict:
    out = {}
    for k, t in after.items():
        b = before.get(k, {"count": 0, "total_s": 0.0})
        out[k] = {"count": t["count"] - b["count"],
                  "total_s": t["total_s"] - b["total_s"]}
    return out


def run_sessions(sessions, fn) -> None:
    """``fn(k, session)`` on one thread a session; re-raises the first
    failure once all have ended."""
    errors = []

    def guarded(k, s):
        try:
            fn(k, s)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(k, s), name=f"load-{k}")
               for k, s in enumerate(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compare the lower-precision reference, which must fail")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    return ap.parse_args(argv)


def look_for_chip(args, chips: int):
    """-> (devices, peaks of the device kind), or None where the run may
    not go on: no TPU (and no rehearsal), or fewer chips than the cell asks."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    emit({"device": {"platform": d0.platform, "kind": d0.device_kind,
                     "count": len(devices)}, "workload": args.workload,
          "seed": args.seed,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    if d0.platform != "tpu" and not args.rehearse:
        print(f"perfbench: no TPU (platform={d0.platform!r})", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"perfbench: {args.workload} needs {chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return None
    peaks = None
    if d0.platform == "tpu":
        table = load_json("perfbench", "peaks.json")
        if d0.device_kind not in table:
            raise SystemExit(f"perfbench: no peaks for device kind {d0.device_kind!r}")
        peaks = table[d0.device_kind]
    return devices, peaks


def trace_one_request(sessions, variant_of, workload: str) -> dict:
    """One request a session under the profiler, reduced in-process; only
    the reduced JSON stays."""
    import jax

    from . import trace_reduce

    tdir = os.path.join(OUT_DIR, "trace-" + workload)
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            run_sessions(sessions, lambda k, s: s.request(variant_of(k, 0)))
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.reduce(
        trace_reduce.load(trace_reduce.find_xplane(tdir)), requests=1)
    shutil.rmtree(tdir, ignore_errors=True)
    with open(os.path.join(OUT_DIR, workload + ".trace.json"), "w") as f:
        json.dump(trace, f)
    return trace


def serve_and_measure(args, traffic, data, devices, watched) -> dict:
    """Set-up, the window and (``--trace 1`` on a chip) the traced request,
    all against one in-process server."""
    import numpy as np

    from spark_rapids_jni_tpu import serving
    from spark_rapids_jni_tpu.utils import metrics

    from . import script

    n_sessions = int(traffic["sessions"])
    variants = data.variants
    order = np.random.default_rng([args.seed, 1 << 20]).permutation(variants)
    pick = np.random.default_rng([args.seed, 1 << 21])
    keep = int(traffic["keep_answers"])
    records, kept, failures, lock = [], [], [], threading.Lock()

    def variant_of(k, i):
        return int(order[(i * n_sessions + k) % variants])

    with serving.Server(session_hbm_fraction=1.0, workers=3).start() as srv:
        clients = [serving.Client(srv.port, timeout=1200.0,
                                  mesh=traffic.get("mesh") or None).connect()
                   for _ in range(n_sessions)]
        try:
            sessions = [script.Session(c, data, traffic["request"]) for c in clients]
            for k, s in enumerate(sessions):
                s.upload_resident()
                s.request(variant_of(k, 0))  # warms this cell's plan, no other
            if n_sessions > 1:
                # and once side by side, as the window runs them: the first
                # concurrent round is slower than every later one
                run_sessions(sessions, lambda k, s: s.request(variant_of(k, 0)))
            for s in sessions:
                s.serde_s = 0.0
            setup_s = time.perf_counter() - _T0
            emit({"setup_s": setup_s, "compiles_in_setup": int(
                metrics.counter_values(["compile_cache.miss"])["compile_cache.miss"])})

            c0 = metrics.counter_values(watched)
            t0 = metrics.snapshot()["timers"]
            t_open = time.perf_counter()
            deadline = t_open + args.seconds

            def load(k, s):
                i = 0
                while time.perf_counter() < deadline:
                    v = variant_of(k, i)
                    i += 1
                    began = time.perf_counter()
                    try:
                        answers = s.request(v)
                    except Exception as e:  # noqa: BLE001 - counted, reported
                        with lock:
                            failures.append(f"{type(e).__name__}: {e}")
                            if len(failures) >= 3:
                                return
                        continue
                    ended = time.perf_counter()
                    with lock:
                        records.append((began - t_open, ended - t_open, k, v))
                        # a seeded reservoir of the answers to compare
                        if len(kept) < keep:
                            kept.append((v, answers))
                        else:
                            j = int(pick.integers(0, len(records)))
                            if j < keep:
                                kept[j] = (v, answers)

            run_sessions(sessions, load)
            c1 = metrics.counter_values(watched)
            out = {
                "setup_s": setup_s, "records": records, "kept": kept,
                "failures": failures,
                "counters": {k: c1[k] - c0[k] for k in watched},
                "timers": timer_deltas(t0, metrics.snapshot()["timers"]),
                "stats": clients[0].stats(),
                "clocks": {"serde_s": sum(s.serde_s for s in sessions)},
                "trace": None, "memory": {},
            }
            if devices[0].platform == "tpu":
                if args.trace:
                    out["trace"] = trace_one_request(
                        sessions, variant_of, args.workload)
                per_chip = [d.memory_stats() or {} for d in devices]
                out["memory"] = max(
                    per_chip, key=lambda m: m.get("peak_bytes_in_use", 0))
            return out
        finally:
            for c in clients:
                c.close()


def check_answers(args, config, traffic, data, m, rows_in: int) -> bool:
    """The plain reference over every kept answer, the counters against
    their limits; prints each number beside its limit."""
    from . import compare, reference

    t_check = time.perf_counter()
    tol = float(config["guarantees"]["float64_sum_tol"])
    steps, specs = traffic["request"], traffic["answers"]
    refs, results = {}, []
    for v, answers in m["kept"]:
        if v not in refs:
            refs[v] = reference.run_request(steps, data.env(v))
        results += [compare.compare(got, refs[v][n], specs[n], tol)
                    for n, got in answers.items()]
    check = compare.fold(results)
    delta, done = m["counters"], len(m["records"])
    zero = {k: delta[k] for k in traffic["zero_counters"]}
    expect = traffic.get("expect_counters", {})
    due = {k: (rows_in if v == "rows_in" else int(v)) * done
           for k, v in expect.items()}
    correct = bool(
        check.pop("ok") and done > 0 and not m["failures"]
        and delta["compile_cache.miss"] == 0 and not any(zero.values())
        and all(delta[k] == due[k] for k in expect)
    )
    check.update({
        "compiles_in_window": delta["compile_cache.miss"], "compiles_limit": 0,
        "zero_counters": zero, "zero_limit": 0,
        "counters_moved": {k: delta[k] for k in expect}, "counters_due": due,
        "failed_requests": len(m["failures"]), "failed_limit": 0,
        "check_s": time.perf_counter() - t_check,
    })
    emit({"check": check, "failures": m["failures"][:3]})
    if args.control:
        low = {v: reference.run_request(steps, data.env(v), lowprec=True)
               for v in sorted(refs)}
        ctl = compare.fold(compare.compare(low[v][n], refs[v][n], specs[n], tol)
                           for v in low for n in refs[v])
        emit({"control": "reference in float32",
              "control_correct": ctl.pop("ok"), "check": ctl})
    return correct


def main(argv=None) -> int:
    args = parse_args(argv)
    cell, config, traffic, e2e, layer = load_cell(args.workload)
    chips = int(cell["chips"])

    import jax

    from spark_rapids_jni_tpu.utils import config as program_config

    from . import readers, script

    if not args.rehearse:
        program_config.place_compile_cache()
        # the persistent cache keeps the small programs too, so that a
        # second run of a cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    found = look_for_chip(args, chips)
    if found is None:
        return 1
    devices, peaks = found[0][:chips], found[1]
    d0 = devices[0]
    on_chip = d0.platform == "tpu"
    program_config.set_flag("METRICS", True)  # the counters `correct` reads
    if not on_chip:
        program_config.set_flag("KERNELS", "on")  # the same kernels, interpreted
    os.makedirs(OUT_DIR, exist_ok=True)

    data = script.Data(config, traffic, args.seed, args.rehearse)
    rows_in = script.rows_in(traffic, data)
    watched = (list(traffic["zero_counters"])
               + list(traffic.get("expect_counters", {})) + ["compile_cache.miss"])
    m = serve_and_measure(args, traffic, data, devices, watched)
    correct = check_answers(args, config, traffic, data, m, rows_in)

    records, failures = m["records"], m["failures"]
    done = len(records)
    secs = sorted(e - b for b, e, _, _ in records)
    with open(os.path.join(OUT_DIR, args.workload + ".requests.json"), "w") as f:
        json.dump([{"began_s": b, "seconds": e - b, "session": k, "variant": v}
                   for b, e, k, v in records], f)
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": chips,
              "memory_peak_bytes": m["memory"].get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": done + len(failures),
              "failed": len(failures), "metrics": {}, "device": device}
    if args.rehearse or not on_chip:
        result["rehearsal"] = True  # tiny sizes or a CPU: no metric is reported
    if "rehearsal" in result or not records:
        emit(result)
        return 0
    span = max(e for _, e, _, _ in records)
    emit({"server_sessions": [
        {k: x.get(k) for k in ("name", "requests", "queue_wait", "latency")}
        for x in m["stats"].get("sessions", [])]})
    emit({"requests": done, "min_s": secs[0], "median_s": statistics.median(secs),
          "max_s": secs[-1], "rows_each": rows_in, "last_completion_s": span})
    if args.trace:
        trace = m["trace"]
        ctx = dict(m, requests=done, peaks=peaks, config=config,
                   traffic=traffic, rows_in=rows_in)
        for spec in layer:
            v = readers.read(spec, ctx)
            if v is not None:
                result["metrics"][spec["name"]] = {"value": v, "unit": spec["unit"]}
        device.update({"busy_s": trace["busy_s"], "window_s": trace["window_s"]})
        result["breakdown"] = trace["breakdown"]
    else:
        # an end-to-end metric is one of four statistics, told by its ending
        values = {
            "setup_s": m["setup_s"],
            "rows_per_s": rows_in * done / span,
            "p50_s": statistics.median(secs),
            "p95_s": secs[math.ceil(0.95 * len(secs)) - 1],  # nearest rank
        }
        for x in e2e:
            (stat,) = [k for k in values if x["name"].endswith(k)]
            result["metrics"][x["name"]] = {"value": values[stat], "unit": x["unit"]}
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
