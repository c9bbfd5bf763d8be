"""Render profiler sessions as a human-readable EXPLAIN ANALYZE tree.

Input is anything that carries profile sessions (utils/profiler.py):

* a ``SPARK_RAPIDS_TPU_PROFILE_DUMP`` file (``{"sessions": [...]}``),
* a flight-recorder dump (sessions ride as the ``profile_sessions``
  exit section),
* a raw session doc, or a bench output file / stdout whose config
  records embed ``profile`` blocks (last-parseable-line discipline).

One line per plan op, annotated with its fused-segment membership;
segment headers carry the wall-time split (compile / execute / serde /
stall — they sum to the segment wall by construction), time %, rows
in/out, pad waste and compile-cache status. ``--json`` emits the
machine form instead.

``--merge`` combines dumps from SEVERAL processes/hosts into one
report ordered on the shared wall clock (profiler.merge_sessions) and
— when the inputs are flight dumps with events — one merged Perfetto
trace with a process track per dump (tracing.merge_chrome_traces),
written to ``-o`` (default: merged.trace.json).

``--static`` switches to plan-time analysis: the input is a plan JSON
file (a list of op objects) rendered as a tagged report — per-op
support tier + reason, inferred output schema, predicted segmentation
and the static HBM footprint bound — without executing anything
(spark_rapids_jni_tpu/plancheck.py, the GpuOverrides tagging analog).
``--schema`` supplies the input column signature as comma-separated
tokens (``int64``, ``decimal64:-2``, ``list<int32>``, ``string``...);
without it the walk is structural only.

``--drift`` renders the plan-stats store (utils/planstats.py) instead:
per-(plan, schema, bucket) group, each segment's observed rows/HBM/
wall-time percentiles next to plancheck's static prediction, plus the
typed drift findings recorded at append time. Inputs are store files
or directories (default: the configured ``PLANSTATS_DIR``).

Usage:
    python tools/explain.py profile.json
    python tools/explain.py --json profile.json
    python tools/explain.py --merge worker0.json worker1.json -o m.json
    python tools/explain.py --static plan.json --schema int64,bool8 --rows 4096
    python tools/explain.py --drift [statsdir]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
# report rendering is pure stdlib, but importing the package pulls jax
# in — keep the reader off the accelerator plugin
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from spark_rapids_jni_tpu.utils.profiler import (  # noqa: E402
    extract_sessions,
    merge_sessions,
)
from spark_rapids_jni_tpu.utils.tracing import (  # noqa: E402
    merge_chrome_traces,
)


def load_doc(path: str):
    """One JSON doc from ``path``, or the LAST parseable line of a
    file of JSON lines."""
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        doc = None
        for line in text.splitlines():
            try:
                doc = json.loads(line)
            except json.JSONDecodeError:
                continue
        if doc is None:
            raise
        return doc


def parse_schema_tokens(spec: str):
    """``int64,decimal64:-2,list<int32>,string`` -> [ColType, ...]."""
    from spark_rapids_jni_tpu import dtype as dt
    from spark_rapids_jni_tpu import plancheck

    cols = []
    for raw in spec.split(","):
        tok = raw.strip()
        if not tok:
            continue
        scale = 0
        child = None
        if tok.lower().startswith("list<") and tok.endswith(">"):
            child = dt.TypeId[tok[5:-1].strip().upper()]
            tid = dt.TypeId.LIST
        else:
            if ":" in tok:
                tok, scale_s = tok.split(":", 1)
                scale = int(scale_s)
            tid = dt.TypeId[tok.strip().upper()]
        cols.append(plancheck.ColType(tid, scale, child))
    return cols


def run_drift(args) -> int:
    """--drift: render the plan-stats store as predicted-vs-observed
    per-segment history with percentiles (utils/planstats.py). Inputs
    are stats-store files or directories; with none, the configured
    ``SPARK_RAPIDS_TPU_PLANSTATS_DIR`` (or its tempdir default)."""
    from spark_rapids_jni_tpu.utils import planstats

    records = []
    paths = args.inputs or [planstats.stats_dir()]
    for p in paths:
        records.extend(planstats.load(p))
    if not records:
        print(
            "explain: no plan-stats records in "
            + ", ".join(repr(p) for p in paths)
            + " (was SPARK_RAPIDS_TPU_PLANSTATS on?)",
            file=sys.stderr,
        )
        return 1
    report = planstats.drift_report(records)
    if args.as_json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(planstats.render_drift(report))
    return 0


def run_static(args) -> int:
    """--static: tag a plan file without executing it."""
    from spark_rapids_jni_tpu import plancheck

    rc = 0
    out = []
    for path in args.inputs:
        with open(path) as f:
            ops = json.load(f)
        schema = (
            parse_schema_tokens(args.schema) if args.schema else None
        )
        report = plancheck.analyze(ops, schema=schema, rows=args.rows)
        if args.as_json:
            out.append(json.dumps(report, indent=1, sort_keys=True))
        else:
            out.append(f"== {path} ==\n" + plancheck.render_report(report))
        if not report["ok"]:
            rc = 1
    print("\n\n".join(out))
    return rc


def _ms(seconds) -> str:
    return f"{float(seconds or 0.0) * 1e3:.2f}ms"


def _bytes_h(n) -> str:
    n = int(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return f"{n}B"


def _cache_status(seg: dict) -> str:
    hits = int(seg.get("cache_hits") or 0)
    misses = int(seg.get("cache_misses") or 0)
    if hits == 0 and misses == 0:
        return "cache -"
    return f"cache {hits}H/{misses}M"


def render_session(doc: dict) -> str:
    """One session doc -> the EXPLAIN ANALYZE tree."""
    lines = []
    wall = float(doc.get("wall_s") or 0.0)
    head = (
        f"EXPLAIN ANALYZE  session={doc.get('session_id', '?')}"
        f"  label={doc.get('label', '?')}"
        f"  pid={doc.get('pid', '?')}@{doc.get('host', '?')}"
        f"  wall={_ms(wall)}"
    )
    if doc.get("batches") is not None:
        head += f"  batches={doc['batches']}"
    lines.append(head)
    segs = doc.get("segments", []) or []
    plan = doc.get("plan") or []
    fused = sum(1 for s in segs if s.get("kind") == "fused")
    launches = sum(int(s.get("launches") or 0) for s in segs)
    hits = sum(int(s.get("cache_hits") or 0) for s in segs)
    misses = sum(int(s.get("cache_misses") or 0) for s in segs)
    lines.append(
        f"plan: {len(plan) or sum(len(s.get('ops', [])) for s in segs)}"
        f" ops -> {len(segs)} segments ({fused} fused)"
        f" · launches {launches} (cache {hits}H/{misses}M)"
    )
    for s in segs:
        pct = (100.0 * float(s.get("wall_s") or 0.0) / wall) if wall else 0.0
        calls = int(s.get("calls") or 1)
        hdr = (
            f"  Segment {s.get('index', '?')} [{s.get('kind', '?')}"
            + (f" x{calls}" if calls > 1 else "")
            + f"]  {pct:5.1f}%  {_ms(s.get('wall_s'))}"
            f"  (compile {_ms(s.get('compile_s'))}"
            f" + execute {_ms(s.get('execute_s'))}"
            f" + serde {_ms(s.get('serde_s'))}"
            f" + stall {_ms(s.get('stall_s'))})"
        )
        lines.append(hdr)
        detail = (
            f"      rows {int(s.get('rows_in') or 0)}"
            f" -> {int(s.get('rows_out') or 0)}"
            f" · {_cache_status(s)}"
        )
        if s.get("pad_rows"):
            detail += (
                f" · pad {int(s['pad_rows'])} rows"
                f"/{_bytes_h(s.get('pad_waste_bytes'))}"
            )
        if s.get("donated_bytes"):
            detail += f" · donated {_bytes_h(s['donated_bytes'])}"
        if s.get("fallbacks"):
            detail += f" · FALLBACKS {int(s['fallbacks'])}"
        lines.append(detail)
        ops = s.get("ops", []) or []
        for j, op in enumerate(ops):
            branch = "└─" if j == len(ops) - 1 else "├─"
            member = (
                f"seg {s.get('index', '?')} · {s.get('kind', '?')}"
            )
            lines.append(f"      {branch} {op}  [{member}]")
    b = doc.get("boundary") or {}
    extras = []
    if b.get("serde_s") or b.get("serde_bytes_in") or b.get(
        "serde_bytes_out"
    ):
        extras.append(
            f"serde {_ms(b.get('serde_s'))}"
            f" (in {_bytes_h(b.get('serde_bytes_in'))}"
            f" / out {_bytes_h(b.get('serde_bytes_out'))})"
        )
    if b.get("stall_s"):
        extras.append(f"stall {_ms(b.get('stall_s'))}")
    if b.get("compile_s"):
        extras.append(f"compile {_ms(b.get('compile_s'))}")
    if b.get("pad_rows"):
        extras.append(
            f"pad {int(b['pad_rows'])} rows"
            f"/{_bytes_h(b.get('pad_waste_bytes'))}"
        )
    if b.get("shuffles"):
        extras.append(
            f"shuffles {int(b['shuffles'])}"
            f" ({int(b.get('shuffle_rows') or 0)} rows)"
        )
    if extras:
        lines.append("  boundary (outside segments): " + " · ".join(extras))
    ua = float(doc.get("unattributed_s") or 0.0)
    if wall:
        lines.append(
            f"  unattributed: {_ms(ua)} ({100.0 * ua / wall:.1f}%)"
        )
    return "\n".join(lines)


def render_merged(merged: dict) -> str:
    """A profiler.merge_sessions document -> one multi-process report."""
    lines = []
    procs = merged.get("processes", []) or []
    sess = merged.get("sessions", []) or []
    lines.append(
        f"MERGED PROFILE  {len(procs)} process(es), "
        f"{len(sess)} session(s)"
    )
    for p in procs:
        ids = ", ".join(str(s)[:8] for s in p.get("session_ids", []))
        lines.append(
            f"  process {p.get('host', '?')}:{p.get('pid', '?')}"
            f"  sessions: {ids}"
        )
    for s in sess:
        lines.append("")
        lines.append(render_session(s))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="profiler sessions -> EXPLAIN ANALYZE report",
    )
    ap.add_argument(
        "inputs", nargs="*",
        help="profile dump / flight dump / bench output file(s); with "
        "--drift, stats-store files/directories (default: the "
        "configured store directory)",
    )
    ap.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit the machine-readable document instead of the tree",
    )
    ap.add_argument(
        "--merge", action="store_true",
        help="merge multiple process dumps into one report (+ one "
        "Perfetto trace when the inputs carry flight events)",
    )
    ap.add_argument(
        "-o", "--output",
        help="merged Perfetto trace path (with --merge; default: "
        "merged.trace.json)",
    )
    ap.add_argument(
        "--static", action="store_true",
        help="inputs are plan JSON files: render the plancheck tagged "
        "report (tiers, inferred schemas, predicted segments, HBM "
        "bound) without executing; exit 1 if any plan is rejected",
    )
    ap.add_argument(
        "--schema",
        help="with --static: input column signature, comma-separated "
        "(int64, decimal64:-2, list<int32>, string, ...)",
    )
    ap.add_argument(
        "--rows", type=int,
        help="with --static: input row-count bound for the footprint "
        "estimate",
    )
    ap.add_argument(
        "--drift", action="store_true",
        help="inputs are plan-stats store files/dirs (utils/"
        "planstats.py): render predicted-vs-observed per-segment "
        "history with percentiles + typed drift findings",
    )
    args = ap.parse_args(argv)
    if args.drift:
        return run_drift(args)
    if not args.inputs:
        ap.error("inputs are required (except with --drift)")
    if args.static:
        return run_static(args)
    if len(args.inputs) > 1 and not args.merge:
        args.merge = True
    docs = [load_doc(p) for p in args.inputs]

    if args.merge:
        merged = merge_sessions(docs)
        if not merged["sessions"]:
            print(
                "explain: no profile sessions in "
                + ", ".join(repr(p) for p in args.inputs)
                + " (was SPARK_RAPIDS_TPU_PROFILE on?)",
                file=sys.stderr,
            )
            return 1
        if args.as_json:
            print(json.dumps(merged, indent=1, sort_keys=True))
        else:
            print(render_merged(merged))
        # one merged Perfetto timeline from whichever inputs are flight
        # dumps with events (wall-clock aligned, one process track per
        # dump)
        flight_docs = [
            d for d in docs
            if isinstance(d, dict) and isinstance(d.get("events"), list)
            and d["events"]
        ]
        if flight_docs:
            trace = merge_chrome_traces(flight_docs)
            out_path = args.output or "merged.trace.json"
            with open(out_path, "w") as f:
                json.dump(trace, f, indent=1, sort_keys=True)
                f.write("\n")
            print(
                f"\nwrote {out_path}: {len(trace['traceEvents'])} trace "
                f"events across {len(flight_docs)} process(es) — open "
                "at https://ui.perfetto.dev",
                file=sys.stderr,
            )
        return 0

    sessions = extract_sessions(docs[0])
    if not sessions:
        print(
            f"explain: no profile sessions in {args.inputs[0]!r} "
            "(was SPARK_RAPIDS_TPU_PROFILE on?)",
            file=sys.stderr,
        )
        return 1
    if args.as_json:
        print(json.dumps(sessions, indent=1, sort_keys=True))
        return 0
    out = []
    for s in sessions:
        out.append(render_session(s))
    print("\n\n".join(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
