"""Convert a flight-recorder dump into a chrome://tracing / Perfetto JSON.

Input is either:

* a ``SPARK_RAPIDS_TPU_FLIGHT_DUMP`` file (``{"events": [...], ...}``,
  written at exit / SIGTERM by utils/flight.py), or
* a bench output file (``BENCH_r*.json`` or the raw bench stdout): the
  last parseable JSON line is scanned and every structured failure
  record's ``flight_tail`` is concatenated into one timeline — the
  postmortem view of a run that died with ``"device unreachable"``.

Usage:
    python tools/trace2chrome.py flight.json [-o trace.json]

Open the output at https://ui.perfetto.dev ("Open trace file") or
chrome://tracing ("Load"). Spans appear as per-thread tracks grouped by
subsystem category (dispatch, wire, bucketed, shuffle, ...); counter
samples (``resident.live``, ``bucket.pad_waste_bytes``) appear as
counter tracks; the completion clock's device intervals
(``device.<program>``, utils/devclock.py) appear as a ``device`` lane
beside the threads', each with the span that launched it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
# the converter itself is pure stdlib, but importing the package pulls
# jax in — keep a converter-only import off the accelerator plugin
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from spark_rapids_jni_tpu.utils.tracing import to_chrome_trace  # noqa: E402


def _events_from(doc) -> list:
    """Flight events from a flight dump or a bench summary document."""
    if isinstance(doc, dict) and isinstance(doc.get("events"), list):
        return doc["events"]
    events = []
    if isinstance(doc, dict):
        # bench headline line: collect every failure record's tail
        summary = doc.get("parsed") or doc
        for e in summary.get("configs", []) or []:
            f = e.get("failure")
            if isinstance(f, dict) and isinstance(
                f.get("flight_tail"), list
            ):
                events.extend(f["flight_tail"])
    # several configs may carry the same parent-process tail: dedup by
    # (seq, t_ns) so the timeline doesn't stack identical spans. Older
    # or corrupt dumps may carry non-dict rows — drop them here, the
    # same tolerance the exporter applies (a postmortem tool must read
    # every format that ever wrote a dump)
    seen = set()
    out = []
    for e in events:
        if not isinstance(e, dict):
            continue
        key = (e.get("seq"), e.get("t_ns"))
        if key in seen:
            continue
        seen.add(key)
        out.append(e)
    return out


def load_doc(path: str):
    """Parse ``path`` as one JSON doc, or line-wise (a file of JSON
    lines: take the LAST parseable line)."""
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


def load_events(path: str) -> list:
    return _events_from(load_doc(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="flight-recorder dump -> Chrome-trace/Perfetto JSON"
    )
    ap.add_argument("input", help="flight dump or bench JSON file")
    ap.add_argument(
        "-o", "--output",
        help="output path (default: <input>.trace.json)",
    )
    args = ap.parse_args(argv)
    doc = load_doc(args.input)
    events = _events_from(doc)
    if not events:
        print(
            f"trace2chrome: no flight events in {args.input!r} "
            "(was SPARK_RAPIDS_TPU_FLIGHT_DUMP / FLIGHT enabled?)",
            file=sys.stderr,
        )
        return 1
    # a flight dump carries (pid, host, session_id) process metadata:
    # label the process track so a multi-process Perfetto merge doesn't
    # collide on tid alone
    kw = {}
    if isinstance(doc, dict) and isinstance(doc.get("events"), list):
        if doc.get("pid") is not None:
            kw["pid"] = int(doc["pid"])
        if doc.get("host"):
            name = f"{doc['host']}:{doc.get('pid', '?')}"
            if doc.get("session_id"):
                name = f"{name} [{str(doc['session_id'])[:8]}]"
            kw["process_name"] = name
            kw["process_sort_index"] = 0
    trace = to_chrome_trace(events, **kw)
    out_path = args.output or args.input + ".trace.json"
    with open(out_path, "w") as f:
        json.dump(trace, f, indent=1, sort_keys=True)
        f.write("\n")
    spans = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    counters = {
        e["name"] for e in trace["traceEvents"] if e.get("ph") == "C"
    }
    print(
        f"wrote {out_path}: {len(trace['traceEvents'])} trace events "
        f"({spans} spans, {len(counters)} counter tracks) — open at "
        "https://ui.perfetto.dev"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
