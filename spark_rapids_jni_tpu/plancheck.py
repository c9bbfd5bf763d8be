"""Plan-time static analyzer — the ``GpuOverrides`` tagging pass analog.

The reference plugin decides *at plan time* which operators can run on the
accelerator and why (GpuOverrides.scala: every Expr/Exec gets a tag with a
human-readable willNotWorkOnGpu reason before any kernel launches). This
module is that pass for the TPU dispatch plane: it walks a plan's JSON op
list against an input schema signature — before any upload, compile, or
scheduler admission — and produces a tagged report:

* per-op inferred output schema/dtypes (each op's ``infer`` rule in the
  one op table, ``planops.OPS``),
* a support tier per op — ``fusable`` (can ride inside a traced fused
  segment, ``planops.op_fusable``), ``per-op`` (a one-op bucketed runner,
  ``planops.op_bucketable``), ``exact-only`` (eager exact dispatch only),
  or ``unsupported`` (statically known to raise) — each with a reason,
* the fusion segmentation (``predict_segments``; ``plan.segment_plan``
  is this function over the ops themselves),
* a static cost/footprint bound (rows-in bound x row widths -> per-segment
  HBM bytes) that serving admission and the spill preflight can consult.

The analyzer is deliberately *permissive*: it rejects only what is
statically certain to raise in the dispatch plane (unknown op, malformed
spec, out-of-range column, dtype combo the kernels refuse). Anything
data-dependent — a regex that never matches, a sample larger than the
filtered row count — passes and keeps its runtime error surface. When the
input schema is unknown (resident tables still materializing), the walk
degrades to structural validation and schema inference reports ``None``.

Error strings mirror the dispatch plane's own messages wherever a runtime
equivalent exists (e.g. ``unknown table op {name!r}``) so callers matching
on substrings see the same text whether a plan dies statically or at
dispatch.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import dtype as dt
from . import planops
from .planrules import ColType, _Reject, _State

__all__ = [
    "ColType",
    "PlanCheckError",
    "schema_from_wire",
    "schema_of_table",
    "schema_behind",
    "predict_segments",
    "analyze",
    "check_plan",
    "render_report",
]


# ---------------------------------------------------------------------------
# schema signatures
# ---------------------------------------------------------------------------


def schema_from_wire(
    type_ids: Sequence[int], scales: Sequence[int]
) -> List[ColType]:
    """Schema signature from the JNI-style parallel (type_ids, scales)
    arrays. LIST reuses the scale slot for the child type id, exactly as
    the wire decoder does."""
    out: List[ColType] = []
    for tid, scale in zip(type_ids, scales):
        tid = dt.TypeId(int(tid))
        if tid == dt.TypeId.LIST:
            out.append(ColType(tid, 0, dt.TypeId(int(scale))))
        else:
            out.append(ColType(tid, int(scale)))
    return out


def schema_of_table(table) -> List[ColType]:
    """Schema signature of a live Table (for the resident-plan entry)."""
    out: List[ColType] = []
    for col in table.columns:
        d = col.dtype
        if d.id == dt.TypeId.LIST:
            out.append(ColType(d.id, 0, col.list_child_dtype.id))
        else:
            out.append(ColType(d.id, int(d.scale)))
    return out


def schema_behind(ops, schema, names=None):
    """``(schema, names)`` of the table that flows out of ``ops`` over an
    input of ``schema``, by the ops' inference rules alone; None where a
    rule rejects an op or loses the schema (the op's own path then says
    why)."""
    st = _State(list(schema), names, None, ())
    for op in ops:
        spec = planops.OPS.get(op.get("op")) if isinstance(op, dict) else None
        if spec is None:
            return None
        try:
            st.schema, st.names, st.rows = spec.infer(op, st)
        except _Reject:
            return None
        if st.schema is None:
            return None
    return st.schema, st.names


class PlanCheckError(ValueError):
    """A plan that statically cannot run. Subclasses ValueError so
    pre-existing callers matching the dispatch plane's error class (and
    the serving ``bad_request`` mapping) keep working; carries the op
    index, op name, reason, and the full tagged report."""

    def __init__(self, index: int, op_name, reason: str, plan_report=None):
        self.index = index
        self.op_name = op_name
        self.reason = reason
        self.plan_report = plan_report
        super().__init__(f"plancheck: op[{index}] {op_name!r}: {reason}")


# nominal per-row byte widths for the variable-width layouts, used only by
# the footprint *estimate* (never by support decisions): strings are a
# padded byte matrix plus a length vector, lists a child run plus lengths.
_STRING_EST_BYTES = 20
_LIST_EST_ELEMS = 8


def _col_width(col: ColType) -> int:
    w = dt._WIDTHS.get(col.id)
    if w is not None:
        return w
    if col.is_string:
        return _STRING_EST_BYTES
    if col.is_list:
        cw = dt._WIDTHS.get(col.child, 8) if col.child is not None else 8
        return cw * _LIST_EST_ELEMS + 4
    return 8


def _row_width(schema: Optional[List[ColType]]) -> Optional[int]:
    if schema is None:
        return None
    return sum(_col_width(c) for c in schema)


# ---------------------------------------------------------------------------
# support tiers (the GpuOverrides tag): what the op's spec says, with a
# reason's text chosen by name
# ---------------------------------------------------------------------------

_FUSABLE_REASONS = {
    "groupby": "rides a fused segment tail-only: the groupby closes "
               "its run (plan.segment_plan)",
    "slice": "non-negative static bounds ride inside a fused segment",
}

_EXACT_REASONS = {
    "concat": "multi-table op: exact path only",
    "join": "join how={how!r} needs the exact path (outer-side row "
            "expansion defeats bucket padding)",
    "groupby": "collect_list/collect_set needs a data-dependent list "
               "capacity pre-pass only the exact path owns",
    "slice": "non-static slice bounds fall back to the exact path",
    "cross_join": "multi-table op with n*m row expansion: exact only",
    "explode": "data-dependent output rows: exact path only",
    "repeat": "row-multiplying op: exact path only",
    "sample": "data-dependent gather: exact path only",
    "partition": "exchange boundary: exact path reorders in place; "
                 "the mesh path (planmesh) runs a counts-sized "
                 "all-to-all here, fuses the chains either side and "
                 "runs a groupby on its keys a device at a time behind it",
    "to_rows": "row-format transpose: exact path only",
    "from_rows": "row-format transpose: exact path only",
}


def _tier(op: dict) -> Tuple[str, str]:
    """(tier, reason) for a well-formed op — GpuOverrides-style tag."""
    name = op.get("op")
    if name not in planops.OPS:
        return "unsupported", f"unknown table op {name!r}"
    if planops.op_fusable(op):
        return "fusable", _FUSABLE_REASONS.get(
            name, "single-table row-local op: rides fused segments"
        )
    how = op.get("how", "inner")
    if planops.op_bucketable(op):
        return "per-op", f"{name} how={how!r} has a bucketed per-op runner"
    return "exact-only", _EXACT_REASONS.get(
        name, "no fused or bucketed runner: exact path only"
    ).format(how=how)


# ---------------------------------------------------------------------------
# kernel tier (kernels/registry.py) — static eligibility tags
# ---------------------------------------------------------------------------

# the static halves of the registry's applicability predicates. Keys
# must equal kernels.registry.KERNEL_NAMES — the SRT012 parity pair
# (enforced statically by srt_check pass SRT012 and dynamically by
# tests/test_kernel_tier.py). The tag is ADDITIVE to the support tier:
# a kernel-tagged op keeps its fusable/per-op/exact-only tier and may
# still decline at runtime on facts plancheck cannot see — the tag
# means "structurally eligible", never "will launch".

def _k_row_pack(op: dict, st) -> Optional[str]:
    if st.schema is not None:
        for ct in st.schema:
            if not ct.is_fixed_width:
                return (
                    f"{ct.id.name} column has no fixed-width row slot"
                )
    return None


def _k_row_unpack(op: dict, st) -> Optional[str]:
    if st.schema is not None and st.schema:
        first = st.schema[0]
        if not first.is_list:
            return "legacy flat row buffer (host decode path)"
    for tid in op.get("type_ids") or ():
        try:
            if dt.TypeId(int(tid)) not in dt._WIDTHS:
                return "non-fixed-width target schema"
        except (TypeError, ValueError):
            return "non-fixed-width target schema"
    return None


# kernel name -> (covered op name, static eligibility rule). The keys
# are the SRT012 anchor; the op coverage must mirror the registry's
# KernelSpec.ops tuples.
_KERNEL_RULES = {
    "row_pack": ("to_rows", _k_row_pack),
    "row_unpack": ("from_rows", _k_row_unpack),
}

_KERNELS_BY_OP: Dict[str, List[str]] = {}
for _kname, (_opname, _) in _KERNEL_RULES.items():
    _KERNELS_BY_OP.setdefault(_opname, []).append(_kname)
for _v in _KERNELS_BY_OP.values():
    _v.sort()


def _kernel_tag(op: dict, st) -> Optional[str]:
    """The kernel-tier tag for one op against the INPUT schema state:
    the registered kernel name when the op is statically eligible, else
    None. Never raises — malformed specs answer None and the op rule
    reports the real rejection."""
    for kname in _KERNELS_BY_OP.get(op.get("op"), ()):
        _, krule = _KERNEL_RULES[kname]
        try:
            if krule(op, st) is None:
                return kname
        # srt: allow-broad-except(the tag is advisory; a rule surprise degrades to untagged and the op rule reports the real rejection)
        except Exception:
            return None
    return None


def predict_segments(
    ops: Sequence[dict],
    join_selects: Optional[Callable[[int, dict], bool]] = None,
) -> List[Tuple[str, List[int]]]:
    """The fusion segmentation as ``[(kind, [op indices])]``:
    ``"fused"`` (a run of >= 2 fusable ops compiled as one executable)
    or ``"exact"`` (a single op through the per-op dispatch —
    non-fusable ops, and 1-op runs, which the one-op runners already
    cache under their own keys). A groupby is tail-only: it closes the
    run it ends. ``plan.segment_plan`` runs what this returns.

    A join is a boundary unless ``join_selects(index, op)`` says its
    build side makes it a selection (``plan._run_segments``, which
    holds the build tables, reads that from the data) AND only ops
    through which occupancy flows as a mask lie between it and a
    groupby tail (``planops.keeps_rows``): then it rides that run and
    moves no row. A join that would have to hand on a prefix gains
    nothing from riding and is never asked. With nothing known of the
    build side (``join_selects`` None: every static caller) a join
    stays the boundary it is."""
    riding = _riding_joins(ops, join_selects) if join_selects else ()
    segs: List[Tuple[str, List[int]]] = []
    cur: List[int] = []

    def flush():
        nonlocal cur
        if not cur:
            return
        if len(cur) >= 2:
            segs.append(("fused", cur))
        else:
            segs.extend(("exact", [i]) for i in cur)
        cur = []

    for i, op in enumerate(ops):
        if planops.op_fusable(op) or i in riding:
            cur.append(i)
            if op.get("op") == "groupby":
                flush()
        else:
            flush()
            segs.append(("exact", [i]))
    flush()
    return segs


def _riding_joins(ops: Sequence[dict], join_selects) -> set:
    """Indices of the joins that ride a fused run: walking back from
    each fusable groupby over the ops that keep their rows, every join
    met is asked; the first that does not select ends the walk."""
    riding: set = set()
    reaches_groupby = False
    for i in range(len(ops) - 1, -1, -1):
        op = ops[i]
        name = op.get("op")
        if name == "groupby":
            reaches_groupby = planops.op_fusable(op)
        elif name == "join":
            reaches_groupby = reaches_groupby and bool(join_selects(i, op))
            if reaches_groupby:
                riding.add(i)
        elif not (planops.op_fusable(op) and planops.keeps_rows(op)):
            reaches_groupby = False
    return riding


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------


def analyze(
    ops,
    schema: Optional[Sequence[ColType]] = None,
    rows: Optional[int] = None,
    rest: Sequence[Tuple[Optional[Sequence[ColType]], Optional[int]]] = (),
    names: Optional[Sequence[str]] = None,
) -> dict:
    """Walk a plan statically and return the tagged report (never raises
    for plan content — malformed ops become ``unsupported`` entries with
    ``ok: False``). ``schema=None`` degrades to structural validation.

    ``rest`` carries the extra input tables as ``(schema, rows)`` pairs,
    consumed exactly like ``plan._take_rest``.
    """
    report: dict = {
        "ok": True,
        "rows_in": rows,
        "ops": [],
        "kernel_ops": [],
        "segments": [],
        "est_hbm_peak_bytes": None,
    }
    if not isinstance(ops, list):
        report["ok"] = False
        report["ops"].append(
            {
                "index": 0,
                "op": None,
                "tier": "unsupported",
                "reason": "plan must be a JSON list of op objects, got "
                + type(ops).__name__,
                "out_schema": None,
                "rows_bound": None,
            }
        )
        return report

    st = _State(list(schema) if schema is not None else None, names, rows, rest)
    op_rows: List[Optional[int]] = []
    op_widths: List[Tuple[Optional[int], Optional[int]]] = []
    for i, op in enumerate(ops):
        entry = {
            "index": i,
            "op": None,
            "tier": None,
            "reason": None,
            "kernel": None,
            "out_schema": None,
            "rows_bound": None,
        }
        if not isinstance(op, dict) or "op" not in op:
            entry["tier"] = "unsupported"
            entry["reason"] = f"plan entries must be op objects, got {op!r}"
            report["ok"] = False
            report["ops"].append(entry)
            op_rows.append(None)
            op_widths.append((None, None))
            # schema unknowable downstream of a malformed entry
            st.schema, st.names, st.rows = None, None, None
            continue
        name = op.get("op")
        entry["op"] = name
        tier, reason = _tier(op)
        entry["tier"], entry["reason"] = tier, reason
        # kernel tag against the INPUT state — before the rule advances
        # st past this op (the runtime predicate sees the same input)
        entry["kernel"] = _kernel_tag(op, st)
        spec = planops.OPS.get(name)
        if spec is None:
            report["ok"] = False
            report["ops"].append(entry)
            op_rows.append(None)
            op_widths.append((None, None))
            st.schema, st.names, st.rows = None, None, None
            continue
        width_in = _row_width(st.schema)
        try:
            out_schema, out_names, out_rows = spec.infer(op, st)
        except _Reject as e:
            entry["tier"] = "unsupported"
            entry["reason"] = e.reason
            entry["kernel"] = None
            report["ok"] = False
            report["ops"].append(entry)
            op_rows.append(None)
            op_widths.append((width_in, None))
            st.schema, st.names, st.rows = None, None, None
            continue
        entry["out_schema"] = (
            [c.to_json() for c in out_schema]
            if out_schema is not None
            else None
        )
        entry["rows_bound"] = out_rows
        report["ops"].append(entry)
        op_rows.append(out_rows)
        op_widths.append((width_in, _row_width(out_schema)))
        st.schema, st.names, st.rows = out_schema, out_names, out_rows

    report["kernel_ops"] = [
        e["index"] for e in report["ops"] if e.get("kernel")
    ]
    report["out_schema"] = report["ops"][-1]["out_schema"] if report["ops"] else (
        [c.to_json() for c in schema] if schema is not None else None
    )
    report["rows_out_bound"] = op_rows[-1] if op_rows else rows

    # segmentation + footprint: per-op working set ~ rows_in*width_in +
    # rows_out*width_out; segment bound = max over its ops; plan peak =
    # max over segments. None propagates (variable-width/unbounded ops).
    segs = predict_segments(ops)
    peak: Optional[int] = None
    rows_before: List[Optional[int]] = [rows] + op_rows[:-1] if ops else []
    for kind, idxs in segs:
        seg_bytes: Optional[int] = 0
        seg_rows: Optional[int] = None
        for i in idxs:
            win, wout = op_widths[i]
            rin, rout = rows_before[i], op_rows[i]
            seg_rows = rout
            if None in (win, rin):
                op_bytes = None
            else:
                op_bytes = rin * win
                if wout is not None and rout is not None:
                    op_bytes += rout * wout
            if op_bytes is None:
                seg_bytes = None
            elif seg_bytes is not None:
                seg_bytes = max(seg_bytes, op_bytes)
        report["segments"].append(
            {
                "kind": kind,
                "ops": list(idxs),
                "rows_bound": seg_rows,
                "est_hbm_bytes": seg_bytes,
            }
        )
        if seg_bytes is not None:
            peak = seg_bytes if peak is None else max(peak, seg_bytes)
    report["est_hbm_peak_bytes"] = peak
    return report


def check_plan(
    ops,
    schema: Optional[Sequence[ColType]] = None,
    rows: Optional[int] = None,
    rest: Sequence[Tuple[Optional[Sequence[ColType]], Optional[int]]] = (),
    names: Optional[Sequence[str]] = None,
) -> dict:
    """``analyze`` + fail-fast: raises :class:`PlanCheckError` naming the
    first statically-invalid op (index, name, reason, full report
    attached) — before any upload, compile, or scheduler admission.
    Returns the report when the plan tags clean."""
    report = analyze(ops, schema=schema, rows=rows, rest=rest, names=names)
    if not report["ok"]:
        for entry in report["ops"]:
            if entry["tier"] == "unsupported":
                raise PlanCheckError(
                    entry["index"], entry["op"], entry["reason"], report
                )
        raise PlanCheckError(0, None, "plan failed static analysis", report)
    return report


# ---------------------------------------------------------------------------
# rendering (tools/explain.py --static)
# ---------------------------------------------------------------------------

_TIER_GLYPH = {
    "fusable": "*",
    "per-op": "+",
    "exact-only": "=",
    "unsupported": "!",
}


def render_report(report: dict) -> str:
    """Human-readable tagged plan, GpuOverrides-style: one line per op
    with tier glyph, inferred output schema, and reason; then the
    predicted segmentation and the static footprint bound."""
    lines: List[str] = []
    ok = report.get("ok", False)
    lines.append(f"plancheck: {'clean' if ok else 'REJECTED'}")
    rows_in = report.get("rows_in")
    if rows_in is not None:
        lines.append(f"rows in: {rows_in}")
    for e in report.get("ops", []):
        glyph = _TIER_GLYPH.get(e.get("tier"), "?")
        schema = e.get("out_schema")
        if schema is None:
            sch = "?"
        else:
            sch = "[" + ", ".join(c["pretty"] for c in schema) + "]"
        rb = e.get("rows_bound")
        rows_s = f" rows<={rb}" if rb is not None else ""
        kern = e.get("kernel")
        kern_s = f" ~kernel:{kern}" if kern else ""
        lines.append(
            f"  {glyph} op[{e['index']}] {e.get('op')!s:<10} "
            f"{e.get('tier') or '?':<11} -> {sch}{rows_s}{kern_s}"
        )
        lines.append(f"      {e.get('reason')}")
    segs = report.get("segments", [])
    if segs:
        parts = []
        for s in segs:
            idxs = ",".join(str(i) for i in s["ops"])
            b = s.get("est_hbm_bytes")
            b_s = f" ~{b}B" if b is not None else ""
            parts.append(f"{s['kind']}[{idxs}]{b_s}")
        lines.append("segments: " + " | ".join(parts))
    peak = report.get("est_hbm_peak_bytes")
    lines.append(
        "est HBM peak: " + (f"{peak} bytes" if peak is not None else "unbounded/unknown")
    )
    return "\n".join(lines)
