"""Plan-time static analyzer — the ``GpuOverrides`` tagging pass analog.

The reference plugin decides *at plan time* which operators can run on the
accelerator and why (GpuOverrides.scala: every Expr/Exec gets a tag with a
human-readable willNotWorkOnGpu reason before any kernel launches). This
module is that pass for the TPU dispatch plane: it walks a plan's JSON op
list against an input schema signature — before any upload, compile, or
scheduler admission — and produces a tagged report:

* per-op inferred output schema/dtypes (a rule table covering every op key
  the ``runtime_bridge`` dispatch plane accepts; SRT008 enforces that the
  two registries can never drift),
* a support tier per op — ``fusable`` (can ride inside a traced fused
  segment, plan.op_fusable), ``per-op`` (bucketed per-op runner coverage,
  bucketed.is_bucketable), ``exact-only`` (eager exact dispatch only), or
  ``unsupported`` (statically known to raise) — each with a reason,
* predicted fusion segmentation that must agree exactly with
  ``plan.segment_plan`` (cross-checked by test so the two cannot drift),
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

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from . import dtype as dt

__all__ = [
    "ColType",
    "PlanCheckError",
    "schema_from_wire",
    "schema_of_table",
    "predict_segments",
    "analyze",
    "check_plan",
    "render_report",
]


# ---------------------------------------------------------------------------
# schema signatures
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ColType:
    """Static column signature: type id + decimal scale + LIST child id.

    The wire-protocol analog of a cudf ``data_type``: for LIST columns the
    wire scale slot carries the child's type id (runtime_bridge
    ``_host_column_from_wire``), which this class splits back out so rules
    can reason about element types.
    """

    id: dt.TypeId
    scale: int = 0
    child: Optional[dt.TypeId] = None

    @property
    def is_fixed_width(self) -> bool:
        return self.id in dt._WIDTHS

    @property
    def is_string(self) -> bool:
        return self.id == dt.TypeId.STRING

    @property
    def is_list(self) -> bool:
        return self.id == dt.TypeId.LIST

    @property
    def is_decimal(self) -> bool:
        return self.id in dt._DECIMAL_IDS

    @property
    def is_integer(self) -> bool:
        return self.id in dt._SIGNED_INT_IDS or self.id in dt._UNSIGNED_INT_IDS

    @property
    def is_floating(self) -> bool:
        return self.id in dt._FLOAT_IDS

    @property
    def is_boolean(self) -> bool:
        return self.id == dt.TypeId.BOOL8

    def pretty(self) -> str:
        if self.is_list:
            child = self.child.name if self.child is not None else "?"
            return f"LIST<{child}>"
        if self.is_decimal and self.scale:
            return f"{self.id.name}(scale={self.scale})"
        return self.id.name

    def to_json(self) -> dict:
        return {
            "type_id": int(self.id),
            "scale": int(self.scale),
            "child": int(self.child) if self.child is not None else None,
            "pretty": self.pretty(),
        }


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


class _Reject(Exception):
    """Internal: a rule refused the op; .reason is the message."""

    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


# ---------------------------------------------------------------------------
# shared helpers for the rule table
# ---------------------------------------------------------------------------

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


def _col_index(op: dict, key: str, schema, *, what: str) -> Optional[int]:
    """Resolve an op's column reference. Integer indices are range-checked
    against the schema when known; string names would need a named table —
    wire tables are unnamed, so names only resolve when the caller passed
    them. Returns None when the reference cannot be checked statically."""
    if key not in op:
        raise _Reject(f"missing required field {key!r}")
    ref = op[key]
    if isinstance(ref, bool) or not isinstance(ref, int):
        raise _Reject(
            f"{what} must be an integer column index, got {ref!r}"
        )
    if schema is not None and not (0 <= ref < len(schema)):
        raise _Reject(
            f"{what} index {ref} out of range for "
            f"{len(schema)}-column input"
        )
    return ref


def _key_ref(ref, schema, names, *, what: str) -> Optional[int]:
    """Resolve a sort/groupby/join/distinct key that the runtime routes
    through ``_resolve_col`` (int index or string name)."""
    if isinstance(ref, bool):
        raise _Reject(f"{what} must be a column index or name, got {ref!r}")
    if isinstance(ref, int):
        if schema is not None and not (0 <= ref < len(schema)):
            raise _Reject(
                f"{what} index {ref} out of range for "
                f"{len(schema)}-column input"
            )
        return ref
    if isinstance(ref, str):
        if schema is None:
            return None
        if not names:
            # mirrors ops/join._resolve_col on a name-less table
            raise _Reject(f"column name {ref!r} on an unnamed table")
        if ref not in names:
            raise _Reject(f"unknown column name {ref!r}")
        return list(names).index(ref)
    raise _Reject(f"{what} must be a column index or name, got {ref!r}")


def _cast_ok(src: ColType, to: ColType) -> Optional[str]:
    """None when the cast is statically supported; else the reason the
    kernel would refuse it. Mirrors ops/strings.cast and ops/cast.cast."""
    to_d = f"DType({to.id.name}" + (f", scale={to.scale})" if to.is_decimal else ")")
    src_d = f"DType({src.id.name}" + (
        f", scale={src.scale})" if src.is_decimal else ")"
    )
    if src.is_string or to.id == dt.TypeId.STRING:
        # strings.cast path (checked first in the dispatch plane)
        if src.is_string:
            ok = (
                to.is_boolean
                or to.is_integer
                or to.is_floating
                or to.is_decimal
                or to.id == dt.TypeId.STRING
            )
            return None if ok else f"cast STRING -> {to_d} not supported"
        ok = (
            src.is_boolean
            or src.is_integer
            or src.is_decimal
            or src.is_floating
        )
        return None if ok else f"cast {src_d} -> STRING not supported"
    if src.id == to.id and src.scale == to.scale:
        return None
    if to.id == dt.TypeId.DECIMAL128:
        if src.is_decimal or src.is_integer:
            return None
        return f"cannot cast {src_d} to DECIMAL128"
    if src.id == dt.TypeId.DECIMAL128:
        if to.is_decimal or to.is_floating or to.is_integer or to.is_boolean:
            return None
        return f"cannot cast DECIMAL128 to {to_d}"
    if not src.is_fixed_width or not to.is_fixed_width:
        return f"cast {src_d} -> {to_d} not supported"
    return None


# agg output-dtype rules mirroring ops/groupby.py; raises _Reject for
# combos the kernel refuses.
def _agg_out(agg: str, col: ColType) -> ColType:
    i64 = ColType(dt.TypeId.INT64)
    f64 = ColType(dt.TypeId.FLOAT64)
    if agg == "count":
        return i64
    if col.is_string and agg != "count":
        # string byte-matrix aggregation is not meaningful; only count is
        # statically safe (the kernels would mangle bytes shape-wise)
        raise _Reject(f"aggregation {agg!r} not supported on STRING values")
    if col.is_list:
        raise _Reject(f"aggregation {agg!r} not supported on LIST values")
    if agg == "nunique":
        if col.id == dt.TypeId.DECIMAL128:
            raise _Reject("nunique not supported for DECIMAL128")
        return i64
    if agg in ("first", "last", "min", "max"):
        return col
    if agg in ("collect_list", "collect_set"):
        from .column import _LIST_CHILD_IDS

        if col.id not in _LIST_CHILD_IDS:
            raise _Reject(
                f"{agg} not supported for DType({col.id.name}) (LIST "
                "children are int8..64, uint8..64, float32, bool)"
            )
        return ColType(dt.TypeId.LIST, 0, col.id)
    if agg == "sum":
        if col.is_floating:
            return f64
        if col.id in (dt.TypeId.DECIMAL32, dt.TypeId.DECIMAL64):
            return ColType(dt.TypeId.DECIMAL64, col.scale)
        if col.id == dt.TypeId.DECIMAL128:
            return ColType(dt.TypeId.DECIMAL128, col.scale)
        return i64
    if agg in ("mean", "variance", "std"):
        return f64
    raise _Reject(f"unknown aggregation {agg!r}")


# ---------------------------------------------------------------------------
# per-op inference rules
#
# Each rule takes (op, state) where state carries the flowing schema and
# row bound plus the rest-table queue, validates what is statically
# checkable, and returns (out_schema | None, out_names | None,
# rows_bound | None). A rule raises _Reject when the op statically cannot
# run. The key set of _RULES is the SRT008 parity anchor: it must equal
# runtime_bridge.DISPATCH_OPS.
# ---------------------------------------------------------------------------


class _State:
    def __init__(self, schema, names, rows, rest):
        self.schema = schema  # Optional[List[ColType]]
        self.names = names  # Optional[Sequence[str]]
        self.rows = rows  # Optional[int]
        # rest entries: (schema | None, rows | None); consumed exactly
        # like plan._take_rest
        self.orig_rest: List[Tuple] = list(rest)
        self.queue: List[Tuple] = list(rest)

    def take_rest(self, op: dict) -> List[Tuple]:
        idxs = op.get("rest")
        if idxs is not None:
            try:
                picked = [self.orig_rest[int(i)] for i in idxs]
            except (IndexError, TypeError, ValueError):
                raise _Reject(
                    f"'rest' indices {idxs!r} out of range for "
                    f"{len(self.orig_rest)} extra tables"
                ) from None
            return picked
        name = op.get("op")
        if name in ("join", "cross_join"):
            return [self.queue.pop(0)] if self.queue else []
        if name == "concat":
            out = list(self.queue)
            self.queue.clear()
            return out
        return []


def _r_cast(op, st):
    ci = _col_index(op, "column", st.schema, what="cast column")
    if "type_id" not in op:
        raise _Reject("missing required field 'type_id'")
    try:
        target_id = dt.TypeId(int(op["type_id"]))
    except (ValueError, TypeError):
        raise _Reject(f"unknown type_id {op.get('type_id')!r}") from None
    scale = op.get("scale", 0)
    if not isinstance(scale, int) or isinstance(scale, bool):
        raise _Reject(f"cast scale must be an integer, got {scale!r}")
    if scale != 0 and target_id not in dt._DECIMAL_IDS:
        # mirrors DType.__post_init__
        raise _Reject(f"non-zero scale on non-decimal type {target_id!r}")
    target = ColType(target_id, scale)
    if st.schema is None:
        return None, None, st.rows
    src = st.schema[ci]
    why = _cast_ok(src, target)
    if why is not None:
        raise _Reject(why)
    out = list(st.schema)
    out[ci] = target
    return out, st.names, st.rows


def _r_project(op, st):
    """Type inference over the expression trees, by the functions the
    runtime types them with (ops/project.py): decimal scales propagate,
    a comparison is BOOL8, a literal has the type it names."""
    from .ops import project

    try:
        if st.schema is None:
            project.check_structure(op.get("exprs"))
            return None, None, st.rows
        types = project.infer_schema(op.get("exprs"), st.schema)
    except project.ExprError as e:
        raise _Reject(str(e)) from None
    out = [
        t if isinstance(t, ColType) else ColType(t.id, int(t.scale))
        for t in types
    ]
    return out, None, st.rows  # names dropped, rows unchanged


def _r_filter(op, st):
    mi = _col_index(op, "mask", st.schema, what="filter mask")
    if st.schema is None:
        return None, None, st.rows
    if not st.schema[mi].is_boolean:
        # mirrors ops/filter.filter_table's gate
        raise _Reject(
            f"filter mask must be BOOL8, got {st.schema[mi].pretty()}"
        )
    out = [c for i, c in enumerate(st.schema) if i != mi]
    if not out:
        raise _Reject("filter would leave a zero-column table")
    return out, None, st.rows  # names dropped, rows <= input


def _r_rlike(op, st):
    ci = _col_index(op, "column", st.schema, what="rlike column")
    pat = op.get("pattern")
    if not isinstance(pat, str):
        raise _Reject(f"rlike pattern must be a string, got {pat!r}")
    if st.schema is None:
        return None, None, st.rows
    if not st.schema[ci].is_string:
        # mirrors ops/strings._require_string
        raise _Reject(
            f"rlike expected a STRING column, got {st.schema[ci].pretty()}"
        )
    return list(st.schema), st.names, st.rows  # rows <= input


def _r_sort_by(op, st):
    keys = op.get("keys")
    if not isinstance(keys, list) or not keys:
        raise _Reject("sort_by needs a non-empty 'keys' list")
    for k in keys:
        if not isinstance(k, dict) or "column" not in k:
            raise _Reject(f"sort_by key must be {{'column': ...}}, got {k!r}")
        _key_ref(k["column"], st.schema, st.names, what="sort_by key")
    if st.schema is None:
        return None, None, st.rows
    return list(st.schema), st.names, st.rows


def _r_distinct(op, st):
    keys = op.get("keys")
    if keys is not None:
        if not isinstance(keys, list):
            raise _Reject(f"distinct 'keys' must be a list, got {keys!r}")
        for k in keys:
            _key_ref(k, st.schema, st.names, what="distinct key")
    if st.schema is None:
        return None, None, st.rows
    return list(st.schema), st.names, st.rows  # rows <= input


def _r_slice(op, st):
    start = op.get("start", 0)
    stop = op.get("stop")
    try:
        start_i = int(start)
        stop_i = None if stop is None else int(stop)
    except (TypeError, ValueError):
        raise _Reject(
            f"slice bounds must be integers, got start={start!r} "
            f"stop={stop!r}"
        ) from None
    if start_i < 0 or (stop_i is not None and stop_i < 0):
        # mirrors ops/copying.slice_rows
        raise _Reject(
            "slice: negative bounds not supported "
            f"(start={start_i}, stop={stop_i})"
        )
    rows = st.rows
    if rows is not None:
        hi = rows if stop_i is None else min(stop_i, rows)
        rows = max(0, hi - min(start_i, rows))
    elif stop_i is not None:
        rows = max(0, stop_i - start_i)
    if st.schema is None:
        return None, None, rows
    return list(st.schema), st.names, rows


def _r_groupby(op, st):
    by = op.get("by")
    if not isinstance(by, list) or not by:
        raise _Reject("groupby needs a non-empty 'by' list")
    aggs = op.get("aggs")
    if not isinstance(aggs, list):
        raise _Reject("groupby needs an 'aggs' list")
    key_idx = [
        _key_ref(b, st.schema, st.names, what="groupby 'by' column")
        for b in by
    ]
    agg_specs = []
    for a in aggs:
        if not isinstance(a, dict) or "column" not in a or "agg" not in a:
            raise _Reject(
                f"groupby agg must be {{'column': ..., 'agg': ...}}, "
                f"got {a!r}"
            )
        agg = a["agg"]
        if agg not in _KNOWN_AGGS:
            raise _Reject(f"unknown aggregation {a!r}")
        ci = _key_ref(
            a["column"], st.schema, st.names, what="groupby agg column"
        )
        agg_specs.append((ci, agg))
    if st.schema is None:
        return None, None, st.rows
    out = [st.schema[i] for i in key_idx]
    for ci, agg in agg_specs:
        out.append(_agg_out(agg, st.schema[ci]))
    return out, None, st.rows  # groups <= rows; output names dropped


_KNOWN_AGGS = frozenset(
    {
        "sum",
        "count",
        "min",
        "max",
        "mean",
        "variance",
        "std",
        "collect_list",
        "collect_set",
        "nunique",
        "first",
        "last",
    }
)

_JOIN_HOWS = frozenset({"inner", "left", "right", "full", "semi", "anti"})


def _r_join(op, st):
    how = op.get("how", "inner")
    if how not in _JOIN_HOWS:
        raise _Reject(f"unknown join how={how!r}")
    rest = st.take_rest(op)
    if not rest:
        raise _Reject("join needs two input tables")
    on = op.get("on")
    if on is None:
        raise _Reject("missing required field 'on'")
    on = on if isinstance(on, list) else [on]
    left_idx = [
        _key_ref(c, st.schema, st.names, what="join 'on' column") for c in on
    ]
    r_schema, r_rows = rest[0]
    right_idx = None
    if r_schema is not None:
        right_idx = []
        for c in on:
            if isinstance(c, int) and not isinstance(c, bool):
                if not (0 <= c < len(r_schema)):
                    raise _Reject(
                        f"join 'on' index {c} out of range for "
                        f"{len(r_schema)}-column right table"
                    )
                right_idx.append(c)
            else:
                right_idx = None
                break
    if (
        how in ("right", "full")
        and st.schema is not None
        and r_schema is not None
        and right_idx is not None
        and None not in left_idx
    ):
        for li, ri in zip(left_idx, right_idx):
            lt, rt = st.schema[li], r_schema[ri]
            if (lt.id, lt.scale) != (rt.id, rt.scale):
                # mirrors ops/join's outer-join gate
                raise _Reject(
                    "outer-join key dtypes differ: "
                    f"{lt.pretty()} vs {rt.pretty()}"
                )
    rows = None
    if st.rows is not None and r_rows is not None:
        if how in ("semi", "anti"):
            rows = st.rows
        else:
            rows = st.rows * r_rows  # upper bound without key stats
    if how in ("semi", "anti"):
        return (
            (list(st.schema) if st.schema is not None else None),
            st.names,
            rows,
        )
    if st.schema is None or r_schema is None or right_idx is None:
        return None, None, rows
    # USING semantics: left columns + right columns minus right join keys
    out = list(st.schema)
    out.extend(c for i, c in enumerate(r_schema) if i not in set(right_idx))
    return out, None, rows


def _r_cross_join(op, st):
    rest = st.take_rest(op)
    if not rest:
        raise _Reject("cross_join needs two input tables")
    r_schema, r_rows = rest[0]
    rows = None
    if st.rows is not None and r_rows is not None:
        rows = st.rows * r_rows
    if st.schema is None or r_schema is None:
        return None, None, rows
    return list(st.schema) + list(r_schema), None, rows


def _r_concat(op, st):
    rest = st.take_rest(op)
    rows = st.rows
    out = list(st.schema) if st.schema is not None else None
    for r_schema, r_rows in rest:
        rows = rows + r_rows if (rows is not None and r_rows is not None) else None
        if out is None or r_schema is None:
            out = None
            continue
        if len(r_schema) != len(out):
            raise _Reject(
                "concatenate: column counts differ "
                f"({len(out)} vs {len(r_schema)})"
            )
        for a, b in zip(out, r_schema):
            if (a.id, a.scale, a.child) != (b.id, b.scale, b.child):
                raise _Reject(
                    f"concatenate dtype mismatch: {a.pretty()} vs "
                    f"{b.pretty()}"
                )
    return out, (st.names if out is not None else None), rows


def _r_explode(op, st):
    ci = _col_index(op, "column", st.schema, what="explode column")
    if st.schema is None:
        return None, None, None  # output rows are data-dependent
    col = st.schema[ci]
    if not col.is_list:
        # mirrors ops/lists._require_list
        raise _Reject(
            f"explode expected a LIST column, got {col.pretty()}"
        )
    out = list(st.schema)
    child = col.child if col.child is not None else dt.TypeId.INT64
    out[ci] = ColType(child)
    return out, st.names, None  # rows unbounded statically


def _r_repeat(op, st):
    count = op.get("count")
    if isinstance(count, bool) or not isinstance(count, int):
        raise _Reject(f"repeat count must be an integer, got {count!r}")
    if count < 0:
        # mirrors ops/copying.repeat
        raise _Reject("repeat: count must be non-negative")
    rows = st.rows * count if st.rows is not None else None
    if st.schema is None:
        return None, None, rows
    return list(st.schema), st.names, rows


def _r_sample(op, st):
    n = op.get("n")
    if isinstance(n, bool) or not isinstance(n, int):
        raise _Reject(f"sample n must be an integer, got {n!r}")
    if n < 0:
        raise _Reject(f"sample n must be non-negative, got {n}")
    # n > rows without replacement is a *runtime* error: upstream filters
    # make the live row count data-dependent, so it stays dynamic.
    if st.schema is None:
        return None, None, n
    return list(st.schema), st.names, n


def _r_to_rows(op, st):
    if st.schema is None:
        return None, None, st.rows
    if not st.schema:
        raise _Reject("row format requires at least one column")
    for c in st.schema:
        if not c.is_fixed_width:
            # mirrors rows.compute_fixed_width_layout
            raise _Reject(
                "only fixed-width types supported in row format "
                f"(got {c.pretty()})"
            )
    return [ColType(dt.TypeId.LIST, 0, dt.TypeId.UINT8)], None, st.rows


def _r_from_rows(op, st):
    tids = op.get("type_ids")
    scales = op.get("scales")
    if not isinstance(tids, list) or not isinstance(scales, list):
        raise _Reject("from_rows needs 'type_ids' and 'scales' lists")
    if len(tids) != len(scales):
        raise _Reject(
            f"from_rows type_ids/scales length mismatch "
            f"({len(tids)} vs {len(scales)})"
        )
    if not tids:
        raise _Reject("row format requires at least one column")
    out: List[ColType] = []
    for t, s in zip(tids, scales):
        try:
            tid = dt.TypeId(int(t))
        except (ValueError, TypeError):
            raise _Reject(f"unknown type_id {t!r} in from_rows") from None
        if tid not in dt._WIDTHS:
            raise _Reject(
                "only fixed-width types supported in row format "
                f"(got {tid.name})"
            )
        if s != 0 and tid not in dt._DECIMAL_IDS:
            raise _Reject(f"non-zero scale on non-decimal type {tid!r}")
        out.append(ColType(tid, int(s)))
    if st.schema is not None:
        first = st.schema[0] if st.schema else None
        if first is not None and not (
            first.is_list or first.id == dt.TypeId.UINT8
        ):
            raise _Reject(
                "from_rows input must be a LIST<UINT8> row column or a "
                f"flat UINT8 buffer, got {first.pretty()}"
            )
        if first is not None and not first.is_list and "num_rows" not in op:
            raise _Reject(
                "from_rows on a flat UINT8 buffer needs 'num_rows'"
            )
    rows = st.rows
    if "num_rows" in op:
        nr = op["num_rows"]
        if isinstance(nr, bool) or not isinstance(nr, int) or nr < 0:
            raise _Reject(f"from_rows num_rows must be a non-negative "
                          f"integer, got {nr!r}")
        rows = nr
    return out, None, rows


def _r_partition(op, st):
    kind = op.get("kind", "hash")
    if kind not in ("hash", "range"):
        raise _Reject(f"unknown partition kind {kind!r}")
    num = op.get("num")
    if isinstance(num, bool) or not isinstance(num, int):
        raise _Reject(f"partition num must be an integer, got {num!r}")
    if num < 1:
        raise _Reject(f"partition num must be >= 1, got {num}")
    keys = op.get("keys", [])
    if not isinstance(keys, list):
        raise _Reject(f"partition 'keys' must be a list, got {keys!r}")
    if kind == "range" and not keys:
        raise _Reject("partition kind='range' needs a non-empty 'keys' list")
    for k in keys:
        _key_ref(k, st.schema, st.names, what="partition key")
    # pure row redistribution: schema and total rows pass through
    # unchanged — only the row ORDER (exact path) / placement (mesh
    # path) moves, which is why it can sit on a segment boundary.
    if st.schema is None:
        return None, None, st.rows
    return list(st.schema), st.names, st.rows


# The rule table — the plancheck side of the SRT008 registry-parity pair.
# Keys must equal runtime_bridge.DISPATCH_OPS (enforced statically by
# srt_check pass SRT008 and dynamically by tests/test_plancheck.py).
_RULES = {
    "join": _r_join,
    "concat": _r_concat,
    "groupby": _r_groupby,
    "sort_by": _r_sort_by,
    "filter": _r_filter,
    "distinct": _r_distinct,
    "cast": _r_cast,
    "explode": _r_explode,
    "rlike": _r_rlike,
    "cross_join": _r_cross_join,
    "slice": _r_slice,
    "repeat": _r_repeat,
    "sample": _r_sample,
    "partition": _r_partition,
    "to_rows": _r_to_rows,
    "from_rows": _r_from_rows,
    "project": _r_project,
}


# ---------------------------------------------------------------------------
# support tiers (the GpuOverrides tag)
# ---------------------------------------------------------------------------

# ops the per-op bucketed runners cover (bucketed._RUNNERS); parity is
# asserted dynamically by tests/test_plancheck.py
_BUCKETED_OPS = frozenset(
    {
        "cast", "project", "filter", "sort_by", "groupby", "distinct",
        "rlike", "join",
    }
)
_BUCKETED_JOIN_HOWS = frozenset({"inner", "left", "semi", "anti"})
_COLLECT_AGGS = frozenset({"collect_list", "collect_set"})


def _op_fusable(op: dict) -> bool:
    """Mirror of plan.op_fusable — kept local so the analyzer stays
    import-light; parity with the runtime is cross-checked by test."""
    if not isinstance(op, dict):
        return False
    name = op.get("op")
    if name in ("cast", "project", "filter", "rlike", "distinct", "sort_by"):
        return True
    if name == "slice":
        try:
            start = int(op.get("start", 0))
            stop = op.get("stop")
            return start >= 0 and (stop is None or int(stop) >= 0)
        except (TypeError, ValueError):
            return False
    if name == "groupby":
        return not any(
            a.get("agg") in _COLLECT_AGGS
            for a in op.get("aggs", ())
            if isinstance(a, dict)
        )
    return False


def _tier(op: dict) -> Tuple[str, str]:
    """(tier, reason) for a well-formed op — GpuOverrides-style tag."""
    name = op.get("op")
    if _op_fusable(op):
        if name == "groupby":
            return (
                "fusable",
                "rides a fused segment tail-only: the groupby closes "
                "its run (plan.segment_plan)",
            )
        if name == "slice":
            return (
                "fusable",
                "non-negative static bounds ride inside a fused segment",
            )
        return "fusable", "single-table row-local op: rides fused segments"
    if name in _BUCKETED_OPS:
        if name == "join":
            how = op.get("how", "inner")
            if how in _BUCKETED_JOIN_HOWS:
                return (
                    "per-op",
                    f"join how={how!r} has a bucketed per-op runner",
                )
            return (
                "exact-only",
                f"join how={how!r} needs the exact path (outer-side "
                "row expansion defeats bucket padding)",
            )
        if name == "groupby":
            return (
                "exact-only",
                "collect_list/collect_set needs a data-dependent list "
                "capacity pre-pass only the exact path owns",
            )
        if name == "slice":
            return (
                "exact-only",
                "non-static or negative slice bounds fall back to the "
                "exact path (where negative bounds raise)",
            )
    if name == "slice":
        return (
            "exact-only",
            "non-static slice bounds fall back to the exact path",
        )
    _EXACT_REASONS = {
        "concat": "multi-table op: exact path only",
        "join": "multi-table op: exact path only",
        "cross_join": "multi-table op with n*m row expansion: exact only",
        "explode": "data-dependent output rows: exact path only",
        "repeat": "row-multiplying op: exact path only",
        "sample": "data-dependent gather: exact path only",
        "partition": "exchange boundary: exact path reorders in place; "
                     "the mesh path (planmesh) runs a counts-sized "
                     "all-to-all here and fuses the chains either side",
        "to_rows": "row-format transpose: exact path only",
        "from_rows": "row-format transpose: exact path only",
    }
    if name in _RULES:
        return "exact-only", _EXACT_REASONS.get(
            name, "no fused or bucketed runner: exact path only"
        )
    return "unsupported", f"unknown table op {name!r}"


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


def predict_segments(ops: Sequence[dict]) -> List[Tuple[str, List[int]]]:
    """Predicted fusion segmentation as ``[(kind, [op indices])]`` —
    must agree exactly with ``plan.segment_plan`` (cross-checked by
    test so the two can never drift)."""
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
        if _op_fusable(op):
            cur.append(i)
            if op.get("op") == "groupby":
                flush()
        else:
            flush()
            segs.append(("exact", [i]))
    flush()
    return segs


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
        rule = _RULES.get(name)
        if rule is None:
            report["ok"] = False
            report["ops"].append(entry)
            op_rows.append(None)
            op_widths.append((None, None))
            st.schema, st.names, st.rows = None, None, None
            continue
        width_in = _row_width(st.schema)
        try:
            out_schema, out_names, out_rows = rule(op, st)
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
