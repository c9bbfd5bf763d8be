"""Plan-op inference rules: what each op does to a schema, statically.

One rule per plan op, ``(op, state) -> (out_schema | None, out_names |
None, rows_bound | None)``, raising :class:`_Reject` when the op is
statically certain to raise in the dispatch plane. The rules and the
schema types they speak (:class:`ColType`, the walk's :class:`_State`)
sit BELOW the op table (``planops.OPS`` names each rule as its op's
``infer``); ``plancheck`` walks a plan with them and nothing here
imports upward. Error strings mirror the dispatch plane's own messages
wherever a runtime equivalent exists.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from . import dtype as dt


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


class _Reject(Exception):
    """Internal: a rule refused the op; .reason is the message."""

    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


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
# run. ``planops.OPS`` names each rule as its op's ``infer``.
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


# aggregations whose value over a group is the same bits wherever the
# group's rows lie among a device's other rows. A served float sum is a
# cumsum difference over every row of the device (ops/groupby.py): its
# last bits follow the layout, and so do the means and variances built
# on one; collect_* have no traced body at all
_LAYOUT_FREE_AGGS = frozenset(
    {"sum", "count", "min", "max", "nunique", "first", "last"}
)


def groupby_behind_exchange(op, part, schema, names):
    """May this groupby run a device at a time BEHIND the exchange
    ``part`` on the mesh (``parallel/planmesh``), over a table of
    ``schema`` (known: the stage holds the table)? ``(keys, None)`` when
    it may: the exchange's keys as positions among the groupby's OUTPUT
    columns (which start with its ``by`` columns in order), by which the
    stage finds a group's partition again. ``(None, reason)`` when it
    may not. ``planops.OPS`` names this as the groupby's
    ``behind_exchange``.

    It may where the exchange is by hash on keys that are all among the
    grouping keys: equal grouping keys then hash alike, every group
    lies whole on one device, and the union of the devices' results is
    the aggregate of the whole table. And where every aggregate is free
    of the layout (``_LAYOUT_FREE_AGGS``; no float sum), so that the
    bytes are the same on four devices, on two and on one."""
    if part.get("kind", "hash") != "hash":
        return None, "a groupby rides a hash exchange only"
    keys = list(part.get("keys") or [])
    if not keys:
        return None, (
            "the exchange hashes every column, the groupby's keys only some"
        )
    try:
        by = [
            _key_ref(b, schema, names, what="groupby 'by' column")
            for b in op.get("by") or []
        ]
        on = [_key_ref(k, schema, names, what="partition key") for k in keys]
        aggs = [
            (a["agg"], _key_ref(a["column"], schema, names,
                                what="groupby agg column"))
            for a in op.get("aggs") or []
        ]
    except (_Reject, KeyError, TypeError) as e:
        return None, f"malformed groupby: {e}"
    if not set(on) <= set(by):
        return None, (
            f"partition keys {keys!r} are not all among the groupby's "
            f"'by' columns {op.get('by')!r}: a group could span devices"
        )
    for agg, ci in aggs:
        if agg not in _LAYOUT_FREE_AGGS or (
            agg == "sum" and schema[ci].is_floating
        ):
            return None, (
                f"aggregation {agg!r} of column {ci!r} is not exact in "
                "every row layout: its bytes would follow the mesh size"
            )
    return [by.index(k) for k in on], None


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
