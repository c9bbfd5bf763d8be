"""Expression trees over a table's columns: the ``project`` plan op.

``{"op": "project", "exprs": [e0, e1, ...]}`` yields one column per
expression, in order (Spark's ``ProjectExec``). An expression is a JSON
tree of

* ``{"col": i}`` — column ``i`` of the input, of any type, unchanged;
* ``{"lit": v, "type_id": t, "scale": s}`` — a typed literal broadcast
  over the rows; ``v`` is the stored value (a decimal's unscaled
  integer, a date's day count), ``null`` makes every row null;
* ``{"binary": name, "left": e, "right": e}`` over
  ``ops.binaryop.binary_op``'s names, optionally with ``type_id`` /
  ``scale`` naming the output type as cudf's ``binary_operation`` takes
  it; without one a decimal ``mul`` yields scale s1 + s2 and ``add`` /
  ``sub`` the finer scale (Spark's ``DecimalPrecision`` for the scale;
  cudf's fixed-point types carry no precision, so none is tracked and a
  DECIMAL64 product stays DECIMAL64: whether it fits is the plan
  writer's statement);
* ``{"unary": name, "arg": e}`` over ``ops.unaryop`` (``not``,
  ``is_null``, ``is_not_null``, ``is_nan``, ``abs``, ``neg``, ...);
* ``{"cast": e, "type_id": t, "scale": s}`` over ``ops.cast.cast``.

Null semantics are ``binaryop``'s (Spark non-ANSI: null in, null out;
three-valued ``and`` / ``or``; integer and decimal division by zero is
null). Strings, lists and structs pass through a column reference only.

``infer`` types a tree from the input's dtypes alone, and
``project_table`` types each node by the same functions from the columns
its operands came back as, so the schema ``plancheck`` predicts is the
schema the runtime returns, and both refuse the same trees with the same
:class:`ExprError`. Everything is elementwise and jit-traceable: inside
a fused segment the expressions compile into the segment's own fusions.
"""

from __future__ import annotations

import numbers
from typing import List, Sequence

import numpy as np

from .. import dtype as dt

_NODE_KINDS = ("col", "lit", "binary", "unary", "cast")
# a tree deeper than this is a malformed plan, not a query
_MAX_DEPTH = 64


class ExprError(ValueError):
    """An expression that statically cannot be evaluated."""


def _kind(expr, depth: int) -> str:
    if not isinstance(expr, dict):
        raise ExprError(f"expression must be an object, got {expr!r}")
    if depth > _MAX_DEPTH:
        raise ExprError(f"expression nests deeper than {_MAX_DEPTH}")
    kinds = [k for k in _NODE_KINDS if k in expr]
    if len(kinds) != 1:
        raise ExprError(
            "expression needs exactly one of "
            f"{list(_NODE_KINDS)}, got {sorted(expr)!r}"
        )
    return kinds[0]


def _named_type(expr: dict, what: str, required: bool):
    """The ``type_id`` / ``scale`` a node carries -> DType, or None."""
    if "type_id" not in expr:
        if required:
            raise ExprError(f"{what} needs a 'type_id'")
        if expr.get("scale", 0) != 0:
            raise ExprError(f"{what} names a scale without a 'type_id'")
        return None
    tid, scale = expr["type_id"], expr.get("scale", 0)
    if isinstance(scale, bool) or not isinstance(scale, int):
        raise ExprError(f"{what} scale must be an integer, got {scale!r}")
    try:
        return dt.DType(dt.TypeId(int(tid)), scale)
    except (ValueError, TypeError) as e:
        raise ExprError(f"{what}: {e}") from None


def _fixed_width(d: dt.DType, what: str) -> None:
    if not d.is_fixed_width:
        raise ExprError(
            f"{what} must be fixed-width, got {d!r}: strings, lists and "
            "structs pass through a column reference only"
        )


def _literal_value(expr: dict, d: dt.DType):
    """The literal's stored value, checked against its type."""
    v = expr["lit"]
    _fixed_width(d, "literal")
    if d.id == dt.TypeId.DECIMAL128:
        raise ExprError("a DECIMAL128 literal is not supported")
    if v is None:
        return None
    if d.is_boolean:
        if not isinstance(v, bool):
            raise ExprError(f"BOOL8 literal must be true or false, got {v!r}")
        return v
    if d.is_floating:
        if isinstance(v, bool) or not isinstance(v, numbers.Real):
            raise ExprError(f"{d!r} literal must be a number, got {v!r}")
        return float(v)
    if isinstance(v, bool) or not isinstance(v, int):
        raise ExprError(f"{d!r} literal must be an integer, got {v!r}")
    info = np.iinfo(np.dtype(d.storage_dtype))
    if not info.min <= v <= info.max:
        raise ExprError(f"literal {v} does not fit {d!r}")
    return v


def _cast_type(expr: dict, src: dt.DType) -> dt.DType:
    to = _named_type(expr, "cast", required=True)
    _fixed_width(src, "cast operand")
    _fixed_width(to, "cast target")
    if to.id == dt.TypeId.DECIMAL128 and src != to and not (
        src.is_decimal or src.is_integer
    ):
        raise ExprError(f"cannot cast {src!r} to DECIMAL128")
    if src.id == dt.TypeId.DECIMAL128 and src != to and not (
        to.is_decimal or to.is_floating or to.is_integer or to.is_boolean
    ):
        raise ExprError(f"cannot cast DECIMAL128 to {to!r}")
    return to


def _unary_type(expr: dict, src: dt.DType) -> dt.DType:
    from . import unaryop

    _fixed_width(src, "unary operand")
    try:
        return unaryop.result_dtype(str(expr["unary"]), src)
    except (TypeError, ValueError) as e:
        raise ExprError(str(e)) from None


def _binary_type(expr: dict, a: dt.DType, b: dt.DType) -> dt.DType:
    from . import binaryop

    _fixed_width(a, "binary operand")
    _fixed_width(b, "binary operand")
    name = str(expr["binary"])
    if (
        name in binaryop._CMP_OPS
        and a.is_decimal != b.is_decimal
        and (a.is_floating or b.is_floating)
    ):
        # binary_op would truncate the float to the decimal's integers
        raise ExprError("decimal/float comparison requires an explicit cast")
    try:
        return binaryop.result_dtype(
            name, a, b, _named_type(expr, "binary node", required=False),
            spark=True,
        )
    except (TypeError, ValueError) as e:
        raise ExprError(str(e)) from None


def infer(expr, schema: Sequence, depth: int = 0) -> dt.DType:
    """The dtype ``expr`` evaluates to over columns typed ``schema``
    (anything with ``id`` and ``scale``). Raises :class:`ExprError`."""
    kind = _kind(expr, depth)
    if kind == "col":
        i = expr["col"]
        if isinstance(i, bool) or not isinstance(i, int):
            raise ExprError(f"'col' must be a column index, got {i!r}")
        if not 0 <= i < len(schema):
            raise ExprError(
                f"column index {i} out of range for "
                f"{len(schema)}-column input"
            )
        return dt.DType(schema[i].id, schema[i].scale)
    if kind == "lit":
        d = _named_type(expr, "literal", required=True)
        _literal_value(expr, d)
        return d
    if kind == "cast":
        return _cast_type(expr, infer(expr["cast"], schema, depth + 1))
    if kind == "unary":
        if "arg" not in expr:
            raise ExprError("unary node needs 'arg'")
        return _unary_type(expr, infer(expr["arg"], schema, depth + 1))
    if "left" not in expr or "right" not in expr:
        raise ExprError("binary node needs 'left' and 'right'")
    return _binary_type(
        expr,
        infer(expr["left"], schema, depth + 1),
        infer(expr["right"], schema, depth + 1),
    )


def infer_schema(exprs, schema: Sequence) -> list:
    """One output type per expression: the input's own entry for a bare
    column reference (a LIST keeps its child), a DType otherwise."""
    check_structure(exprs)
    out = []
    for e in exprs:
        d = infer(e, schema)
        out.append(schema[e["col"]] if "col" in e else d)
    return out


def check_structure(exprs) -> None:
    """What can be said of ``exprs`` with no schema at hand."""
    if not isinstance(exprs, list) or not exprs:
        raise ExprError("project needs a non-empty 'exprs' list")

    def walk(e, depth):
        kind = _kind(e, depth)
        if kind == "cast":
            walk(e["cast"], depth + 1)
        elif kind == "unary":
            walk(e.get("arg"), depth + 1)
        elif kind == "binary":
            walk(e.get("left"), depth + 1)
            walk(e.get("right"), depth + 1)

    for e in exprs:
        walk(e, 0)


def _literal(expr: dict, rows: int):
    import jax.numpy as jnp

    from ..column import Column

    d = _named_type(expr, "literal", required=True)
    v = _literal_value(expr, d)
    storage = np.dtype(d.storage_dtype)
    if v is None:
        return Column(
            jnp.zeros((rows,), storage), d, jnp.zeros((rows,), jnp.bool_)
        )
    if d.id == dt.TypeId.FLOAT64:
        v = np.float64(v).view(np.uint64)  # stored as its bit pattern
    return Column(jnp.full((rows,), v, storage), d, None)


def _evaluate(expr: dict, table):
    """``expr`` over ``table`` -> Column; ``infer_schema`` has accepted
    the tree, and a cast or a binary node gets its output type from the
    columns its operands really came back as."""
    from . import binaryop, unaryop
    from .cast import cast as cast_fn

    kind = _kind(expr, 0)
    if kind == "col":
        return table.columns[expr["col"]]
    if kind == "lit":
        return _literal(expr, table.row_count)
    if kind == "cast":
        src = _evaluate(expr["cast"], table)
        return cast_fn(src, _cast_type(expr, src.dtype))
    if kind == "unary":
        arg = _evaluate(expr["arg"], table)
        name = str(expr["unary"])
        predicate = unaryop.NULL_PREDICATES.get(name)
        return predicate(arg) if predicate else unaryop.unary_op(name, arg)
    a = _evaluate(expr["left"], table)
    b = _evaluate(expr["right"], table)
    return binaryop.binary_op(
        str(expr["binary"]), a, b,
        out_dtype=_binary_type(expr, a.dtype, b.dtype),
    )


def project_table(table, exprs: List[dict]):
    """One output column per expression over ``table``'s rows (names
    dropped, like every op that changes the column set)."""
    from ..column import Table

    # the whole list is typed before any expression runs
    infer_schema(exprs, [c.dtype for c in table.columns])
    return Table([_evaluate(e, table) for e in exprs])
