"""Null-aware unary operators (cudf ``unary_op`` family + null predicates)."""

from __future__ import annotations

import jax.numpy as jnp

from .. import dtype as dt
from ..column import Column
from . import compute

_FLOAT_ONLY = {
    "sqrt",
    "cbrt",
    "exp",
    "log",
    "sin",
    "cos",
    "tan",
    "arcsin",
    "arccos",
    "arctan",
    "sinh",
    "cosh",
    "tanh",
    "rint",
}

_FNS = {
    "abs": jnp.abs,
    "neg": lambda v: -v,
    "sqrt": jnp.sqrt,
    "cbrt": jnp.cbrt,
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "log": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "log1p": jnp.log1p,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "rint": jnp.rint,
    "bitnot": lambda v: ~v,
    "not": jnp.logical_not,
}


def result_dtype(op: str, d: dt.DType) -> dt.DType:
    """The dtype ``unary_op`` (or a null predicate) returns over a
    fixed-width column of dtype ``d``, from the dtype alone; raises
    where the op would, or would read the values wrongly (a decimal's
    unscaled integers, DECIMAL128's limbs)."""
    if op in NULL_PREDICATES:
        if op == "is_nan" and not d.is_floating:
            raise TypeError("is_nan requires a float column")
        return dt.BOOL8
    if op == "not":
        if not d.is_boolean:
            raise TypeError("'not' requires BOOL8")
        return dt.BOOL8
    if op not in _FNS:
        raise ValueError(f"unknown unary op {op!r}")
    if d.id == dt.TypeId.DECIMAL128 or (
        d.is_decimal and op not in ("abs", "neg")
    ):
        raise TypeError(f"unary op {op!r} not supported on {d}")
    if not d.is_numeric:
        raise TypeError(f"unary op {op!r} requires a numeric column, got {d}")
    if op == "bitnot" and not d.is_integer:
        raise TypeError("'bitnot' requires an integer column")
    if op in _FLOAT_ONLY and not d.is_floating:
        return dt.FLOAT64
    return d


def unary_op(op: str, col: Column) -> Column:
    if op == "not":
        if not col.dtype.is_boolean:
            raise TypeError("'not' requires BOOL8")
        return Column(jnp.logical_not(col.data), dt.BOOL8, col.validity)
    try:
        fn = _FNS[op]
    except KeyError:
        raise ValueError(f"unknown unary op {op!r}") from None

    vals = compute.values(col)
    out_dtype = col.dtype
    if op in _FLOAT_ONLY:
        if not col.dtype.is_floating:
            vals = vals.astype(jnp.float64)
            out_dtype = dt.FLOAT64
    if op in ("floor", "ceil", "rint") and not col.dtype.is_floating:
        return Column(col.data, col.dtype, col.validity)  # integral: no-op
    if op in ("abs", "neg") and col.dtype.is_decimal:
        return compute.from_values(fn(vals), col.dtype, col.validity)
    return compute.from_values(fn(vals), out_dtype, col.validity)


def is_null(col: Column) -> Column:
    """Spark ``IS NULL`` — never itself null."""
    if col.validity is None:
        return Column(jnp.zeros(len(col), dtype=jnp.bool_), dt.BOOL8, None)
    return Column(jnp.logical_not(col.validity), dt.BOOL8, None)


def is_not_null(col: Column) -> Column:
    if col.validity is None:
        return Column(jnp.ones(len(col), dtype=jnp.bool_), dt.BOOL8, None)
    return Column(col.validity, dt.BOOL8, None)


def is_nan(col: Column) -> Column:
    if not col.dtype.is_floating:
        raise TypeError("is_nan requires a float column")
    return Column(jnp.isnan(compute.values(col)), dt.BOOL8, col.validity)


# the null predicates by the names a ``project`` expression uses
NULL_PREDICATES = {
    "is_null": is_null,
    "is_not_null": is_not_null,
    "is_nan": is_nan,
}
