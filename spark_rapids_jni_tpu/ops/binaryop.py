"""Null-aware binary operators (the cudf ``binaryop`` family).

Semantics follow Spark SQL's non-ANSI mode, which is what the RAPIDS
Accelerator implements on GPU:
* any null operand -> null result (plus ``null_safe_eq``, Spark's <=>),
* integer/decimal division or modulo by zero -> null,
* float division by zero -> IEEE inf/NaN,
* decimal add/sub are exact at the finer scale and a decimal product at
  s1 + s2; the result is then brought to the output type's scale, which
  the caller names as cudf's ``binary_operation`` has it named
  (``out_dtype``; ``project`` names Spark's) and which defaults to the
  finer input scale; decimal div rescales the dividend first (cudf's
  fixed-point behavior).

Everything is jit-traceable; FLOAT64 goes through the compute view
(ops/compute.py) so storage stays bit-exact.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtype as dt
from ..column import Column
from . import compute

_CMP_OPS = {"eq", "ne", "lt", "le", "gt", "ge", "null_safe_eq"}
_LOGICAL_OPS = {"and", "or"}
_ARITH_OPS = {
    "add",
    "sub",
    "mul",
    "div",
    "true_div",
    "floor_div",
    "mod",
    "pmod",
    "pow",
    "bitand",
    "bitor",
    "bitxor",
    "shiftleft",
    "shiftright",
    "shiftright_unsigned",
}


def _promote(da: dt.DType, db: dt.DType) -> dt.DType:
    if da.is_decimal or db.is_decimal:
        # Spark promotes an integer operand to decimal(scale 0), so
        # qty * price works without an explicit cast; floats still
        # require one (the result type would silently stop being exact)
        if not da.is_decimal:
            if not da.is_integer:
                raise TypeError(
                    "decimal/float binary ops require explicit cast"
                )
            da = dt.DType(
                dt.TypeId.DECIMAL64 if da.itemsize >= 8 else dt.TypeId.DECIMAL32
            )
        if not db.is_decimal:
            if not db.is_integer:
                raise TypeError(
                    "decimal/float binary ops require explicit cast"
                )
            db = dt.DType(
                dt.TypeId.DECIMAL64 if db.itemsize >= 8 else dt.TypeId.DECIMAL32
            )
        wid = max(da.itemsize, db.itemsize)
        scale = min(da.scale, db.scale)
        return dt.DType(
            dt.TypeId.DECIMAL64 if wid >= 8 else dt.TypeId.DECIMAL32, scale
        )
    return dt.common_numeric_dtype(da, db)


_DECIMAL_ARITH = ("add", "sub", "mul", "div", "true_div")


def result_dtype(
    op: str,
    da: dt.DType,
    db: dt.DType,
    out_dtype: Optional[dt.DType] = None,
    spark: bool = False,
) -> dt.DType:
    """The dtype ``binary_op(op, a, b, out_dtype)`` returns for fixed-width
    operands, from their dtypes alone — what ``plancheck`` infers a
    ``project`` expression's type with.

    ``out_dtype`` is the caller's output type, as cudf's
    ``binary_operation`` takes it: a comparison or a logical op yields
    BOOL8 and nothing else; a decimal result may be named at any scale
    (the exact result is rescaled to it, truncating toward zero when it
    is coarser), a non-decimal result as any non-decimal type. With none
    named the result is typed as it always was (a decimal at the finer
    input scale), or, under ``spark=True``, by Spark's rule for the
    scale: a decimal ``mul`` at s1 + s2. Raises TypeError / ValueError
    as ``binary_op`` would."""
    wide = dt.TypeId.DECIMAL128
    if wide in (da.id, db.id) or (out_dtype is not None and out_dtype.id == wide):
        if op in _CMP_OPS:
            natural = dt.BOOL8
        elif op in ("add", "sub"):
            for d in (da, db):
                if not (d.is_decimal or d.is_integer):
                    raise TypeError(
                        "decimal128 binary ops require decimal/integer "
                        f"operands, got {d}"
                    )
            natural = dt.DType(wide, min(da.scale, db.scale))
        else:
            raise TypeError(f"decimal128 op {op!r} not supported")
    elif op in _LOGICAL_OPS:
        if not (da.is_boolean and db.is_boolean):
            raise TypeError("logical ops require BOOL8 columns")
        natural = dt.BOOL8
    elif op in _CMP_OPS:
        natural = dt.BOOL8
    elif op in _ARITH_OPS:
        natural = _promote(da, db)
        if natural.is_decimal:
            if op not in _DECIMAL_ARITH:
                raise TypeError(f"decimal op {op!r} not supported")
            if spark and op == "mul":
                natural = dt.DType(natural.id, da.scale + db.scale)
    else:
        raise ValueError(f"unknown binary op {op!r}")
    if out_dtype is None or out_dtype == natural:
        return natural
    if (
        natural.is_boolean
        or natural.id == wide
        or out_dtype.id == wide
        or natural.is_decimal != out_dtype.is_decimal
        or not out_dtype.is_numeric
    ):
        raise TypeError(
            f"binary op {op!r} over {da} and {db} yields {natural}; "
            f"it cannot be asked for as {out_dtype}"
        )
    return out_dtype


def _rescale_decimal(vals: jax.Array, from_scale: int, to_scale: int) -> jax.Array:
    if from_scale == to_scale:
        return vals
    if to_scale < from_scale:
        return vals * (10 ** (from_scale - to_scale))
    # narrowing truncates toward zero (cudf fixed_point / int128.rescale
    # convention; // would floor negatives: -3.75 at scale -1 is -3.7)
    return jax.lax.div(vals, jnp.asarray(10 ** (to_scale - from_scale),
                                         vals.dtype))


def binary_op(
    op: str, a: Column, b: Column, out_dtype: Optional[dt.DType] = None
) -> Column:
    """Elementwise ``a <op> b`` with Spark null semantics.

    ``out_dtype`` names the result's type the way cudf's
    ``binary_operation`` takes it from its caller (:func:`result_dtype`
    has the rules); a caller that names none gets the type it always
    got."""
    if a.dtype.is_string or b.dtype.is_string:
        from . import strings

        if out_dtype is not None:
            raise TypeError("string binary ops take no output type")
        return strings.binary_op(op, a, b)

    if out_dtype is not None:
        out_dtype = result_dtype(op, a.dtype, b.dtype, out_dtype)

    if dt.TypeId.DECIMAL128 in (
        a.dtype.id, b.dtype.id, out_dtype.id if out_dtype else None
    ):
        return _binary_op_decimal128(op, a, b)

    valid = compute.merge_validity(a, b)

    if op in _LOGICAL_OPS:
        return _logical(op, a, b)

    av, bv = compute.values(a), compute.values(b)

    if op in _CMP_OPS:
        if a.dtype.is_decimal or b.dtype.is_decimal:
            scale = min(a.dtype.scale, b.dtype.scale)
            av = _rescale_decimal(av.astype(jnp.int64), a.dtype.scale, scale)
            bv = _rescale_decimal(bv.astype(jnp.int64), b.dtype.scale, scale)
        out = {
            "eq": lambda: av == bv,
            "ne": lambda: av != bv,
            "lt": lambda: av < bv,
            "le": lambda: av <= bv,
            "gt": lambda: av > bv,
            "ge": lambda: av >= bv,
            "null_safe_eq": lambda: av == bv,
        }[op]()
        if op == "null_safe_eq":
            # Spark's <=>: null <=> null is True, null <=> x is False.
            va, vb = compute.valid_mask(a), compute.valid_mask(b)
            out = jnp.where(
                va & vb, out, jnp.logical_and(~va, ~vb)
            )
            return Column(out, dt.BOOL8, None)
        return Column(out, dt.BOOL8, valid)

    if op not in _ARITH_OPS:
        raise ValueError(f"unknown binary op {op!r}")

    natural = _promote(a.dtype, b.dtype)
    out_dtype = natural if out_dtype is None else out_dtype

    if out_dtype.is_decimal:
        # add/sub are exact at the finer input scale, the product at
        # s1 + s2; each is then brought to the output scale (cudf
        # fixed_point: a coarser one truncates toward zero)
        if op in ("add", "sub"):
            av = _rescale_decimal(av.astype(jnp.int64), a.dtype.scale, natural.scale)
            bv = _rescale_decimal(bv.astype(jnp.int64), b.dtype.scale, natural.scale)
            res = _rescale_decimal(
                av + bv if op == "add" else av - bv,
                natural.scale, out_dtype.scale,
            )
        elif op == "mul":
            res = _rescale_decimal(
                av.astype(jnp.int64) * bv.astype(jnp.int64),
                a.dtype.scale + b.dtype.scale,
                out_dtype.scale,
            )
        elif op in ("div", "true_div"):
            # quotient AT THE OUTPUT SCALE: rescale the dividend by
            # 10^(scale_a - scale_b - scale_out) before the truncated
            # divide (review catch: dividing two same-scale unscaled
            # values yields a scale-0 quotient, which was mislabeled
            # as scale_out — 7.50/2.00 read as 0.03). Truncation is
            # toward zero (cudf fixed_point / Java), via lax.div.
            e = a.dtype.scale - b.dtype.scale - out_dtype.scale
            av_raw = compute.values(a).astype(jnp.int64)
            bv_raw = compute.values(b).astype(jnp.int64)
            num = av_raw * (10 ** e) if e >= 0 else av_raw
            den = bv_raw if e >= 0 else bv_raw * (10 ** (-e))
            zero = bv_raw == 0
            res = jax.lax.div(num, jnp.where(zero, 1, den))
            valid = (
                ~zero if valid is None else jnp.logical_and(valid, ~zero)
            )
        else:
            raise TypeError(f"decimal op {op!r} not supported")
        return compute.from_values(res, out_dtype, valid)

    # computed in the operands' common type, returned as the output type
    want = np.dtype(natural.device_dtype)
    av = av.astype(want)
    bv = bv.astype(want)
    is_float = natural.is_floating

    if op == "add":
        res = av + bv
    elif op == "sub":
        res = av - bv
    elif op == "mul":
        res = av * bv
    elif op in ("div", "true_div"):
        if is_float:
            res = av / bv  # IEEE inf/NaN on zero divide
        else:
            # Spark IntegralDivide / Java: truncation toward zero, the
            # same convention as mod (lax.rem) so a == b*div + mod
            # holds for mixed signs; jnp's // floors (-7 div 2 must be
            # -3, not -4) — caught by the binaryop fuzz
            zero = bv == 0
            res = jnp.where(
                zero, 0, jax.lax.div(av, jnp.where(zero, 1, bv))
            )
            valid = ~zero if valid is None else jnp.logical_and(valid, ~zero)
    elif op == "floor_div":
        if is_float:
            res = jnp.floor(av / bv)
        else:
            zero = bv == 0
            res = jnp.where(zero, 0, av // jnp.where(zero, 1, bv))
            valid = ~zero if valid is None else jnp.logical_and(valid, ~zero)
    elif op == "mod":
        # Spark % / cudf MOD: C/Java-style — result carries the
        # DIVIDEND's sign (jnp.mod is Python-style and would differ for
        # mixed signs: -7 % 3 is -1 in Spark, 2 in Python)
        if is_float:
            res = jnp.fmod(av, bv)
        else:
            zero = bv == 0
            res = jnp.where(
                zero, 0, jax.lax.rem(av, jnp.where(zero, 1, bv))
            )
            valid = ~zero if valid is None else jnp.logical_and(valid, ~zero)
    elif op == "pmod":
        # Spark Pmod: r = a % n (Java %); negative remainders are
        # corrected to (r + n) % n, non-negative ones returned as-is
        # (so pmod(7, -3) = 1, pmod(-7, 3) = 2, pmod(-7, -3) = -1)
        if is_float:
            m = jnp.fmod(av, bv)
            res = jnp.where(m < 0, jnp.fmod(m + bv, bv), m)
        else:
            zero = bv == 0
            safe = jnp.where(zero, 1, bv)
            m = jax.lax.rem(av, safe)
            res = jnp.where(
                zero, 0,
                jnp.where(m < 0, jax.lax.rem(m + safe, safe), m),
            )
            valid = ~zero if valid is None else jnp.logical_and(valid, ~zero)
    elif op == "pow":
        res = jnp.power(av, bv)
    elif op == "bitand":
        res = av & bv
    elif op == "bitor":
        res = av | bv
    elif op == "bitxor":
        res = av ^ bv
    elif op in ("shiftleft", "shiftright", "shiftright_unsigned"):
        # Java/Spark shift semantics: the amount is masked to
        # (bit width - 1), so x << 64 == x for int64 (XLA's behavior
        # for amounts >= width is implementation-defined)
        width = np.dtype(str(av.dtype)).itemsize * 8
        shift = (bv & (width - 1)).astype(av.dtype)
        if op == "shiftleft":
            res = av << shift
        elif op == "shiftright":
            res = av >> shift
        else:
            # logical shift: reinterpret at the SAME width as unsigned
            # so the vacated high bits fill with zeros for any int width
            kind = np.dtype(str(av.dtype))
            if kind.kind == "i":
                u = np.dtype(f"uint{width}")
                shifted = (
                    jax.lax.bitcast_convert_type(av, u) >> shift.astype(u)
                )
                res = jax.lax.bitcast_convert_type(shifted, kind)
            else:
                res = av >> shift
    else:  # pragma: no cover
        raise AssertionError(op)

    return compute.from_values(res, out_dtype, valid)


def _logical(op: str, a: Column, b: Column) -> Column:
    """Spark three-valued logic for AND/OR."""
    if not (a.dtype.is_boolean and b.dtype.is_boolean):
        raise TypeError("logical ops require BOOL8 columns")
    av, bv = a.data, b.data
    va, vb = compute.valid_mask(a), compute.valid_mask(b)
    ta = av & va  # definitely true
    tb = bv & vb
    fa = (~av) & va  # definitely false
    fb = (~bv) & vb
    if op == "and":
        out = ta & tb
        known = (fa | fb) | (va & vb)  # false wins over null
    else:
        out = ta | tb
        known = (ta | tb) | (va & vb)  # true wins over null
    return Column(out, dt.BOOL8, None if (a.validity is None and b.validity is None) else known)


# Convenience wrappers
def add(a, b):
    return binary_op("add", a, b)


def sub(a, b):
    return binary_op("sub", a, b)


def mul(a, b):
    return binary_op("mul", a, b)


def div(a, b):
    return binary_op("div", a, b)


def eq(a, b):
    return binary_op("eq", a, b)


def ne(a, b):
    return binary_op("ne", a, b)


def lt(a, b):
    return binary_op("lt", a, b)


def le(a, b):
    return binary_op("le", a, b)


def gt(a, b):
    return binary_op("gt", a, b)


def ge(a, b):
    return binary_op("ge", a, b)


def _limbs_at_scale(col: Column, to_scale: int):
    """A column's values as (lo, hi) u64 limbs rescaled to ``to_scale``.
    Rescaling to the smaller (more negative) scale multiplies, so the
    common-scale alignment below is exact."""
    from . import int128

    if col.dtype.id == dt.TypeId.DECIMAL128:
        lo, hi = col.data[:, 0], col.data[:, 1]
        return int128.rescale(lo, hi, col.dtype.scale, to_scale)
    if col.dtype.is_decimal or col.dtype.is_integer:
        lo, hi = int128.from_signed_int(col.data)
        return int128.rescale(lo, hi, col.dtype.scale, to_scale)
    raise TypeError(
        f"decimal128 binary ops require decimal/integer operands, "
        f"got {col.dtype}"
    )


def _binary_op_decimal128(op: str, a: Column, b: Column) -> Column:
    """DECIMAL128 arithmetic/comparisons over two-u64-limb vectors
    (ops/int128.py). add/sub/neg-style ops and every comparison; mul/div
    between two 128-bit operands is not yet supported (raise, never
    silently truncate)."""
    import jax.numpy as jnp

    from . import int128

    valid = compute.merge_validity(a, b)
    scale = min(
        a.dtype.scale if a.dtype.is_decimal else 0,
        b.dtype.scale if b.dtype.is_decimal else 0,
    )
    al, ah = _limbs_at_scale(a, scale)
    bl, bh = _limbs_at_scale(b, scale)

    if op in _CMP_OPS:
        is_eq = int128.eq(al, ah, bl, bh)
        is_lt = int128.lt_signed(al, ah, bl, bh)
        out = {
            "eq": lambda: is_eq,
            "ne": lambda: ~is_eq,
            "lt": lambda: is_lt,
            "le": lambda: is_lt | is_eq,
            "gt": lambda: ~(is_lt | is_eq),
            "ge": lambda: ~is_lt,
            "null_safe_eq": lambda: is_eq,
        }[op]()
        if op == "null_safe_eq":
            va, vb = compute.valid_mask(a), compute.valid_mask(b)
            out = jnp.where(va & vb, out, jnp.logical_and(~va, ~vb))
            return Column(out, dt.BOOL8, None)
        return Column(out, dt.BOOL8, valid)

    if op == "add":
        lo, hi = int128.add(al, ah, bl, bh)
    elif op == "sub":
        lo, hi = int128.sub(al, ah, bl, bh)
    else:
        raise TypeError(f"decimal128 op {op!r} not supported")
    data = jnp.stack([lo, hi], axis=1)
    return Column(data, dt.DType(dt.TypeId.DECIMAL128, scale), valid)
