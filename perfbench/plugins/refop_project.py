"""The plain reference of the ``project`` plan op: expression trees over
a host table, in numpy int64 and Python integers.

One output column per expression. A tree is ``{"col": i}``, a typed
literal ``{"lit": v, "type_id", "scale"}`` (``v`` the stored value),
``{"binary": name, "left", "right"}`` (optionally naming its output
``type_id`` / ``scale``), ``{"unary": name, "arg"}`` or ``{"cast": e,
"type_id", "scale"}``. Written from Spark's non-ANSI rules, importing
nothing of the program: null in, null out; ``and`` / ``or`` three-valued;
integer and decimal division truncates toward zero and is null on a zero
divisor. A decimal is its unscaled integer at a stated scale: ``add`` /
``sub`` come out at the finer scale, ``mul`` at s1 + s2, or at the scale
the node names (truncating toward zero where that is coarser); the width
is the wider operand's. Every decimal result is checked to fit 63 bits,
and so is the sum of any output decimal column (rows x its largest
magnitude), which is what makes a later int64 sum exact.

``lowprec=True`` is the control of the check: every decimal operand and
every decimal result is carried through float32 — the step a chip with
no native 64-bit multiply tempts.
"""

from __future__ import annotations

import numpy as np

from ..wirefmt import NP_DTYPES, TYPE_NAMES, Col

_CMP = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
        "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal}
_ARITH = ("add", "sub", "mul", "div")
_INTS = ("INT8", "INT16", "INT32", "INT64", "UINT8")
_DECIMALS = ("DECIMAL32", "DECIMAL64")
_LIMIT = 2 ** 63
# numpy dtype -> the wire's plain (non-decimal, non-boolean) type of that dtype
_PLAIN_OF = {np.dtype(v): k for k, v in NP_DTYPES.items()
             if k not in _DECIMALS + ("BOOL8",)}


def _valid(c: Col) -> np.ndarray:
    return np.ones(c.rows, bool) if c.valid is None else c.valid


def _both(a: Col, b: Col):
    return None if a.valid is None and b.valid is None else _valid(a) & _valid(b)


def _named(e: dict):
    if "type_id" not in e:
        return None
    return TYPE_NAMES[int(e["type_id"])], int(e.get("scale", 0))


def _fits(values: np.ndarray, factor: int, what: str) -> None:
    """``values x factor`` stays inside 63 bits, reckoned in Python integers."""
    top = int(np.abs(values).max(initial=0)) * int(factor)
    if top >= _LIMIT:
        raise OverflowError(f"reference project: {what} reaches {top} >= 2^63")


def _rescale(v: np.ndarray, frm: int, to: int) -> np.ndarray:
    if to < frm:
        _fits(v, 10 ** (frm - to), "a rescaled decimal")
        return v * 10 ** (frm - to)
    if to > frm:  # toward zero, as cudf's fixed_point and Java do
        p = 10 ** (to - frm)
        return np.sign(v) * (np.abs(v) // p)
    return v


def _trunc_div(num: np.ndarray, den: np.ndarray) -> np.ndarray:
    safe = np.where(den == 0, 1, den)
    return np.sign(num) * np.sign(safe) * (np.abs(num) // np.abs(safe))


def _as_decimal(c: Col):
    """(type, scale) of an operand of decimal arithmetic: an integer is a
    decimal of scale 0, 64 bits wide if it was."""
    if c.type in _DECIMALS:
        return c.type, c.scale
    if c.type in _INTS:
        return ("DECIMAL64" if c.width >= 8 else "DECIMAL32"), 0
    raise TypeError("reference project: decimal/float arithmetic needs a cast")


def _through_f32(v: np.ndarray) -> np.ndarray:
    return v.astype(np.float32)


def _decimal_arith(name, a: Col, b: Col, named, lowprec: bool) -> Col:
    (ta, sa), (tb, sb) = _as_decimal(a), _as_decimal(b)
    wide = "DECIMAL64" if "DECIMAL64" in (ta, tb) else "DECIMAL32"
    av, bv = a.values.astype(np.int64), b.values.astype(np.int64)
    natural = sa + sb if name == "mul" else min(sa, sb)
    out_t, out_s = named or (wide, natural)
    if out_t not in _DECIMALS:
        raise TypeError("reference project: a decimal result needs a decimal type")
    valid = _both(a, b)
    if name == "div":
        # the quotient at the output scale: a x 10^e / b, e = sa - sb - out
        e = sa - sb - out_s
        num = _rescale(av, 0, -e) if e >= 0 else av
        den = bv if e >= 0 else _rescale(bv, 0, e)
        if lowprec:
            q = _through_f32(num) / np.where(den == 0, 1, _through_f32(den))
            res = np.trunc(q).astype(np.int64)
        else:
            res = _trunc_div(num, den)
        zero = bv == 0
        valid = ~zero if valid is None else valid & ~zero
    else:
        if name == "mul":
            x, y = av, bv
        else:
            x, y = _rescale(av, sa, natural), _rescale(bv, sb, natural)
        if lowprec:
            fn = {"add": np.add, "sub": np.subtract, "mul": np.multiply}[name]
            r32 = fn(_through_f32(x), _through_f32(y), dtype=np.float32)
            res = np.rint(r32.astype(np.float64)).astype(np.int64)
        elif name == "mul":
            _fits(x, int(np.abs(y).max(initial=0)), "a decimal product")
            res = x * y
        else:
            _fits(x, 2, "a decimal sum")
            _fits(y, 2, "a decimal sum")
            res = x + y if name == "add" else x - y
        res = _rescale(res, natural, out_s)
    return Col(out_t, out_s, res.astype(NP_DTYPES[out_t]), valid)


def _plain_arith(name, a: Col, b: Col, named) -> Col:
    av, bv = a.values, b.values
    common = np.promote_types(av.dtype, bv.dtype)
    av, bv = av.astype(common), bv.astype(common)
    valid = _both(a, b)
    if name == "div" and common.kind != "f":
        res = _trunc_div(av.astype(np.int64), bv.astype(np.int64)).astype(common)
        res = np.where(bv == 0, 0, res).astype(common)
        valid = bv != 0 if valid is None else valid & (bv != 0)
    else:
        with np.errstate(all="ignore"):
            res = {"add": np.add, "sub": np.subtract, "mul": np.multiply,
                   "div": np.divide}[name](av, bv)
    out_t = named[0] if named else _PLAIN_OF[common]
    if out_t in _DECIMALS or out_t == "BOOL8":
        raise TypeError("reference project: a plain result needs a plain type")
    return Col(out_t, 0, res.astype(NP_DTYPES[out_t]), valid)


def _logical(name, a: Col, b: Col) -> Col:
    if a.type != "BOOL8" or b.type != "BOOL8":
        raise TypeError("reference project: and / or need BOOL8 operands")
    at, bt = (a.values != 0) & _valid(a), (b.values != 0) & _valid(b)
    af, bf = (a.values == 0) & _valid(a), (b.values == 0) & _valid(b)
    if name == "and":  # false wins over null
        out, known = at & bt, af | bf | (_valid(a) & _valid(b))
    else:  # true wins over null
        out, known = at | bt, at | bt | (_valid(a) & _valid(b))
    nulls = a.valid is not None or b.valid is not None
    return Col("BOOL8", 0, out.astype(np.uint8), known if nulls else None)


def _compare(name, a: Col, b: Col) -> Col:
    av, bv = a.values, b.values
    if a.type in _DECIMALS or b.type in _DECIMALS:
        if "f" in (av.dtype.kind, bv.dtype.kind):
            raise TypeError("reference project: decimal/float comparison needs a cast")
        s = min(a.scale, b.scale)
        av = _rescale(av.astype(np.int64), a.scale, s)
        bv = _rescale(bv.astype(np.int64), b.scale, s)
    return Col("BOOL8", 0, _CMP[name](av, bv).astype(np.uint8), _both(a, b))


def _binary(e: dict, a: Col, b: Col, lowprec: bool) -> Col:
    name, named = e["binary"], _named(e)
    if name in ("and", "or") or name in _CMP:
        if named not in (None, ("BOOL8", 0)):
            raise TypeError("reference project: a predicate is BOOL8")
        return _logical(name, a, b) if name in ("and", "or") else _compare(name, a, b)
    if name not in _ARITH:
        raise ValueError(f"reference project: no binary op {name!r}")
    if a.type in _DECIMALS or b.type in _DECIMALS:
        return _decimal_arith(name, a, b, named, lowprec)
    return _plain_arith(name, a, b, named)


def _unary(name: str, a: Col) -> Col:
    if name == "is_null":
        return Col("BOOL8", 0, (~_valid(a)).astype(np.uint8), None)
    if name == "is_not_null":
        return Col("BOOL8", 0, _valid(a).astype(np.uint8), None)
    if name == "not":
        if a.type != "BOOL8":
            raise TypeError("reference project: not needs BOOL8")
        return Col("BOOL8", 0, (a.values == 0).astype(np.uint8), a.valid)
    if name in ("neg", "abs"):
        fn = np.negative if name == "neg" else np.abs
        return Col(a.type, a.scale, fn(a.values), a.valid)
    raise ValueError(f"reference project: no unary op {name!r}")


def _cast(a: Col, to_t: str, to_s: int) -> Col:
    """Spark's non-ANSI CAST between the fixed-width types of the wire:
    decimals rescale (toward zero), an integer is a decimal of scale 0,
    a float to a decimal rounds to the nearest unscaled integer."""
    v = a.values
    if a.type in _DECIMALS and to_t in _DECIMALS:
        res = _rescale(v.astype(np.int64), a.scale, to_s)
    elif a.type in _DECIMALS:
        if to_t in ("FLOAT32", "FLOAT64"):
            res = v.astype(np.float64) * 10.0 ** a.scale
        else:
            res = _rescale(v.astype(np.int64), a.scale, 0)
    elif to_t in _DECIMALS:
        if v.dtype.kind == "f":
            res = np.rint(v * 10.0 ** -to_s).astype(np.int64)
        else:
            res = _rescale(v.astype(np.int64), 0, to_s)
    elif to_t == "BOOL8":
        res = v != 0
    else:
        res = v
    return Col(to_t, to_s, np.asarray(res).astype(NP_DTYPES[to_t]), a.valid)


def _literal(e: dict, rows: int) -> Col:
    t, s = _named(e)
    if e["lit"] is None:
        return Col(t, s, np.zeros(rows, NP_DTYPES[t]), np.zeros(rows, bool))
    return Col(t, s, np.full(rows, e["lit"], NP_DTYPES[t]), None)


def evaluate(e: dict, table, lowprec: bool = False) -> Col:
    if "col" in e:
        return table[e["col"]]
    if "lit" in e:
        return _literal(e, table[0].rows)
    if "cast" in e:
        return _cast(evaluate(e["cast"], table, lowprec), *_named(e))
    if "unary" in e:
        return _unary(e["unary"], evaluate(e["arg"], table, lowprec))
    return _binary(e, evaluate(e["left"], table, lowprec),
                   evaluate(e["right"], table, lowprec), lowprec)


def apply(op, tables, lowprec):
    (t,) = tables
    out = [evaluate(e, t, lowprec) for e in op["exprs"]]
    for c in out:
        if c.type in _DECIMALS and not lowprec:
            _fits(c.values, c.rows, "the sum of a decimal output column")
    return out
