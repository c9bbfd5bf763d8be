"""The plain reference of the ``slice`` plan op: rows [start, stop) of
the table, both clamped to its row count (``stop`` absent = to the end)."""

from ..wirefmt import Col, table_rows


def apply(op, tables, lowprec):
    (t,) = tables
    n = table_rows(t)
    start = min(int(op.get("start", 0)), n)
    stop = n if op.get("stop") is None else max(start, min(int(op["stop"]), n))
    return [Col(c.type, c.scale, c.values[start:stop],
                None if c.valid is None else c.valid[start:stop]) for c in t]
