"""The packed row format, written from the reference's description.

``row_conversion.cu`` (spark-rapids-jni), ``compute_fixed_width_layout``:
columns in order, each at its own width's alignment; validity bytes
(1 bit a column, LSB first) straight after the last column; the row
padded to a multiple of 8 bytes. Plain numpy, independent of the
program's ``rows.py`` — it is what ``to_rows`` / ``from_rows`` are held
to. Value bytes of a null are copied as they stand.
"""

from __future__ import annotations

import numpy as np

from .wirefmt import NP_DTYPES, Col, Table, width_of


def layout(widths):
    """-> (column offsets, validity offset, validity bytes, row size)."""
    offsets, cursor = [], 0
    for w in widths:
        cursor = (cursor + w - 1) // w * w
        offsets.append(cursor)
        cursor += w
    vbytes = (len(widths) + 7) // 8
    row = (cursor + vbytes + 7) // 8 * 8
    return offsets, cursor, vbytes, row


def pack(table: Table) -> np.ndarray:
    n = table[0].rows
    offsets, voff, vbytes, row = layout([c.width for c in table])
    out = np.zeros((n, row), np.uint8)
    for c, off in zip(table, offsets):
        raw = np.ascontiguousarray(c.values).view(np.uint8)
        out[:, off:off + c.width] = raw.reshape(n, c.width)
    for i, c in enumerate(table):
        bit = np.ones(n, np.uint8) if c.valid is None else c.valid.astype(np.uint8)
        out[:, voff + i // 8] |= bit << np.uint8(i % 8)
    return out


def unpack(rows: np.ndarray, types, scales) -> Table:
    n = rows.shape[0]
    widths = [width_of(t) for t in types]
    offsets, voff, _, row = layout(widths)
    if rows.shape[1] != row:
        raise ValueError(f"rows are {rows.shape[1]} bytes wide, schema needs {row}")
    out = []
    for i, (t, s, w, off) in enumerate(zip(types, scales, widths, offsets)):
        vals = np.ascontiguousarray(rows[:, off:off + w]).view(NP_DTYPES[t])
        valid = (rows[:, voff + i // 8] >> np.uint8(i % 8)) & 1
        out.append(Col(t, int(s), vals.reshape(n), valid != 0))
    return out
