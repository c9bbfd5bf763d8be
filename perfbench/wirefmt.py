"""Host tables and the wire 5-tuple ``(type_ids, scales, datas, valids, n)``.

The type ids are cudf's public ``type_id`` enum; nothing is imported from
the program. A host table is a list of :class:`Col`. Packed rows travel
as one LIST<UINT8> column (type 24, child type in ``scale``): int32
offsets[n+1] followed by the row bytes.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

TYPE_IDS = {
    "INT8": 1, "INT16": 2, "INT32": 3, "INT64": 4, "UINT8": 5,
    "FLOAT32": 9, "FLOAT64": 10, "BOOL8": 11, "LIST": 24,
    "DECIMAL32": 25, "DECIMAL64": 26,
}
TYPE_NAMES = {v: k for k, v in TYPE_IDS.items()}
NP_DTYPES = {
    "INT8": np.int8, "INT16": np.int16, "INT32": np.int32,
    "INT64": np.int64, "UINT8": np.uint8, "FLOAT32": np.float32,
    "FLOAT64": np.float64, "BOOL8": np.uint8, "DECIMAL32": np.int32,
    "DECIMAL64": np.int64,
}


def width_of(type_name: str) -> int:
    """Bytes of one value of a fixed-width type."""
    return int(np.dtype(NP_DTYPES[type_name]).itemsize)


@dataclasses.dataclass
class Col:
    """One column: ``values`` is 1-D, or (n, row_size) uint8 for LIST."""

    type: str
    scale: int
    values: np.ndarray
    valid: Optional[np.ndarray] = None  # bool per row; None = no nulls

    @property
    def rows(self) -> int:
        return int(self.values.shape[0])

    @property
    def width(self) -> int:
        return width_of(self.type)


Table = List[Col]


def table_rows(table: Table) -> int:
    return table[0].rows if table else 0


def wire(table: Table) -> tuple:
    """Host table -> wire batch (copies every buffer to bytes)."""
    type_ids, scales, datas, valids = [], [], [], []
    for c in table:
        type_ids.append(TYPE_IDS[c.type])
        scales.append(int(c.scale))
        if c.type == "LIST":
            n, w = c.values.shape
            offsets = np.arange(n + 1, dtype=np.int32) * np.int32(w)
            datas.append(offsets.tobytes() + c.values.tobytes())
        else:
            datas.append(np.ascontiguousarray(c.values).tobytes())
        valids.append(
            None if c.valid is None else c.valid.astype(np.uint8).tobytes()
        )
    return (type_ids, scales, datas, valids, table_rows(table))


def unwire(batch) -> Table:
    """Wire batch -> host table (views over the received bytes)."""
    type_ids, scales, datas, valids, n = batch
    out = []
    for tid, scale, d, v in zip(type_ids, scales, datas, valids):
        name = TYPE_NAMES[int(tid)]
        if name == "LIST":
            body = np.frombuffer(d, np.uint8, offset=4 * (n + 1))
            vals = body.reshape(n, -1) if n else body.reshape(0, 0)
        else:
            vals = np.frombuffer(d, NP_DTYPES[name], count=n)
        valid = None if v is None else np.frombuffer(v, np.uint8, n) != 0
        out.append(Col(name, int(scale), vals, valid))
    return out
