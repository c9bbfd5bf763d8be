"""Bytes a kernel's algorithm has to move, from shapes alone.

The least the row kernels can do: read every column value and one
validity bit a value, write every row byte (and the reverse). Padding of
narrow operands to the chip's lanes is the kernel's waste, not the
algorithm's need, so it is not counted — a share of the roofline
computed from these bytes cannot pass 100%.
"""

from __future__ import annotations

from . import plugins, rowformat
from .wirefmt import width_of


def row_kernel_bytes(widths, rows: int) -> int:
    """Bytes one ``row_pack`` (or one ``row_unpack``) moves for ``rows``."""
    _, _, vbytes, row = rowformat.layout(widths)
    return rows * (sum(widths) + vbytes + row)


def row_pack_unpack_bytes(config: dict, traffic: dict, rows: int) -> int:
    """One request of a row round trip: one pack and one unpack."""
    spec = config["tables"][traffic["tables"][traffic["rows_in"]]["table"]]
    widths = [width_of(c["type"]) for c in spec["columns"]]
    return 2 * row_kernel_bytes(widths, rows)


COUNTS = {"row_pack_unpack_bytes": row_pack_unpack_bytes}


def find(name: str):
    return COUNTS.get(name) or plugins.find("count", name, "count")
