"""The numpy packer against a layout of the 8-column schema worked out by
hand from row_conversion.cu's rules; nothing of the program is imported."""

import json
import os

import numpy as np

from perfbench import counts, rowformat
from perfbench.wirefmt import NP_DTYPES, Col

HERE = os.path.dirname(os.path.abspath(__file__))
TYPES = ["INT64", "FLOAT64", "INT32", "BOOL8", "FLOAT32", "INT8",
         "DECIMAL32", "DECIMAL64"]


def widths():
    return [np.dtype(NP_DTYPES[t]).itemsize for t in TYPES]


def test_layout_of_the_eight_column_schema():
    # by hand: 8 @0, 8 @8, 4 @16, 1 @20, 4 aligned to 24, 1 @28,
    # 4 aligned to 32, 8 aligned to 40 -> 48; one validity byte; 49 -> 56
    offsets, voff, vbytes, row = rowformat.layout(widths())
    assert offsets == [0, 8, 16, 20, 24, 28, 32, 40]
    assert (voff, vbytes, row) == (48, 1, 56)


def two_rows():
    vals = [
        np.array([0x0102030405060708, -1], np.int64),
        np.array([1.0, -2.5], np.float64),
        np.array([0x11223344, 7], np.int32),
        np.array([1, 0], np.uint8),
        np.array([0.5, 3.0], np.float32),
        np.array([-2, 9], np.int8),
        np.array([123456, -1], np.int32),
        np.array([10**12, 5], np.int64),
    ]
    # row 0: columns 2 and 5 null; row 1: all valid
    valid = [np.array([i not in (2, 5), True]) for i in range(8)]
    scales = [0, 0, 0, 0, 0, 0, -3, -8]
    return [Col(t, s, v, m) for t, s, v, m in zip(TYPES, scales, vals, valid)]


def test_pack_bytes_by_hand():
    rows = rowformat.pack(two_rows())
    assert rows.shape == (2, 56)
    r0 = rows[0]
    assert bytes(r0[0:8]) == bytes([8, 7, 6, 5, 4, 3, 2, 1])  # little endian
    assert bytes(r0[8:16]) == np.float64(1.0).tobytes()
    assert bytes(r0[16:20]) == bytes([0x44, 0x33, 0x22, 0x11])  # null: copied
    assert r0[20] == 1 and not r0[21:24].any()  # padding to the float32
    assert bytes(r0[24:28]) == np.float32(0.5).tobytes()
    assert r0[28] == 0xFE and not r0[29:32].any()
    assert bytes(r0[32:36]) == np.int32(123456).tobytes()
    assert not r0[36:40].any()
    assert bytes(r0[40:48]) == np.int64(10**12).tobytes()
    assert r0[48] == 0b11011011  # bits 2 and 5 clear, LSB first
    assert not r0[49:56].any()
    assert rows[1][48] == 0xFF


def test_unpack_returns_values_and_validity():
    table = two_rows()
    back = rowformat.unpack(rowformat.pack(table), TYPES,
                            [c.scale for c in table])
    for a, b in zip(back, table):
        assert (a.type, a.scale) == (b.type, b.scale)
        assert np.array_equal(a.valid, b.valid)
        assert a.values.tobytes() == b.values.tobytes()


def test_row_kernel_bytes_at_the_eight_column_schema():
    # least traffic of one kernel: 38 B of values + 1 B of validity bits
    # + the 56-byte row = 95 B a row
    assert counts.row_kernel_bytes(widths(), 1) == 95
    assert counts.row_kernel_bytes(widths(), 4_000_000) == 380_000_000
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "configs", "rowconv-8col-4m.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "traffic", "c2r-r2c.json")) as f:
        traffic = json.load(f)
    assert counts.find("row_pack_unpack_bytes")(config, traffic, 1000) == 190_000
