"""Pallas kernel tier: the hand-written TPU kernels, interpreted on CPU.

The reference's only hand-written kernel pair is the row transpose
(row_conversion.cu:48-304); its test is a golden round-trip through the
real device stack (RowConversionTest.java:28-59). Same shape here, plus a
cross-backend check the reference can't do: the Pallas kernels must emit
byte-identical results to the XLA-fusion backend. On CPU these run under
``interpret=True`` (tests/conftest.py pins the cpu platform); the same
calls compile through Mosaic when the suite runs on a TPU
(SPARK_RAPIDS_TPU_TEST_PLATFORM=tpu).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import spark_rapids_jni_tpu as srt
from spark_rapids_jni_tpu import rows
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.kernels import hashing as khash
from spark_rapids_jni_tpu.ops import hashing as xhash


def _mixed_table(rng, n, with_nulls=True):
    t = Table.from_pydict(
        {
            "i64": rng.integers(-(2**62), 2**62, n).astype(np.int64),
            "f64": rng.standard_normal(n),
            "i32": rng.integers(-(2**31), 2**31, n).astype(np.int32),
            "i16": rng.integers(-(2**15), 2**15, n).astype(np.int16),
            "i8": rng.integers(-128, 128, n).astype(np.int8),
            "f32": rng.standard_normal(n).astype(np.float32),
            "b": rng.random(n) > 0.5,
        }
    )
    if with_nulls:
        for c in t.columns[::2]:
            c.validity = jnp.asarray(rng.random(n) > 0.25)
    return t


@pytest.mark.parametrize("n", [7, 513, 4096])
def test_pack_matches_xla(rng, n):
    t = _mixed_table(rng, n)
    ref = rows.to_rows(t, backend="xla")
    got = rows.to_rows(t, backend="pallas")
    assert len(ref) == len(got) == 1
    np.testing.assert_array_equal(
        np.asarray(ref[0].data), np.asarray(got[0].data)
    )


@pytest.mark.parametrize("n", [7, 513, 4096])
def test_roundtrip_pallas(rng, n):
    t = _mixed_table(rng, n)
    packed = rows.to_rows(t, backend="pallas")
    back = rows.from_rows(packed, backend="pallas", names=t.names)
    for a, b in zip(t.columns, back.columns):
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
        av = (
            np.ones(n, bool)
            if a.validity is None
            else np.asarray(a.validity)
        )
        bv = (
            np.ones(n, bool)
            if b.validity is None
            else np.asarray(b.validity)
        )
        np.testing.assert_array_equal(av, bv)


def test_cross_backend_roundtrip(rng):
    """pallas-packed bytes unpack on the XLA backend and vice versa."""
    t = _mixed_table(rng, 1000)
    a = rows.from_rows(rows.to_rows(t, backend="pallas"), backend="xla")
    b = rows.from_rows(rows.to_rows(t, backend="xla"), backend="pallas")
    for ca, cb in zip(a.columns, b.columns):
        np.testing.assert_array_equal(
            np.asarray(ca.data), np.asarray(cb.data)
        )


def test_single_column_narrow(rng):
    """1-column schema: validity byte matmul with a width-1 output."""
    t = Table.from_pydict({"x": rng.integers(0, 100, 100).astype(np.int64)})
    t.columns[0].validity = jnp.asarray(rng.random(100) > 0.5)
    packed = rows.to_rows(t, backend="pallas")
    back = rows.from_rows(packed, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(back.columns[0].data), np.asarray(t.columns[0].data)
    )
    np.testing.assert_array_equal(
        np.asarray(back.columns[0].validity),
        np.asarray(t.columns[0].validity),
    )


def test_wide_schema_validity_bytes(rng):
    """>8 columns: multiple validity bytes per row."""
    n = 257
    cols = {
        f"c{i}": rng.integers(0, 100, n).astype(np.int32) for i in range(13)
    }
    t = Table.from_pydict(cols)
    for i, c in enumerate(t.columns):
        if i % 3 == 0:
            c.validity = jnp.asarray(rng.random(n) > 0.3)
    ref = rows.to_rows(t, backend="xla")[0]
    got = rows.to_rows(t, backend="pallas")[0]
    np.testing.assert_array_equal(np.asarray(ref.data), np.asarray(got.data))
    back = rows.from_rows(got, backend="pallas")
    for a, b in zip(t.columns, back.columns):
        av = (
            np.ones(n, bool) if a.validity is None else np.asarray(a.validity)
        )
        bv = (
            np.ones(n, bool) if b.validity is None else np.asarray(b.validity)
        )
        np.testing.assert_array_equal(av, bv)


def test_fused_hash_matches_xla(rng):
    t = _mixed_table(rng, 3000)
    ref = np.asarray(xhash.murmur3_table(t).data)
    got = np.asarray(khash.murmur3_table_fused(t).data)
    np.testing.assert_array_equal(ref, got)


def test_fused_hash_subset_and_seed(rng):
    t = _mixed_table(rng, 500)
    ref = np.asarray(xhash.murmur3_table(t, ["i64", "i32"], seed=7).data)
    got = np.asarray(
        khash.murmur3_table_fused(t, ["i64", "i32"], seed=7).data
    )
    np.testing.assert_array_equal(ref, got)


def test_fused_hash_string_fallback(rng):
    """String keys take the XLA path transparently."""
    import pyarrow as pa

    from spark_rapids_jni_tpu import interop

    t = interop.table_from_arrow(
        pa.table({"s": ["a", "bb", None, "dddd"], "v": [1, 2, 3, 4]})
    )
    ref = np.asarray(xhash.murmur3_table(t).data)
    got = np.asarray(khash.murmur3_table_fused(t).data)
    np.testing.assert_array_equal(ref, got)


def test_spark_golden_hash_values():
    """Known Spark Murmur3Hash(seed=42) outputs still hold on the fused
    kernel (same vectors as the XLA-path golden test)."""
    t = Table.from_pydict({"x": np.array([0, 1, -1], dtype=np.int64)})
    got = np.asarray(khash.murmur3_table_fused(t).data)
    # org.apache.spark.sql.catalyst.expressions.Murmur3HashFunction(long),
    # seed 42 — literals pinned from the independent python oracle
    # (test_ops.spark_hash_long), NOT recomputed through the library.
    expect = np.array([-1670924195, -1712319331, -939490007], np.int32)
    np.testing.assert_array_equal(got, expect)
