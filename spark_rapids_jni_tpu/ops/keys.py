"""Order-preserving key normalization — the backbone of sort/groupby/join.

Every fixed-width column maps to one (or more, for strings) uint64 "order
key" arrays whose unsigned order equals the column's logical order. All
comparison-based ops (sort, merge join, groupby segmentation) then operate
on uniform u64 vectors, which XLA sorts/compares efficiently on TPU —
replacing cudf's per-type comparator template dispatch with a single
normalization pass.

Encodings:
* signed ints / timestamps / durations / decimals: value XOR sign-flip
  (two's complement order -> unsigned order).
* unsigned ints / bool: widen.
* FLOAT32/FLOAT64: the classic IEEE total-order trick on the *stored bit
  pattern* (negative values invert all bits, positives set the sign bit).
  NaN (canonical 0x7FF8...) maps above +inf, matching Spark/cudf's
  "NaN is largest" ordering — and doubles never need decoding, so this is
  exact on TPU regardless of the f64 emulation envelope.
* STRING: pad/8 big-endian u64 words of the padded byte matrix plus the
  length as a final tiebreaker word (memcmp order on '\0'-padded equal
  words == lexicographic byte order).

Nulls are handled by callers as an extra leading key (see sort.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtype as dt
from ..column import Column

_SIGN64 = np.uint64(1) << np.uint64(63)
_SIGN32 = np.uint32(1) << np.uint32(31)


def _float_bits_order(bits: jax.Array, width: int) -> jax.Array:
    """IEEE bits -> order-preserving unsigned key (same width)."""
    if width == 64:
        sign = (bits >> jnp.uint64(63)) != 0
        return jnp.where(sign, ~bits, bits | _SIGN64)
    sign = (bits >> jnp.uint32(31)) != 0
    return jnp.where(sign, ~bits, bits | _SIGN32)


def column_order_keys(col: Column) -> list[jax.Array]:
    """uint64 key array(s) whose unsigned order == the column's order."""
    d = col.dtype
    data = col.data
    if d.is_string:
        return _string_order_keys(col)
    if d.id == dt.TypeId.DECIMAL128:
        # (n, 2) u64 limbs: sign-flipped hi word then lo word — the
        # 128-bit instance of the signed sign-flip rule below
        from .int128 import order_key_words

        return order_key_words(data)
    if d.id == dt.TypeId.FLOAT64:
        return [_float_bits_order(data, 64)]
    if d.id == dt.TypeId.FLOAT32:
        bits = jax.lax.bitcast_convert_type(data, jnp.uint32)
        return [_float_bits_order(bits, 32).astype(jnp.uint64)]
    if d.is_boolean:
        return [data.astype(jnp.uint64)]
    np_dt = np.dtype(d.storage_dtype)
    if np_dt.kind == "u":
        return [data.astype(jnp.uint64)]
    # signed (ints, decimals, timestamps, durations): flip the sign bit
    # after widening so two's-complement order becomes unsigned order.
    widened = data.astype(jnp.int64).astype(jnp.uint64)
    return [widened ^ _SIGN64]


def column_order_fields(col: Column) -> list[tuple[jax.Array, int]]:
    """:func:`column_order_keys` with each word's width in bits: a
    column narrower than 64 bits gives a word that fills only its own
    width (BOOL8 one bit, INT8 eight), so that a sort can fold several
    narrow keys into one word (``ops/groupby._key_words``) instead of
    comparing a 64-bit word a key. Every other column gives its 64-bit
    words."""
    d = col.dtype
    if d.is_boolean:
        return [(col.data.astype(jnp.uint64), 1)]
    if d.id == dt.TypeId.FLOAT32:
        return [(column_order_keys(col)[0], 32)]
    if d.is_fixed_width and d.id != dt.TypeId.DECIMAL128 and d.itemsize < 8:
        bits = 8 * d.itemsize
        wide = col.data.astype(jnp.int64)
        if np.dtype(d.storage_dtype).kind != "u":
            wide = wide + (1 << (bits - 1))  # two's complement -> unsigned order
        return [(wide.astype(jnp.uint64), bits)]
    return [(w, 64) for w in column_order_keys(col)]


def _string_order_keys(col: Column) -> list[jax.Array]:
    mat = col.data  # (n, pad) uint8, zero-padded past length
    n, pad = mat.shape
    words = []
    for w in range((pad + 7) // 8):
        acc = jnp.zeros((n,), dtype=jnp.uint64)
        for b in range(8):
            i = w * 8 + b
            byte = (
                mat[:, i].astype(jnp.uint64)
                if i < pad
                else jnp.zeros((n,), dtype=jnp.uint64)
            )
            acc = (acc << jnp.uint64(8)) | byte  # big-endian => memcmp order
        words.append(acc)
    # length tiebreaker: "a" < "a\0" can't happen (pad bytes are zero and
    # shorter strings compare smaller on the zero word), but "a" vs "a" with
    # embedded NULs needs the explicit length word.
    words.append(col.lengths.astype(jnp.uint64))
    return words


def table_order_keys(cols: list[Column]) -> list[jax.Array]:
    out = []
    for c in cols:
        out.extend(column_order_keys(c))
    return out


def composite_compare_le(
    a_keys: list[jax.Array], a_idx, b_keys: list[jax.Array], b_idx
) -> jax.Array:
    """Lexicographic (a[a_idx] <= b[b_idx]) over parallel u64 key lists."""
    lt = jnp.zeros(jnp.shape(a_idx), dtype=jnp.bool_)
    eq = jnp.ones(jnp.shape(a_idx), dtype=jnp.bool_)
    for ak, bk in zip(a_keys, b_keys):
        av = ak[a_idx]
        bv = bk[b_idx]
        lt = lt | (eq & (av < bv))
        eq = eq & (av == bv)
    return lt | eq


def rows_equal(
    a_keys: list[jax.Array], a_idx, b_keys: list[jax.Array], b_idx
) -> jax.Array:
    eq = jnp.ones(jnp.shape(a_idx), dtype=jnp.bool_)
    for ak, bk in zip(a_keys, b_keys):
        eq = eq & (ak[a_idx] == bk[b_idx])
    return eq
