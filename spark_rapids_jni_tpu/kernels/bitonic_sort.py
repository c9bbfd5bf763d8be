"""Pallas TPU kernel: batched VMEM-resident bitonic sort of key chunks.

The chunked groupby (ops/groupby_chunked.py) turns one n-row sort into
C independent T-row sorts, betting that XLA's batched ``lax.sort``
keeps each small sort VMEM-resident. This kernel removes the bet: each
grid step sorts ONE chunk entirely inside VMEM with an unrolled bitonic
network — compare-exchange partners reached by ``pltpu.roll`` (partner
``i XOR j`` is ``i+j`` for the low element and ``i-j`` for the high
one, so two circular shifts plus a parity select cover every pair), the
TPU translation of the shared-memory tiled sorts GPU libraries use.

Mosaic constraints shape the interface (same discipline as
row_transpose.py's "no Mosaic i64 paths"): 64-bit keys and payloads are
split into u32 (hi, lo) halves OUTSIDE the kernel (free bitcasts under
XLA) and compared lexicographically inside. A per-row index rides as
the final tiebreaker, making the network deterministic and
order-stable for equal keys despite bitonic's inherent instability.

Used today as an A/B against ``jax.lax.sort`` on the chunk shapes
(bench config ``chunk_sort_ab``); flips on as the groupby phase-1
engine only if the chip says it wins (not measured). The roll-based
networks compile for a v5e (tests/test_chip_compile.py); the loop-form
variant at the bottom does not, and nothing dispatches it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import default_interpret


# Typed zero: under jax_enable_x64 a bare python 0 reaches Mosaic as i64
# (same note as row_transpose._Z).
_Z = np.int32(0)


def _check_pow2(t: int) -> None:
    if t & (t - 1) or t < 2:
        raise ValueError(f"chunk length must be a power of two, got {t}")


def _take_partner(p_lt, bit_j, bit_k):
    """Exchange decision of one compare-exchange stage: the low element
    of an ascending pair (or the high one of a descending pair) keeps
    the minimum. ``bit_j``/``bit_k`` are ``index & j`` / ``index & k``
    as i32 words; the result is ``p_lt`` where low == ascending, else
    ``~p_lt`` — written over i32 because Mosaic refuses i1 == i1 and an
    i1-valued select ("Unsupported target bitwidth for truncation")."""
    one = np.int32(1)
    low = jnp.where(bit_j == _Z, one, _Z)
    asc = jnp.where(bit_k == _Z, one, _Z)
    lt = jnp.where(p_lt, one, _Z)
    # low == asc -> lt ; low != asc -> 1 - lt
    return (lt ^ low ^ asc) != _Z


def _kernel(n_payload: int, t: int):
    """Kernel body closure: refs = [hi, lo] keys + n_payload u32
    payloads, each (1, T); same layout out."""
    from jax.experimental.pallas import tpu as pltpu

    def body(*refs):
        ins = refs[: 2 + n_payload]
        outs = refs[2 + n_payload :]
        hi = ins[0][...]
        lo = ins[1][...]
        ps = [r[...] for r in ins[2:]]
        idx = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1)
        i = idx

        ops = [hi, lo, idx] + ps
        k = 2
        while k <= t:
            j = k // 2
            while j >= 1:
                # pltpu.roll wants non-negative shifts: a left shift by
                # j is a right shift by t - j on the circle
                up, dn = np.int32(t - j), np.int32(j)
                rolled_up = [pltpu.roll(x, up, axis=1) for x in ops]
                rolled_dn = [pltpu.roll(x, dn, axis=1) for x in ops]
                bit_j = i & np.int32(j)
                is_low = bit_j == _Z  # lower index of the pair
                partner = [
                    jnp.where(is_low, u, d)
                    for u, d in zip(rolled_up, rolled_dn)
                ]
                p_hi, p_lo, p_idx = partner[0], partner[1], partner[2]
                hi_, lo_, idx_ = ops[0], ops[1], ops[2]
                # lexicographic (hi, lo, idx): partner strictly smaller?
                p_lt = (
                    (p_hi < hi_)
                    | ((p_hi == hi_) & (p_lo < lo_))
                    | ((p_hi == hi_) & (p_lo == lo_) & (p_idx < idx_))
                )
                # keep_min = (is_low == ascending block); masks are
                # combined as i32 words: Mosaic has no i1 compare/select
                take_partner = _take_partner(p_lt, bit_j, i & np.int32(k))
                ops = [
                    jnp.where(take_partner, pv, xv)
                    for pv, xv in zip(partner, ops)
                ]
                j //= 2
            k *= 2

        outs[0][...] = ops[0]
        outs[1][...] = ops[1]
        for r, v in zip(outs[2:], [ops[2]] + ops[3:]):
            r[...] = v

    return body


#: Chunks per grid step. Mosaic requires the sublane (second-to-last)
#: block dim be a multiple of 8; each of the 8 rows runs the same
#: network independently (rolls are along axis 1), so batching them in
#: one block costs nothing and satisfies the tiling rule.
_ROWS_PER_BLOCK = 8


@functools.lru_cache(maxsize=64)
def _sort_call(n_payload: int, t: int, interpret: bool):
    spec = pl.BlockSpec((_ROWS_PER_BLOCK, t), lambda c: (c, _Z))
    n_ops = 2 + n_payload

    def fn(*arrays):
        c = arrays[0].shape[0]
        out_shapes = [
            jax.ShapeDtypeStruct((c, t), jnp.uint32) for _ in range(2)
        ] + [jax.ShapeDtypeStruct((c, t), jnp.int32)] + [
            jax.ShapeDtypeStruct((c, t), jnp.uint32)
            for _ in range(n_payload)
        ]
        return pl.pallas_call(
            _kernel(n_payload, t),
            grid=(c // _ROWS_PER_BLOCK,),
            in_specs=[spec] * n_ops,
            out_specs=[spec] * (n_ops + 1),  # +1: the permutation index
            out_shape=out_shapes,
            interpret=interpret,
        )(*arrays)

    return jax.jit(fn)


def batched_sort_u64(
    key: jax.Array, *payloads: jax.Array, interpret: bool | None = None
):
    """Sort each row of ``key`` (C, T) u64 ascending, carrying payloads.

    Returns ``(sorted_key, perm int32, *sorted_payloads)`` where perm is
    the within-chunk source index (the iota that rode the network — the
    same contract as carrying an iota operand through ``lax.sort``).
    Equal keys keep their original relative order (index tiebreaker).
    Payloads may be u64/i64 (split into u32 halves around the kernel)
    or <=32-bit (widened)."""
    if interpret is None:
        interpret = default_interpret()
    c, t = key.shape
    _check_pow2(t)
    # Mosaic block tiling: pad the chunk count to the 8-row block and
    # strip after (padding chunks sort all-max garbage, discarded).
    pad_c = (-c) % _ROWS_PER_BLOCK
    if pad_c:
        key = jnp.concatenate(
            [key, jnp.full((pad_c, t), ~jnp.uint64(0))], axis=0
        )
        payloads = tuple(
            jnp.concatenate(
                [p, jnp.zeros((pad_c, t), p.dtype)], axis=0
            )
            for p in payloads
        )
    hi = (key >> jnp.uint64(32)).astype(jnp.uint32)
    lo = key.astype(jnp.uint32)

    split = []
    wide = []
    for p in payloads:
        if p.dtype.itemsize == 8:
            pb = jax.lax.bitcast_convert_type(p, jnp.uint64)
            split.append((pb >> jnp.uint64(32)).astype(jnp.uint32))
            split.append(pb.astype(jnp.uint32))
            wide.append(True)
        elif p.dtype.itemsize == 4:
            # bitcast, not astype: a value cast truncates float32
            # payloads (1.5 -> 1) where the 8-byte path bit-preserves
            split.append(jax.lax.bitcast_convert_type(p, jnp.uint32))
            wide.append(False)
        else:
            if jnp.issubdtype(p.dtype, jnp.floating):
                raise TypeError(
                    f"narrow float payload {p.dtype} would lose bits "
                    "through the u32 widening; cast it to float32 first"
                )
            # integer widen/narrow round-trips exactly (two's complement
            # wrap on the way back)
            split.append(p.astype(jnp.uint32))
            wide.append(False)

    out = _sort_call(len(split), t, bool(interpret))(hi, lo, *split)
    if pad_c:
        out = tuple(o[:c] for o in out)
    s_hi, s_lo, perm = out[0], out[1], out[2]
    s_key = (s_hi.astype(jnp.uint64) << jnp.uint64(32)) | s_lo.astype(
        jnp.uint64
    )
    outp = []
    k = 3
    for p, w in zip(payloads, wide):
        if w:
            v = (
                out[k].astype(jnp.uint64) << jnp.uint64(32)
            ) | out[k + 1].astype(jnp.uint64)
            outp.append(jax.lax.bitcast_convert_type(v, p.dtype))
            k += 2
        elif p.dtype.itemsize == 4:
            outp.append(jax.lax.bitcast_convert_type(out[k], p.dtype))
            k += 1
        else:
            outp.append(out[k].astype(p.dtype))
            k += 1
    return (s_key, perm, *outp)


# ---------------------------------------------------------------------------
# u32 single-word variant — the packed-key fast path's engine. When the
# sort key fits ONE u32 (key-range x chunk-rows <= 2^32, the packed
# groupby/ORDER BY word with its embedded per-chunk iota), the network
# compares one word with NO tiebreaker: the embedded iota makes keys
# unique, so stability is structural and the (hi, lo, idx) lexicographic
# compare — and two thirds of the VMEM traffic — vanish.
# ---------------------------------------------------------------------------


def _kernel_u32(n_payload: int, t: int):
    """refs = key + n_payload u32 payloads in, same out; (8, T) blocks.

    Requires every key in a row to be DISTINCT (packed iota contract):
    with distinct keys a bitonic network is deterministic, so no index
    tiebreaker rides."""
    from jax.experimental.pallas import tpu as pltpu

    def body(*refs):
        ins = refs[: 1 + n_payload]
        outs = refs[1 + n_payload:]
        ops = [r[...] for r in ins]
        idx = jax.lax.broadcasted_iota(jnp.int32, ops[0].shape, 1)
        k = 2
        while k <= t:
            j = k // 2
            while j >= 1:
                up, dn = np.int32(t - j), np.int32(j)
                rolled_up = [pltpu.roll(x, up, axis=1) for x in ops]
                rolled_dn = [pltpu.roll(x, dn, axis=1) for x in ops]
                bit_j = idx & np.int32(j)
                is_low = bit_j == _Z
                partner = [
                    jnp.where(is_low, u, d)
                    for u, d in zip(rolled_up, rolled_dn)
                ]
                p_lt = partner[0] < ops[0]
                take_partner = _take_partner(
                    p_lt, bit_j, idx & np.int32(k)
                )
                ops = [
                    jnp.where(take_partner, pv, xv)
                    for pv, xv in zip(partner, ops)
                ]
                j //= 2
            k *= 2
        for r, v in zip(outs, ops):
            r[...] = v

    return body


@functools.lru_cache(maxsize=64)
def _sort_call_u32(n_payload: int, t: int, interpret: bool):
    spec = pl.BlockSpec((_ROWS_PER_BLOCK, t), lambda c: (c, _Z))
    n_ops = 1 + n_payload

    def fn(*arrays):
        c = arrays[0].shape[0]
        return pl.pallas_call(
            _kernel_u32(n_payload, t),
            grid=(c // _ROWS_PER_BLOCK,),
            in_specs=[spec] * n_ops,
            out_specs=[spec] * n_ops,
            out_shape=[
                jax.ShapeDtypeStruct((c, t), jnp.uint32)
                for _ in range(n_ops)
            ],
            interpret=interpret,
        )(*arrays)

    return jax.jit(fn)


def batched_sort_u32(
    key: jax.Array, *payloads: jax.Array, interpret: bool | None = None
):
    """Sort each row of ``key`` (C, T) u32 ascending, carrying payloads.

    Keys within a row MUST be distinct (the packed-word-with-iota
    contract) — with ties the network's output order is undefined.
    Payloads must be 4-byte (bitcast around the kernel) or narrower
    integers (widened). Returns ``(sorted_key, *sorted_payloads)``; the
    caller recovers the permutation from the embedded iota bits."""
    if interpret is None:
        interpret = default_interpret()
    c, t = key.shape
    _check_pow2(t)
    if key.dtype != jnp.uint32:
        raise TypeError(f"key must be uint32, got {key.dtype}")
    for p in payloads:  # validate before any device work
        if p.dtype.itemsize > 4 or (
            p.dtype.itemsize < 4 and jnp.issubdtype(p.dtype, jnp.floating)
        ):
            raise TypeError(
                f"u32 network payload must be <=4-byte int or any "
                f"4-byte dtype, got {p.dtype}"
            )
    pad_c = (-c) % _ROWS_PER_BLOCK
    if pad_c:
        key = jnp.concatenate(
            [key, jnp.full((pad_c, t), ~jnp.uint32(0))], axis=0
        )
        payloads = tuple(
            jnp.concatenate(
                [p, jnp.zeros((pad_c, t), p.dtype)], axis=0
            )
            for p in payloads
        )
    split = [
        jax.lax.bitcast_convert_type(p, jnp.uint32)
        if p.dtype.itemsize == 4
        else p.astype(jnp.uint32)
        for p in payloads
    ]
    out = _sort_call_u32(len(split), t, bool(interpret))(key, *split)
    if pad_c:
        out = tuple(o[:c] for o in out)
    outp = []
    for p, s in zip(payloads, out[1:]):
        if p.dtype.itemsize == 4:
            outp.append(jax.lax.bitcast_convert_type(s, p.dtype))
        else:
            outp.append(s.astype(p.dtype))
    return (out[0], *outp)


# ---------------------------------------------------------------------------
# loop-form variant — interpret-mode only. The unrolled networks
# above trace one program op per compare-exchange (log2(T)^2 / 2 stages
# x rolls x operands), which Mosaic wants but which makes interpret-mode
# tracing quadratically expensive (minutes at T=1024 — unusable for the
# CPU tier-1 parity gate). This variant runs the SAME network as two
# nested lax loops with gather-by-computed-partner (i XOR j) inside the
# kernel body: tracing is O(1) in T, so an interpret run compiles in
# seconds. It does not lower for a TPU (in-kernel gather, as
# hash_table.py), so nothing dispatches it; the roll-based networks
# above are the Mosaic-native engines (tests/test_chip_compile.py).
# ---------------------------------------------------------------------------


def _kernel_u64_looped(n_payload: int, c: int, t: int):
    """refs = hi, lo + payloads in (C, T); out adds the perm. One
    program over the whole batch, stable via the riding iota."""

    def body(*refs):
        ins = refs[: 2 + n_payload]
        outs = refs[2 + n_payload:]
        i = jax.lax.broadcasted_iota(jnp.int32, (c, t), 1)
        ops0 = (ins[0][...], ins[1][...], i) + tuple(
            r[...] for r in ins[2:]
        )

        def stage(ops, k, j):
            p = jnp.bitwise_xor(i, j)  # partner index, same for every row
            partner = tuple(
                jnp.take_along_axis(x, p, axis=1) for x in ops
            )
            hi_, lo_, idx_ = ops[0], ops[1], ops[2]
            p_hi, p_lo, p_idx = partner[0], partner[1], partner[2]
            p_lt = (
                (p_hi < hi_)
                | ((p_hi == hi_) & (p_lo < lo_))
                | ((p_hi == hi_) & (p_lo == lo_) & (p_idx < idx_))
            )
            is_low = (i & j) == 0
            asc = (i & k) == 0
            keep_min = is_low == asc
            take = jnp.where(keep_min, p_lt, ~p_lt)
            return tuple(
                jnp.where(take, pv, xv) for pv, xv in zip(partner, ops)
            )

        n_k = max(t.bit_length() - 1, 0)  # log2(t) outer stages

        def outer(kk, ops):
            k = jnp.int32(1) << (kk + 1)

            def inner(s, ops):
                j = jnp.int32(1) << (kk - s)
                return stage(ops, k, j)

            return jax.lax.fori_loop(0, kk + 1, inner, ops)

        ops = jax.lax.fori_loop(0, n_k, outer, ops0)
        for r, v in zip(outs, (ops[0], ops[1], ops[2]) + ops[3:]):
            r[...] = v

    return body


@functools.lru_cache(maxsize=64)
def _sort_call_looped(n_payload: int, c: int, t: int, interpret: bool):
    def fn(*arrays):
        return pl.pallas_call(
            _kernel_u64_looped(n_payload, c, t),
            out_shape=[
                jax.ShapeDtypeStruct((c, t), jnp.uint32) for _ in range(2)
            ] + [jax.ShapeDtypeStruct((c, t), jnp.int32)] + [
                jax.ShapeDtypeStruct((c, t), jnp.uint32)
                for _ in range(n_payload)
            ],
            interpret=interpret,
        )(*arrays)

    return jax.jit(fn)


def batched_sort_u64_looped(
    key: jax.Array, *payloads: jax.Array, interpret: bool | None = None
):
    """:func:`batched_sort_u64` semantics (stable, same payload dtype
    rules) on the loop-form kernel — O(1) tracing cost in T."""
    if interpret is None:
        interpret = default_interpret()
    c, t = key.shape
    _check_pow2(t)
    hi = (key >> jnp.uint64(32)).astype(jnp.uint32)
    lo = key.astype(jnp.uint32)
    split = []
    wide = []
    for p in payloads:
        if p.dtype.itemsize == 8:
            pb = jax.lax.bitcast_convert_type(p, jnp.uint64)
            split.append((pb >> jnp.uint64(32)).astype(jnp.uint32))
            split.append(pb.astype(jnp.uint32))
            wide.append(True)
        elif p.dtype.itemsize == 4:
            split.append(jax.lax.bitcast_convert_type(p, jnp.uint32))
            wide.append(False)
        else:
            if jnp.issubdtype(p.dtype, jnp.floating):
                raise TypeError(
                    f"narrow float payload {p.dtype} would lose bits "
                    "through the u32 widening; cast it to float32 first"
                )
            split.append(p.astype(jnp.uint32))
            wide.append(False)
    out = _sort_call_looped(len(split), c, t, bool(interpret))(
        hi, lo, *split
    )
    s_key = (out[0].astype(jnp.uint64) << jnp.uint64(32)) | out[1].astype(
        jnp.uint64
    )
    perm = out[2]
    outp = []
    k = 3
    for p, w in zip(payloads, wide):
        if w:
            v = (
                out[k].astype(jnp.uint64) << jnp.uint64(32)
            ) | out[k + 1].astype(jnp.uint64)
            outp.append(jax.lax.bitcast_convert_type(v, p.dtype))
            k += 2
        elif p.dtype.itemsize == 4:
            outp.append(jax.lax.bitcast_convert_type(out[k], p.dtype))
            k += 1
        else:
            outp.append(out[k].astype(p.dtype))
            k += 1
    return (s_key, perm, *outp)
