"""Pallas TPU kernel: VMEM-resident open-addressing hash build/probe.

The join/groupby inner loop of the reference stack is cuco's device
hash table (insert_and_find / contains under warp-cooperative probing).
TPUs have no device-wide atomics, so this kernel re-expresses the same
table as a *vectorized leader election* over linear-probe rounds: every
live row proposes itself for its current slot, the lowest row id wins
the claim (a functional ``.at[slot].min`` — the deterministic stand-in
for ``atomicCAS``), and all rows then re-read the slot to check for a
key match. Rows carrying the same key walk the same probe sequence in
lockstep, so the winning claimant is always the LOWEST original row id
of its key group — exactly the stable representative the sort-based
exact path elects, which is what makes byte-parity provable.

Layout: inputs arrive as (C, T) chunks with a per-chunk table of
``S = table_slots`` slots (S a power of two, typically 2T). The whole
batch runs as ONE program over flattened arrays — chunk c's rows index
slots ``c*S + slot``, so chunks never collide and the interpreter path
stays fully vectorized (no per-chunk python loop, no grid unrolling).

Keys are u64 order words (ops/keys.py) split into u32 (hi, lo) halves
OUTSIDE the kernel — the same "no Mosaic i64 paths" discipline as
bitonic_sort.py. Both kernels gather (the build one also scatters) by
computed 1-D index vectors, which Mosaic refuses for a v5e
("NotImplementedError: Only 2D gather is supported"), so no registry
entry dispatches them (CHANGES.md PR 23); ``interpret=True`` keeps the
module's parity tests running on the CPU tier.

Termination is bounded: ``max_probes`` rounds. Rows still live after
the loop are reported in the ``overflow`` scalar; callers MUST treat a
nonzero overflow (or probe ``unresolved``) as a decline — the table
contents are valid, but unplaced rows have no slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import default_interpret

#: Linear-probe round bound. 64 covers load factors well past 0.5
#: (S = 2T) in practice; clustering beyond it reports overflow and the
#: caller declines to the exact path.
MAX_PROBES = 64


def hash_word(word: jax.Array) -> jax.Array:
    """u64 order word -> u32 slot hash (fmix32 over the folded halves).

    Computed OUTSIDE the kernel (free elementwise ops under XLA) so the
    kernel body only ever sees the initial slot."""
    lo = word.astype(jnp.uint32)
    hi = (word >> jnp.uint64(32)).astype(jnp.uint32)
    h = lo ^ (hi * jnp.uint32(0x9E3779B9))
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _check_pow2(s: int) -> None:
    if s & (s - 1) or s < 2:
        raise ValueError(f"table_slots must be a power of two, got {s}")


def _build_kernel(c: int, t: int, s: int, max_probes: int):
    n = c * t
    ns = c * s

    def body(lo_ref, hi_ref, valid_ref, slot0_ref,
             slot_ref, tlo_ref, thi_ref, trow_ref, ovf_ref, dup_ref):
        lo = lo_ref[...].reshape(n)
        hi = hi_ref[...].reshape(n)
        live0 = valid_ref[...].reshape(n) != 0
        pslot0 = slot0_ref[...].reshape(n)
        rowid = jax.lax.broadcasted_iota(jnp.int32, (c, t), 1).reshape(n)
        base = jax.lax.broadcasted_iota(jnp.int32, (c, t), 0).reshape(n) * s

        def round_(_, st):
            pslot, live, out_slot, tlo, thi, trow, dup = st
            fidx = base + pslot
            empty = trow[fidx] < 0
            # leader election: lowest row id among live rows pointing
            # at an empty slot claims it (rows of one chunk can only
            # collide with each other — fidx is chunk-offset)
            claim = jnp.full((ns,), n, jnp.int32).at[fidx].min(
                jnp.where(live & empty, rowid, n)
            )
            won = live & empty & (claim[fidx] == rowid)
            widx = jnp.where(won, fidx, ns)
            tlo = tlo.at[widx].set(lo, mode="drop")
            thi = thi.at[widx].set(hi, mode="drop")
            trow = trow.at[widx].set(rowid, mode="drop")
            # re-read: freshly claimed or pre-existing entry with our key?
            occ = trow[fidx] >= 0
            hit = live & occ & (tlo[fidx] == lo) & (thi[fidx] == hi)
            out_slot = jnp.where(hit, pslot, out_slot)
            dup = dup + jnp.sum(
                jnp.where(hit & (trow[fidx] != rowid), 1, 0),
                dtype=jnp.int32,
            )
            live = live & ~hit
            pslot = jnp.where(live, (pslot + 1) & (s - 1), pslot)
            return pslot, live, out_slot, tlo, thi, trow, dup

        st = jax.lax.fori_loop(
            0, max_probes, round_,
            (
                pslot0, live0, jnp.full((n,), -1, jnp.int32),
                jnp.zeros((ns,), jnp.uint32), jnp.zeros((ns,), jnp.uint32),
                jnp.full((ns,), -1, jnp.int32), jnp.int32(0),
            ),
        )
        _, live, out_slot, tlo, thi, trow, dup = st
        slot_ref[...] = out_slot.reshape(c, t)
        tlo_ref[...] = tlo.reshape(c, s)
        thi_ref[...] = thi.reshape(c, s)
        trow_ref[...] = trow.reshape(c, s)
        ovf_ref[0, 0] = jnp.sum(live, dtype=jnp.int32)
        dup_ref[0, 0] = dup

    return body


@functools.lru_cache(maxsize=64)
def _build_call(c: int, t: int, s: int, max_probes: int, interpret: bool):
    def fn(lo, hi, valid, slot0):
        return pl.pallas_call(
            _build_kernel(c, t, s, max_probes),
            out_shape=[
                jax.ShapeDtypeStruct((c, t), jnp.int32),
                jax.ShapeDtypeStruct((c, s), jnp.uint32),
                jax.ShapeDtypeStruct((c, s), jnp.uint32),
                jax.ShapeDtypeStruct((c, s), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            interpret=interpret,
        )(lo, hi, valid, slot0)

    return jax.jit(fn)


def build_table(
    lo: jax.Array,
    hi: jax.Array,
    valid: jax.Array,
    *,
    table_slots: int,
    max_probes: int = MAX_PROBES,
    interpret: bool | None = None,
):
    """Build one open-addressing table per chunk.

    ``lo``/``hi``: (C, T) u32 key halves; ``valid``: (C, T) int32
    occupancy (0 = padding/null, never inserted). Returns::

        slot       (C, T) i32  per-row slot in its chunk's table
                               (-1: invalid row, or unplaced overflow)
        table_lo   (C, S) u32  stored key halves per slot
        table_hi   (C, S) u32
        table_row  (C, S) i32  chunk-local row id of the FIRST (lowest
                               row id) inserter; -1 = empty slot
        overflow   ()     i32  valid rows left unplaced after
                               ``max_probes`` rounds (nonzero => the
                               caller must decline)
        dup        ()     i32  valid rows that matched an entry claimed
                               by a DIFFERENT row (== n_valid - distinct
                               when overflow == 0)
    """
    if interpret is None:
        interpret = default_interpret()
    c, t = lo.shape
    s = int(table_slots)
    _check_pow2(s)
    slot0 = (
        hash_word(
            hi.astype(jnp.uint64) << jnp.uint64(32)
            | lo.astype(jnp.uint64)
        )
        & jnp.uint32(s - 1)
    ).astype(jnp.int32)
    out = _build_call(c, t, s, int(max_probes), bool(interpret))(
        lo, hi, valid.astype(jnp.int32), slot0
    )
    slot, tlo, thi, trow, ovf, dup = out
    return slot, tlo, thi, trow, ovf[0, 0], dup[0, 0]


def _probe_kernel(c: int, t: int, s: int, max_probes: int):
    n = c * t

    def body(lo_ref, hi_ref, valid_ref, slot0_ref, tlo_ref, thi_ref,
             trow_ref, found_ref, row_ref, unres_ref):
        lo = lo_ref[...].reshape(n)
        hi = hi_ref[...].reshape(n)
        live0 = valid_ref[...].reshape(n) != 0
        pslot0 = slot0_ref[...].reshape(n)
        tlo = tlo_ref[...].reshape(c * s)
        thi = thi_ref[...].reshape(c * s)
        trow = trow_ref[...].reshape(c * s)
        base = jax.lax.broadcasted_iota(jnp.int32, (c, t), 0).reshape(n) * s

        def round_(_, st):
            pslot, live, found, row = st
            fidx = base + pslot
            occ = trow[fidx] >= 0
            hit = live & occ & (tlo[fidx] == lo) & (thi[fidx] == hi)
            found = found | hit
            row = jnp.where(hit, trow[fidx], row)
            # an empty slot along the probe sequence proves absence
            live = live & occ & ~hit
            pslot = jnp.where(live, (pslot + 1) & (s - 1), pslot)
            return pslot, live, found, row

        st = jax.lax.fori_loop(
            0, max_probes, round_,
            (
                pslot0, live0, jnp.zeros((n,), jnp.bool_),
                jnp.full((n,), -1, jnp.int32),
            ),
        )
        _, live, found, row = st
        found_ref[...] = found.reshape(c, t).astype(jnp.int32)
        row_ref[...] = row.reshape(c, t)
        unres_ref[0, 0] = jnp.sum(live, dtype=jnp.int32)

    return body


@functools.lru_cache(maxsize=64)
def _probe_call(c: int, t: int, s: int, max_probes: int, interpret: bool):
    def fn(lo, hi, valid, slot0, tlo, thi, trow):
        return pl.pallas_call(
            _probe_kernel(c, t, s, max_probes),
            out_shape=[
                jax.ShapeDtypeStruct((c, t), jnp.int32),
                jax.ShapeDtypeStruct((c, t), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            interpret=interpret,
        )(lo, hi, valid, slot0, tlo, thi, trow)

    return jax.jit(fn)


def probe_table(
    lo: jax.Array,
    hi: jax.Array,
    valid: jax.Array,
    table_lo: jax.Array,
    table_hi: jax.Array,
    table_row: jax.Array,
    *,
    max_probes: int = MAX_PROBES,
    interpret: bool | None = None,
):
    """Probe (C, T) query keys against per-chunk tables from
    :func:`build_table` (gather-only — no scatters inside). Returns::

        found       (C, T) i32  1 = key present in the chunk's table
        row         (C, T) i32  ``table_row`` of the matching slot
                                (-1 when not found)
        unresolved  ()     i32  valid queries that neither matched nor
                                hit an empty slot within ``max_probes``
                                (nonzero => the caller must decline)
    """
    if interpret is None:
        interpret = default_interpret()
    c, t = lo.shape
    s = int(table_lo.shape[1])
    _check_pow2(s)
    slot0 = (
        hash_word(
            hi.astype(jnp.uint64) << jnp.uint64(32)
            | lo.astype(jnp.uint64)
        )
        & jnp.uint32(s - 1)
    ).astype(jnp.int32)
    out = _probe_call(c, t, s, int(max_probes), bool(interpret))(
        lo, hi, valid.astype(jnp.int32), slot0,
        table_lo, table_hi, table_row,
    )
    found, row, unres = out
    return found, row, unres[0, 0]
