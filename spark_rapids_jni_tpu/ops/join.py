"""Equi-joins (cudf ``inner_join``/``left_join``/semi/anti), sort-merge.

Design (SURVEY.md §7 hard parts 1 & 5): no device hash tables — the build
side is sorted once by normalized keys (ops/keys.py) and the probe side
binary-searches lower/upper bounds lexicographically over the u64 key
words (log2(m) rounds of gathers, fully vectorized over probe rows).
Output cardinality is data-dependent, so materialization is two-phase:
count matches on device, size the output (host sync in the eager API, a
static capacity in the ``*_capped`` jittable variants), then expand
(`_expand` + `_join_output`) — the XLA-static equivalent of the
reference's two-phase batching (row_conversion.cu:505-511).

The expansion has two forms, and `mat_spreads` chooses between them from
the two widths that are static in the program, the output's and the
probe side's. Where the output is no wider than the probe side it is
the GATHER form: ``jnp.repeat(..., total_repeat_length=...)`` makes each
slot's probe row (one scatter of a probe side's worth of updates, one
cumsum and one gather), and everything a slot needs of that row is one
more gather at the output's width. Where the output is WIDER, a slot's
probe row is constant along a run of slots, and a run-length expansion
is not a gather: each 32-bit word of the row's values goes out as ONE
scatter of its first differences at the runs' starts and ONE cumsum
(`_Runs.spread`, exact in wrapping u32 arithmetic), so only the build
side is still gathered.

Nulls: null join keys never match (Spark inner-join semantics); left joins
still emit their left rows with a null right side.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..column import Column, Table
from . import compute
from . import keys as keys_mod
from .gather import gather_column, gather_table

# The fence is on the SEARCH probe's fused single-shot graph (key
# normalization + lexsort + `_lex_searchsorted` in one compiled region):
# it reproducibly killed the TPU worker at >= 32M rows with 64-bit keys
# (seen by a builder before this round, not re-tested since; every
# sub-graph passed in isolation at the same sizes — an XLA
# codegen/runtime fault, not OOM). 16M passed. Above this threshold the
# eager join APIs route themselves through the chunk-probed search so
# no public join API can crash the worker at any size — the reference's
# own discipline of never letting callers choose safety (its 2 GB batch
# splits are automatic, row_conversion.cu:476-479,505-511). The direct
# probe (`_probe_direct`) is reached only from the bucketed runner,
# whose ladder ends at 2^23 rows, and has never met the fence.
# Module-level so tests can lower it to pin the routing.
#
# MIN_CHUNK_OUT_BYTES floors the batched join's per-chunk output budget
# (module-level so the skew re-split path is testable at small scale).
#
# Scope of the fence: it keeps every compiled search-probe graph at or
# below this row count. The OUTER joins' materialization (expand +
# gathers over the full pair count) still runs single-shot, so a
# pathological fan-out can exhaust HBM — that sizing concern belongs to
# the memory planner (utils/hbm.py), not this fence.
FUSED_PROBE_MAX_ROWS = 16_000_000
MIN_CHUNK_OUT_BYTES = 64 << 20


def _on_accelerator() -> bool:
    """CPU runs the fused graph fine (and tests rely on it); only real
    accelerator backends need the fault fence."""
    return jax.default_backend() != "cpu"


def _is_tracing(table: Table) -> bool:
    return isinstance(table.columns[0].data, jax.core.Tracer)


def _needs_chunked_probe(left: Table, right: Table) -> bool:
    """True when the eager API must avoid the fused single-shot graph.

    Under jit (tracers) the fence cannot host-sync, so the caller keeps
    the fused graph — jittable ``*_capped`` users (e.g. shard_map
    per-device shards) stay below the threshold by construction."""
    if _is_tracing(left) or _is_tracing(right):
        return False
    if not _on_accelerator():
        return False
    return (
        max(left.row_count, right.row_count) > FUSED_PROBE_MAX_ROWS
    )


def _key_words(cols: Sequence[Column]) -> tuple[list[jax.Array], jax.Array]:
    """(order-key words with null payloads zeroed, all-valid mask)."""
    words: list[jax.Array] = []
    n = cols[0].data.shape[0]
    valid = jnp.ones((n,), dtype=jnp.bool_)
    for c in cols:
        if c.validity is not None:
            valid = valid & c.validity
    for c in cols:
        for w in keys_mod.column_order_keys(c):
            words.append(jnp.where(valid, w, jnp.uint64(0)))
    return words, valid


def _lex_searchsorted(
    sorted_words: list[jax.Array], query_words: list[jax.Array], side: str,
    first=0,
) -> jax.Array:
    """Vectorized multi-word binary search (lower/upper bound).
    ``first`` (a scalar, may be traced) starts the search there: the
    rows in front of it are never read."""
    m = sorted_words[0].shape[0]
    nq = query_words[0].shape[0]
    lo = jnp.full((nq,), first, dtype=jnp.int32)
    hi = jnp.full((nq,), m, dtype=jnp.int32)
    steps = max(1, int(np.ceil(np.log2(m + 1)))) if m > 0 else 1

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        safe_mid = jnp.clip(mid, 0, max(m - 1, 0))
        # go_right: sorted[mid] < q (lower bound) or <= q (upper bound)
        lt = jnp.zeros((nq,), dtype=jnp.bool_)
        eq = jnp.ones((nq,), dtype=jnp.bool_)
        for sw, qw in zip(sorted_words, query_words):
            sv = sw[safe_mid]
            lt = lt | (eq & (sv < qw))
            eq = eq & (sv == qw)
        go_right = lt | eq if side == "right" else lt
        active = lo < hi
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _equalize_string_key_pads(left, right, left_on, right_on):
    """Repad string KEY columns to one common width across both sides.

    The chunk-probed path compares each side's order words positionally
    (`zip` in _lex_searchsorted); string columns emit pad/8+1 words, so
    DIFFERENT pads would silently truncate the comparison to the
    narrower side's words and drop matches (caught by
    tests/test_join_routing.py::test_batched_string_join_mismatched_pads
    — batched string joins returned 0 rows). Repadding is free
    semantically: pad bytes are zero and lengths are unchanged."""
    lcols = [left.column(c) for c in left_on]
    rcols = [right.column(c) for c in right_on]
    if not any(
        lc.dtype.is_string or rc.dtype.is_string
        for lc, rc in zip(lcols, rcols)
    ):
        return left, right
    from .strings import repad

    left_cols = list(left.columns)
    right_cols = list(right.columns)
    for lc, rc, lref, rref in zip(lcols, rcols, left_on, right_on):
        if not (lc.dtype.is_string or rc.dtype.is_string):
            continue
        if not (lc.dtype.is_string and rc.dtype.is_string):
            # same rejection as _maybe_encode_string_keys: a silent skip
            # here would let _lex_searchsorted's positional zip truncate
            # the word comparison and return wrong matches
            raise TypeError("join key dtypes differ: STRING vs non-STRING")
        common = max(lc.data.shape[1], rc.data.shape[1])
        li = _resolve_col(left, lref)
        ri = _resolve_col(right, rref)
        if lc.data.shape[1] != common:
            left_cols[li] = repad(lc, common)
        if rc.data.shape[1] != common:
            right_cols[ri] = repad(rc, common)
    return (
        Table(left_cols, left.names),
        Table(right_cols, right.names),
    )


def _maybe_encode_string_keys(lcols, rcols):
    """Auto dictionary-encode string join keys (VERDICT r4 item 5): a
    pad-128 string key costs 17 u64 words per compare; one shared-
    dictionary encode (jittable, ops/strings.py) reduces every later
    sort/search compare to ONE int32 code with identical order and
    equality. Only the fused path encodes — the chunk-probed big-table
    path would need a 2n-row encode sort upfront, the very graph shape
    the fence exists to avoid."""
    if not any(c.dtype.is_string for c in lcols + rcols):
        return lcols, rcols
    from .strings import encode_join_keys

    lcols, rcols = list(lcols), list(rcols)
    for i, (lc, rc) in enumerate(zip(lcols, rcols)):
        if lc.dtype.is_string or rc.dtype.is_string:
            if not (lc.dtype.is_string and rc.dtype.is_string):
                raise TypeError(
                    "join key dtypes differ: STRING vs non-STRING"
                )
            lcols[i], rcols[i] = encode_join_keys(lc, rc)
    return lcols, rcols


def _prepare_build(
    right: Table,
    right_on: Sequence[Union[int, str]],
    right_valid: Optional[jax.Array] = None,
    rcols: Optional[Sequence[Column]] = None,
):
    """Sort the build side once: (perm_r, sorted key words). Invalid
    rows sink to the front on the leading validity word (0 < 1), outside
    the range any valid probe (lead word 1) can reach — reusable across
    any number of probe batches."""
    if rcols is None:
        rcols = [right.column(c) for c in right_on]
    rwords, rvalid = _key_words(rcols)
    if right_valid is not None:
        rvalid = rvalid & right_valid
    rsort_words = [rvalid.astype(jnp.uint64)] + rwords
    perm_r = jnp.lexsort(rsort_words[::-1])
    sorted_words = [w[perm_r] for w in rsort_words]
    return perm_r, sorted_words


def _probe_build(
    sorted_words,
    left: Table,
    left_on: Sequence[Union[int, str]],
    left_valid: Optional[jax.Array] = None,
    lcols: Optional[Sequence[Column]] = None,
):
    """Binary-search the prepared build side: (lo, counts, lvalid).
    ``lo`` is a row's first match in the sorted build side and, where
    it has none, the build side's first valid row (`_no_match_lo`)."""
    if lcols is None:
        lcols = [left.column(c) for c in left_on]
    lwords, lvalid = _key_words(lcols)
    if left_valid is not None:
        lvalid = lvalid & left_valid
    qwords = [jnp.ones_like(lvalid, dtype=jnp.uint64)] + lwords
    lo = _lex_searchsorted(sorted_words, qwords, "left")
    hi = _lex_searchsorted(sorted_words, qwords, "right")
    counts = jnp.where(lvalid, hi - lo, 0)
    first = _first_valid(sorted_words)
    return _no_match_lo(lo, counts, first), counts, lvalid


def _first_valid(sorted_words) -> jax.Array:
    """The first valid row of the sorted build side (its width when no
    row is valid): the invalid rows sort in front of it."""
    valid_w = sorted_words[0]
    return valid_w.shape[0] - jnp.sum(valid_w).astype(jnp.int32)


def _no_match_lo(lo, counts, first):
    """``lo`` as every probe returns it: a row's first match and, where
    it has none, ``first``. Nothing reads it there (`_expand`,
    `_left_emit`, the semi / anti keep) but the build columns of a left
    join's null rows, garbage behind their mask; ONE value, which every
    probe knows (a hole of `_probe_direct`'s table holds no insertion
    point) and no padding moves (the same build row whether the side
    arrived padded or not), keeps that garbage the same bytes by any
    probe, on the bucketed and on the exact path."""
    return jnp.where(counts > 0, lo, first)


# The direct probe's table (four bytes an entry, as wide as the build
# keys' span) may take one part in this many of the device's budget
# (`utils.hbm.budget_bytes`): on a 16 GB TPU v5e 2^27 entries, 512 MiB,
# and not 2^28. Memory is what ends it, not time. Provenance (TPU v5e;
# PERF.md §6, PR 41's stand-alone sweep, two seeds): filling a table
# and scattering the build rows into it costs 2.5 ms for 2^18 rows and
# 13.2 ms for 2^21 at 2^26 entries (3.0 / 13.5 at 2^27: ~5 ns an update
# and the fill's bytes, whatever the span; 1.75 ms in Q3's request), and
# the ONE gather a probe side of 2^23 rows then makes costs 0.073 s
# from a table of up to 2^24 entries, which the compiler keeps in the
# fast memory space, and 0.12-0.24 s by its addresses from 2^25, 2^26
# or 2^27 entries in HBM, no more at 2^27 than at 2^25 (0.150 s in
# Q3's request, at 2^26). The search it replaces gathers
# `2 x ceil(log2(m + 1))` times at that width over one u32 word
# (`_probe_offsets`: 38 x 0.072 s against 2^18 build rows), so the
# table wins by 10x at every width measured, and a share of the chip is
# the honest limit: with P shuffle partitions a TPC-H order key spans
# about P times a partition's `lineitem` rows (60 M at P = 7 over
# batches of 8 M: 2^26 entries), so 2^27 holds P = 15 at that batch
# and the search takes over from there, as it does for a span past
# 2^30. 2^28 entries were not measured.
DIRECT_TABLE_BUDGET_SHARE = 16
# no table is narrower: spans under this share one executable
DIRECT_TABLE_MIN_ENTRIES = 1024


def addressable_key(cols: Sequence[Column]) -> bool:
    """True for ONE fixed-width integer-family column of at most 64 bits
    (ints, DECIMAL32/64, timestamps, durations, BOOL8), whose one order
    word is the value itself, shifted."""
    from .. import dtype as dt

    if len(cols) != 1:
        return False
    d = cols[0].dtype
    return (
        d.is_integer or d.is_boolean or d.is_timestamp or d.is_duration
        or d.id in (dt.TypeId.DECIMAL32, dt.TypeId.DECIMAL64)
    )


def direct_key(lcols: Sequence[Column], rcols: Sequence[Column]) -> bool:
    """True when the join key is one the direct probe can address: an
    `addressable_key` on each side."""
    return addressable_key(lcols) and addressable_key(rcols)


def build_key_span(
    right: Table,
    right_on: Sequence[Union[int, str]],
    right_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """``u64[4]``: the smallest and the largest order word among the
    build side's valid keys, how many there are, and whether any of
    them repeats — what a runner reads (one tiny program at the build
    side's width) to choose the probe of a `direct_key` join, and
    whether an inner join on it only selects (`lookup_unique`)."""
    (word,), valid = _key_words([right.column(c) for c in right_on])
    if right_valid is not None:
        valid = valid & right_valid
    top = jnp.uint64(np.iinfo(np.uint64).max)
    count = jnp.sum(valid)
    # invalid rows sort last as `top`; a valid key that IS `top` sorts
    # among them, and the first `count` words still hold every valid key
    ordered = jnp.sort(jnp.where(valid, word, top))
    pair = jnp.arange(1, ordered.shape[0]) < count
    repeats = jnp.any(pair & (ordered[1:] == ordered[:-1]))
    return jnp.stack([
        jnp.min(jnp.where(valid, word, top)),
        jnp.max(jnp.where(valid, word, jnp.uint64(0))),
        count.astype(jnp.uint64),
        repeats.astype(jnp.uint64),
    ])


def direct_table_size(kmin: int, kmax: int, valid_rows: int) -> Optional[int]:
    """Table size ``T`` of the direct probe for a build side whose valid
    keys span ``[kmin, kmax]`` (`build_key_span`'s words, as host
    integers), or None for the search: ``T`` is the span's next power
    of two (not a row bucket: the ladder's cap bounds batches, not
    tables), taken while its four-byte entries stay within
    `DIRECT_TABLE_BUDGET_SHARE` of the device's budget. A build side
    with no valid key has no span, and a span past 2^30 no int32
    address (4 GiB of entries: no chip's share)."""
    from ..utils import hbm

    if valid_rows <= 0 or kmax - kmin >= 1 << 30:
        return None
    size = max(DIRECT_TABLE_MIN_ENTRIES, 1 << (kmax - kmin).bit_length())
    if 4 * size * DIRECT_TABLE_BUDGET_SHARE > hbm.budget_bytes():
        return None
    return size


def offsets_fit(kmin: int, kmax: int, valid_rows: int) -> bool:
    """True when a build side whose valid keys span ``[kmin, kmax]``
    (`build_key_span`'s words, as host integers) can be searched as ONE
    u32 word a row (`_probe_offsets`): the span is under 2^32 wide."""
    return valid_rows > 0 and kmax - kmin < 1 << 32


def _build_offsets(sorted_words):
    """A `direct_key` build side whose valid keys span under 2^32
    values, as ONE u32 word a row: ``(first, kmin, kmax, offsets)``, the
    first valid row of the sorted build side (invalid rows sort in front
    of it), the span's ends, and every sorted key's distance from
    ``kmin``. In front of ``first`` the distances are garbage, which a
    search that starts there never reads.

    Why one word (TPU v5e, PERF.md §6, PR 40): the search gathers from
    each word's table once a step, and of the four u32 tables a side
    that the validity word and a 64-bit key word make, the compiler
    keeps one in the fast memory space; gathers from the other three
    cost 2-3.7x as much, by the data."""
    key_w = sorted_words[1]
    m = key_w.shape[0]
    first = _first_valid(sorted_words)
    kmin = key_w[jnp.clip(first, 0, m - 1)]
    kmax = key_w[m - 1]
    return first, kmin, kmax, (key_w - kmin).astype(jnp.uint32)


def _probe_offsets(
    sorted_words,
    lcols: Sequence[Column],
    left_valid: Optional[jax.Array] = None,
):
    """`_probe_build`'s ``(lo, counts, lvalid)``, bit for bit, by the
    same two searches over ONE u32 word a side (`_build_offsets`), for
    a `direct_key` join whose build side the caller read: `offsets_fit`.
    A probe key outside the span is decided on the order words before
    the subtraction, as in `_direct_address`, and matches nothing."""
    (q,), lvalid = _key_words(lcols)
    if left_valid is not None:
        lvalid = lvalid & left_valid
    first, kmin, kmax, offsets = _build_offsets(sorted_words)
    query = [(q - kmin).astype(jnp.uint32)]  # wrapped outside the span
    s_lo = _lex_searchsorted([offsets], query, "left", first=first)
    s_hi = _lex_searchsorted([offsets], query, "right", first=first)
    counts = jnp.where(lvalid & (q >= kmin) & (q <= kmax), s_hi - s_lo, 0)
    return _no_match_lo(s_lo, counts, first), counts, lvalid


def _table_heads(sorted_words, table_size: int):
    """Where the direct probe's table is written: ``(first, kmin, kmax,
    addr, run)``, `_build_offsets`' first three and two arrays at the
    build side's width. ``addr[i]`` is the table entry of sorted build
    row ``i``, its key's distance from ``kmin``, where ``i`` is the
    HEAD of a run of equal valid keys, and ``table_size`` (past the
    end: dropped) everywhere else: the null and padded rows, which sort
    in front of the first valid one, the rest of a run, and a key whose
    distance does not fit the table (a span wider than the caller
    said). ``run[i]`` is the length of the run a head starts (garbage
    elsewhere)."""
    first, kmin, kmax, offsets = _build_offsets(sorted_words)
    m = offsets.shape[0]
    i = jnp.arange(m, dtype=jnp.int32)
    head = (i >= first) & ((i == first) | (offsets != jnp.roll(offsets, 1)))
    # the next head behind each row (m behind the last), by a running
    # minimum from the end: a head's run ends where the next one starts
    nxt = jax.lax.cummin(jnp.where(head, i, jnp.int32(m)), reverse=True)
    run = jnp.concatenate([nxt[1:], jnp.full((1,), m, jnp.int32)]) - i
    addr = jnp.where(
        head, jnp.minimum(offsets, jnp.uint32(table_size)),
        jnp.uint32(table_size),
    ).astype(jnp.int32)
    return first, kmin, kmax, addr, run


def _direct_table(addr, values, table_size: int, hole):
    """The direct probe's table, filled by ONE scatter of the build
    rows: ``values`` at `_table_heads`' addresses, ``hole`` at every
    key of the span that no valid build row holds.

    What it costs (TPU v5e, PERF.md §6, PR 41): one word an entry of
    fill and one update a build row, whatever the span; the search's
    own answer for every entry, which this replaced, cost
    ``2 x ceil(log2(m + 1))`` gathers at the TABLE's width."""
    return jnp.full((table_size,), hole, values.dtype).at[addr].set(
        values, mode="drop"
    )


def _direct_address(q, kmin, kmax, table_size: int):
    """Where a probe key's order word ``q`` lies: ``(below, above,
    off)``. Whether it lies in the span is decided on the order words
    BEFORE the subtraction, so a key at INT64's other end cannot wrap
    into it; outside the span the difference is clamped (below it,
    wrapped first) and what ``off`` reads is never used."""
    off = jnp.minimum(q - kmin, jnp.uint64(table_size - 1)).astype(jnp.int32)
    return q < kmin, q > kmax, off


def _probe_direct(
    sorted_words,
    table_size: int,
    lcols: Sequence[Column],
    left_valid: Optional[jax.Array] = None,
    unique: bool = False,
):
    """`_probe_build`'s ``(lo, counts, lvalid)``, bit for bit, by address
    instead of by search, for a build side whose valid keys span at most
    ``table_size`` values (`direct_table_size` chose it from the same
    build side).

    A probe row costs elementwise work on its key and one gather at
    ``key - kmin`` (`_direct_address`) from the table `_direct_table`
    scatters: each key's first row in the sorted build side. ``unique``
    (static: the caller read that no valid build key repeats) makes a
    hit a count of one; otherwise the run's length rides the same word
    where both fit 32 bits, or is one more gather, from the build
    side's width."""
    (q,), lvalid = _key_words(lcols)
    if left_valid is not None:
        lvalid = lvalid & left_valid
    m = sorted_words[1].shape[0]
    first, kmin, kmax, addr, run = _table_heads(sorted_words, table_size)
    below, above, off = _direct_address(q, kmin, kmax, table_size)
    inside = lvalid & ~(below | above)
    i = jnp.arange(m, dtype=jnp.int32)
    bits = int(m).bit_length()  # lo and a run's length lie in [0, m]
    if not unique and 2 * bits <= 32:
        # one word carries both: a hole's is 0, a count of none
        packed = (i.astype(jnp.uint32) << bits) | run.astype(jnp.uint32)
        got = _direct_table(addr, packed, table_size, 0)[off]
        g_lo = (got >> bits).astype(jnp.int32)
        g_cnt = (got & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)
        hit = inside & (g_cnt > 0)
    else:
        g_lo = _direct_table(addr, i, table_size, -1)[off]
        hit = inside & (g_lo >= 0)
        g_cnt = jnp.int32(1) if unique else run[jnp.maximum(g_lo, 0)]
    counts = jnp.where(hit, g_cnt, 0)
    return _no_match_lo(g_lo, counts, first), counts, lvalid


def lookup_unique(
    left: Table,
    right: Table,
    left_on: Sequence[Union[int, str]],
    right_on: Sequence[Union[int, str]],
    left_valid: Optional[jax.Array],
    right_valid: Optional[jax.Array],
    table_size: int,
):
    """An inner join whose build key repeats no valid value, as what it
    is: a selection of the probe rows plus a row-local lookup. Returns
    ``(matched, out)``: the probe rows that have their ONE build row,
    and `_join_output`'s table with every probe row where it was (an
    unmatched row's build columns are garbage behind ``matched``). The
    probe rows that match, in probe order, are the exact inner join.

    The caller read the build side (`build_key_span`): no valid key
    repeats and the valid keys span at most ``table_size`` values; and
    it holds both keys to `direct_key`. Null keys on either side and
    rows outside ``left_valid`` / ``right_valid`` match nothing, as in
    `_match_ranges`.

    One gather at the probe's width finds each row's build row through
    the direct probe's address (`_direct_table` scatters the build ROW
    of every key of the span, -1 where the span has a hole); a build
    column then costs one more a 32-bit word, and none when nothing
    reads it."""
    perm_r, sorted_words = _prepare_build(right, right_on, right_valid)
    (q,), lvalid = _key_words([left.column(c) for c in left_on])
    if left_valid is not None:
        lvalid = lvalid & left_valid
    # one 32-bit word an entry (lexsort's permutation is int64 here)
    _, kmin, kmax, addr, _ = _table_heads(sorted_words, table_size)
    t_row = _direct_table(addr, perm_r.astype(jnp.int32), table_size, -1)
    below, above, off = _direct_address(q, kmin, kmax, table_size)
    right_idx = jnp.where(lvalid & ~(below | above), t_row[off], -1)
    out = _join_output(
        left, right, right_on, None, jnp.maximum(right_idx, 0), None, None
    )
    return right_idx >= 0, out


def _match_ranges(
    left: Table,
    right: Table,
    left_on: Sequence[Union[int, str]],
    right_on: Sequence[Union[int, str]],
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
    table_size: Optional[int] = None,
    narrow: bool = False,
    unique: bool = False,
):
    """Per-left-row [lo, hi) match range into the sorted right side.

    ``left_valid``/``right_valid`` exclude rows entirely (shuffle-padding
    occupancy) — excluded rows behave like null keys and never match:
    invalid left rows get their counts zeroed, and invalid right rows sort
    ahead of every valid row on the leading validity word (0 < 1), outside
    the range any valid query (probing with lead word 1) can reach.

    String join keys are dictionary-encoded to int32 codes first (one
    shared dictionary, order-preserving) so every sort/search compare
    touches one word instead of pad/8+1.

    ``table_size`` (static; `direct_table_size`, chosen by a caller that
    could read the build side's key span) resolves the left keys by
    address (`_probe_direct`; ``unique``, static: the same caller read
    that no valid build key repeats); None, what every caller that
    cannot read passes, searches: over ONE u32 word a side where the
    same caller found that `offsets_fit` (``narrow``, static;
    `_probe_offsets`), else over every order word (`_probe_build`). The
    answer is the same.
    """
    lcols = [left.column(c) for c in left_on]
    rcols = [right.column(c) for c in right_on]
    lcols, rcols = _maybe_encode_string_keys(lcols, rcols)
    perm_r, sorted_words = _prepare_build(
        right, right_on, right_valid, rcols=rcols
    )
    if table_size is None and not narrow:
        lo, counts, lvalid = _probe_build(
            sorted_words, left, left_on, left_valid, lcols=lcols
        )
        return perm_r, lo, counts, lvalid
    if not direct_key(lcols, rcols):
        raise TypeError(
            "a direct-address probe and a one-word search need one "
            "integer-family key column of at most 64 bits a side"
        )
    if table_size is None:
        lo, counts, lvalid = _probe_offsets(sorted_words, lcols, left_valid)
    else:
        lo, counts, lvalid = _probe_direct(
            sorted_words, table_size, lcols, left_valid, unique
        )
    return perm_r, lo, counts, lvalid


@functools.lru_cache(maxsize=64)
def _chunk_ranges_fn(on: tuple, with_valid: bool):
    """Jitted per-chunk probe: (lo, counts, lvalid, chunk total). The
    single probe wrapper every chunked caller shares (one jit cache):
    ``_match_ranges_safe`` uses the full triple, ``inner_join_batched``
    the count sum — returning both costs two extra scalars."""
    def fn(sw, chunk, chunk_valid=None):
        lo, counts, lvalid = _probe_build(
            list(sw), chunk, list(on), chunk_valid
        )
        return lo, counts, lvalid, jnp.sum(counts)

    if with_valid:
        return jax.jit(fn)
    return jax.jit(lambda sw, chunk: fn(sw, chunk))


@functools.lru_cache(maxsize=64)
def _batched_prep_valid_fn(right_on: tuple):
    return jax.jit(
        lambda r, rv: _prepare_build(r, list(right_on), rv)
    )


def _match_ranges_safe(
    left: Table,
    right: Table,
    left_on: Sequence[Union[int, str]],
    right_on: Sequence[Union[int, str]],
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
):
    """Eager ``_match_ranges`` that never builds the faulting fused
    graph: build side sorted in its own jit, probe side searched in
    ``FUSED_PROBE_MAX_ROWS`` chunks (each a known-safe graph), results
    concatenated. Drop-in for the eager outer joins and count APIs;
    occupancy masks ride along (sliced per probe chunk)."""
    if not _needs_chunked_probe(left, right):
        return _match_ranges(
            left, right, left_on, right_on, left_valid, right_valid
        )
    from .copying import slice_rows

    left, right = _equalize_string_key_pads(
        left, right, left_on, right_on
    )
    if right_valid is not None:
        perm_r, sorted_words = _batched_prep_valid_fn(tuple(right_on))(
            right, right_valid
        )
    else:
        perm_r, sorted_words = _batched_prep_fn(tuple(right_on))(right)
    sorted_words = tuple(sorted_words)
    probe = _chunk_ranges_fn(tuple(left_on), left_valid is not None)
    n = left.row_count
    step = FUSED_PROBE_MAX_ROWS
    los, counts, lvalids = [], [], []
    for start in range(0, n, step):
        stop = min(start + step, n)
        chunk = slice_rows(left, start, stop)
        if left_valid is not None:
            lo_c, cnt_c, lv_c, _ = probe(
                sorted_words, chunk, left_valid[start:stop]
            )
        else:
            lo_c, cnt_c, lv_c, _ = probe(sorted_words, chunk)
        los.append(lo_c)
        counts.append(cnt_c)
        lvalids.append(lv_c)
    return (
        perm_r,
        jnp.concatenate(los),
        jnp.concatenate(counts),
        jnp.concatenate(lvalids),
    )


def mat_spreads(total: int, n_left: int) -> bool:
    """Which form a materialise of ``total`` output slots from
    ``n_left`` probe rows takes: True for the SPREAD form (`_Runs`),
    False for the gather form. Both widths are static in the program
    (the output's bucket, or the exact count; the probe side's bucket),
    so every caller of `_expand` and the host that counts
    ``join.mat.spread`` ask this ONE function and nothing else decides.

    A word spread costs one update a PROBE row and a cumsum over the
    output, a word gathered one read an OUTPUT slot (TPU v5e, PERF.md
    §6, PR 51: a scatter 8.7 ns an update, a cumsum 0.19 ns a slot, a
    gather 7-9 ns a slot from the fast memory space and 21-24 from
    HBM). The ladder's buckets grow by 2, so a wider output is at least
    twice the probe side and the spread wins by 2x at the very least;
    at equal widths the two are within each other's error, no cell
    measures it, and the case stays the gather form's, as does every
    output narrower than its probe side (TPC-H Q3's joins place 2^16
    rows from 2^23: a scatter of 2^23 updates a word against gathers
    that cost next to nothing)."""
    return total > n_left


def _as_words(x) -> list:
    """A 1-D fixed-width leaf as u32 words a row: a 64-bit value's low
    and high halves (no carry crosses them in `_Runs.spread`), a 32-bit
    one's bits, a narrower one's or a bool's widened."""
    if x.dtype == jnp.bool_:
        return [x.astype(jnp.uint32)]
    size = x.dtype.itemsize
    bits = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * size}"))
    if size == 8:
        return [bits.astype(jnp.uint32), (bits >> 32).astype(jnp.uint32)]
    return [bits.astype(jnp.uint32)]


def _from_words(words: list, like):
    """`_as_words`' inverse, for a leaf of ``like``'s dtype."""
    if like.dtype == jnp.bool_:
        return words[0] != 0
    size = like.dtype.itemsize
    if size == 8:
        bits = words[0].astype(jnp.uint64) | (
            words[1].astype(jnp.uint64) << 32
        )
    else:
        bits = words[0].astype(jnp.dtype(f"uint{8 * size}"))
    return jax.lax.bitcast_convert_type(bits, like.dtype)


def _spreadable(x) -> bool:
    """A leaf `_as_words` takes: one value a row, of 8 to 64 bits. A
    STRING's or LIST's padded payload and DECIMAL128's limbs (2-D) keep
    their gather."""
    return x.ndim == 1 and x.dtype.itemsize in (1, 2, 4, 8)


class _Runs:
    """The probe rows' runs of output slots, where `mat_spreads`: row
    ``i`` owns the ``emit[i]`` slots from ``start[i] = sum(emit[:i])``,
    and what `_expand` returns in the place of ``left_idx``.

    ``start`` is non-decreasing, and slot ``s`` belongs to the last row
    ``j`` with ``start[j] <= s`` and ``emit[j] > 0``: every row up to
    ``j`` starts at or before ``s`` and every row behind it after (a row
    that emits nothing shares its successor's start). So the sum of the
    first differences ``v[i] - v[i - 1]`` over the rows that start at or
    before ``s`` telescopes to ``v[j]``: scatter-add each row's
    difference at its start, cumsum over the slots, and slot ``s`` holds
    its row's word, bit for bit in u32 arithmetic, wrap and all. Slots
    past ``sum(emit)`` hold the last row's word, as ``jnp.repeat`` pads;
    a row that starts past ``total`` (a capacity under the count) is
    dropped."""

    def __init__(self, start, total: int):
        self.start = start
        self.total = total

    def spread(self, word):
        """A per-row ``u32[n_left]`` word -> the per-slot ``u32[total]``
        word: one scatter-add of a probe side's worth of updates and one
        cumsum over the slots. A word at a time: stacked with another
        (slots on the lanes) a word's scatter costs 2.3x as much and its
        cumsum the same (TPU v5e, PERF.md §6, PR 51)."""
        diff = word - jnp.pad(word[:-1], (1, 0))
        placed = jnp.zeros((self.total,), jnp.uint32).at[self.start].add(
            diff, mode="drop", indices_are_sorted=True
        )
        return jnp.cumsum(placed, dtype=jnp.uint32)

    def leaf(self, x):
        """A 1-D fixed-width leaf of the probe side, spread word by
        word."""
        return _from_words([self.spread(w) for w in _as_words(x)], x)

    @functools.cached_property
    def index(self):
        """Each slot's probe row, `_expand`'s ``left_idx`` of the gather
        form, for what is not spread: the scatter and the cumsum of
        ``jnp.repeat`` without its closing gather of an ``iota``."""
        n_left = self.start.shape[0]
        return self.spread(jnp.arange(n_left, dtype=jnp.uint32)).astype(
            jnp.int32
        )

    def column(self, col: Column) -> Column:
        """``gather_column(col, self.index)`` with every leaf spread
        where all of them are fixed-width; a column with any other leaf
        keeps that gather whole."""
        leaves = col.data, col.validity, col.lengths
        if not all(x is None or _spreadable(x) for x in leaves):
            return gather_column(col, self.index)
        data, validity, lengths = (
            None if x is None else self.leaf(x) for x in leaves
        )
        return Column(data, col.dtype, validity, lengths)


def _left_index(rows):
    """`_expand`'s first result as the index array of the gather form,
    whichever form it took."""
    return rows.index if isinstance(rows, _Runs) else rows


def _expand(
    perm_r, lo, counts, total: int, left_outer: bool, emit=None
):
    """Materialize (left rows, right_idx, right_valid, in_range) over
    ``total`` output slots. The left rows are ``left_idx``, each slot's
    probe row, or where `mat_spreads` the `_Runs` that spread a probe
    row's values without it (`_join_output` takes either, `_left_index`
    gives the array).

    ``emit`` overrides the per-left-row output count (used by the capped
    left join to skip shuffle-padding rows entirely)."""
    n_left = counts.shape[0]
    if emit is None:
        emit = jnp.maximum(counts, 1) if left_outer else counts
    start = jnp.cumsum(emit) - emit
    if mat_spreads(total, n_left):
        # a slot's place in the sorted build side is lo + (slot - start)
        # and it is matched while slot < start + counts: each ONE word
        # of its row (the one nothing reads is no program's work)
        left = _Runs(start, total)
        slot = jnp.arange(total, dtype=jnp.int32)
        matched = slot < left.leaf(start + counts)
        pos = slot + left.leaf(lo - start)
    else:
        left = jnp.repeat(
            jnp.arange(n_left, dtype=jnp.int32), emit,
            total_repeat_length=total,
        )
        k = jnp.arange(total, dtype=jnp.int32) - start[left]
        matched = k < counts[left]
        pos = lo[left] + k
    r_sorted_pos = jnp.clip(pos, 0, max(perm_r.shape[0] - 1, 0))
    right_idx = perm_r[r_sorted_pos]
    # pairs beyond the emitted total (possible when total is a capacity)
    in_range = jnp.arange(total, dtype=jnp.int32) < jnp.sum(emit)
    return left, right_idx, matched & in_range, in_range


def _join_output(
    left: Table,
    right: Table,
    right_on: Sequence[Union[int, str]],
    left_idx,
    right_idx,
    matched,
    row_valid,
) -> Table:
    """left columns + right columns (minus its join keys, like Spark USING)."""
    drop = set()
    for c in right_on:
        if isinstance(c, str):
            if right.names is not None:
                drop.add(right.names.index(c))
        else:
            drop.add(c)
    # left_idx None: every left row where it is (`lookup_unique`);
    # a `_Runs`: the form of `_expand` that gathers no left column
    if left_idx is None:
        out_cols = list(left.columns)
    elif isinstance(left_idx, _Runs):
        out_cols = [left_idx.column(c) for c in left.columns]
    else:
        out_cols = list(gather_table(left, left_idx, None).columns)
    out_names = list(left.names) if left.names else [f"l{i}" for i in range(left.num_columns)]
    for j, c in enumerate(right.columns):
        if j in drop:
            continue
        g = gather_table(Table([c]), right_idx, matched).columns[0]
        out_cols.append(g)
        out_names.append(
            right.names[j] if right.names else f"r{j}"
        )
    return Table(out_cols, out_names)


def inner_join_from_ranges(
    left: Table,
    right: Table,
    right_on: Sequence[Union[int, str]],
    perm_r,
    lo,
    counts,
    capacity: int,
) -> tuple[Table, jax.Array]:
    """Materialize a capped inner join from ALREADY-COMPUTED match
    ranges (a prior _prepare_build + _probe_build pass) — the
    share-the-probe half of two-phase sizing. Jittable; pairs past the
    count are padding (nulled)."""
    left_idx, right_idx, matched, in_range = _expand(
        perm_r, lo, counts, capacity, left_outer=False
    )
    out = _join_output(
        left, right, right_on, left_idx, right_idx, matched, in_range
    )
    cols = [
        Column(
            c.data,
            c.dtype,
            in_range
            if c.validity is None
            else jnp.logical_and(c.validity, in_range),
            c.lengths,
        )
        for c in out.columns
    ]
    return Table(cols, out.names), jnp.sum(counts)


def inner_join_capped(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    capacity: int,
    right_on: Optional[Sequence[Union[int, str]]] = None,
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
) -> tuple[Table, jax.Array]:
    """Jittable inner join with static output capacity; returns (padded
    table, device match count). Pairs past the count are padding."""
    right_on = right_on or on
    perm_r, lo, counts, _ = _match_ranges(
        left, right, on, right_on, left_valid, right_valid
    )
    return inner_join_from_ranges(
        left, right, right_on, perm_r, lo, counts, capacity
    )


def _left_emit(counts, left_valid):
    """Per-left-row output count of a LEFT OUTER join — the single
    definition both sizing phases share: null-KEY rows match nothing
    (counts already zeroed by _match_ranges) but still emit their one
    left-outer row; only shuffle-PADDING rows (left_valid False) emit
    nothing."""
    occ = (
        left_valid
        if left_valid is not None
        else jnp.ones(counts.shape, jnp.bool_)
    )
    return jnp.where(occ, jnp.maximum(counts, 1), 0)


def left_join_capped(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    capacity: int,
    right_on: Optional[Sequence[Union[int, str]]] = None,
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
) -> tuple[Table, jax.Array]:
    """Jittable LEFT OUTER join with static output capacity; returns
    (padded table, device row count). Every valid left row emits at
    least once (null right side when unmatched); shuffle-padding rows
    (``left_valid`` False) emit nothing."""
    right_on = right_on or on
    perm_r, lo, counts, _ = _match_ranges(
        left, right, on, right_on, left_valid, right_valid
    )
    emit = _left_emit(counts, left_valid)
    left_idx, right_idx, matched, in_range = _expand(
        perm_r, lo, counts, capacity, left_outer=True, emit=emit
    )
    out = _join_output(
        left, right, right_on, left_idx, right_idx,
        jnp.logical_and(matched, in_range), in_range,
    )
    cols = [
        Column(
            c.data,
            c.dtype,
            in_range
            if c.validity is None
            else jnp.logical_and(c.validity, in_range),
            c.lengths,
        )
        for c in out.columns
    ]
    return Table(cols, out.names), jnp.sum(emit)


def left_join_count(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Jittable LEFT OUTER output-row count (phase 1 of two-phase
    sizing): matches plus one per unmatched occupied left row (null-key
    rows count; shuffle-padding rows don't)."""
    right_on = right_on or on
    _, _, counts, _ = _match_ranges_safe(
        left, right, on, right_on, left_valid, right_valid
    )
    return jnp.sum(_left_emit(counts, left_valid))


def membership_mask(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Jittable per-left-row bool: has at least one match in right
    (the SEMI/ANTI join predicate; fixed shape, shard_map-friendly)."""
    right_on = right_on or on
    # eager big-table calls take the fault-fenced chunked probe; under
    # jit (tracers) _match_ranges_safe falls through to the fused graph
    _, _, counts, lvalid = _match_ranges_safe(
        left, right, on, right_on, left_valid, right_valid
    )
    return jnp.logical_and(lvalid, counts > 0)


def inner_join_count(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
    left_valid: Optional[jax.Array] = None,
    right_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Jittable match count — phase 1 of the two-phase output sizing
    (the generalization of row_conversion.cu:505-511): count on device,
    host-sync once, then materialize with a static capacity."""
    right_on = right_on or on
    _, _, counts, _ = _match_ranges_safe(
        left, right, on, right_on, left_valid, right_valid
    )
    return jnp.sum(counts)


def inner_join(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
) -> Table:
    """Eager inner equi-join (host-syncs the match count).

    Above ``FUSED_PROBE_MAX_ROWS`` on an accelerator backend this routes
    itself through :func:`inner_join_batched` — the fused single-shot
    graph faults the TPU worker at >= 32M rows (see module constant)."""
    right_on = right_on or on
    if _needs_chunked_probe(left, right):
        return inner_join_batched(left, right, on, right_on)
    perm_r, lo, counts, _ = _match_ranges(left, right, on, right_on)
    total = int(jnp.sum(counts))
    if total == 0:
        left_idx = jnp.zeros((0,), jnp.int32)
        right_idx = jnp.zeros((0,), jnp.int32)
        return _join_output(
            left, right, right_on, left_idx, right_idx,
            jnp.zeros((0,), jnp.bool_), jnp.zeros((0,), jnp.bool_),
        )
    left_idx, right_idx, matched, _ = _expand(
        perm_r, lo, counts, total, left_outer=False
    )
    return _join_output(left, right, right_on, left_idx, right_idx, None, None)


@functools.lru_cache(maxsize=64)
def _batched_prep_fn(right_on: tuple):
    return jax.jit(lambda r: _prepare_build(r, list(right_on)))


@functools.lru_cache(maxsize=256)
def _batched_materialize_fn(right_on: tuple, cap: int):
    def fn(perm_r, lo, counts, chunk, r):
        left_idx, right_idx, _, _ = _expand(
            perm_r, lo, counts, cap, left_outer=False
        )
        # no matched/row_valid masks: rows past the chunk total are
        # sliced away by the caller, and passing masks here would hang
        # an all-True validity on right columns that the single-shot
        # inner_join leaves as None (schema parity)
        return _join_output(
            chunk, r, list(right_on), left_idx, right_idx, None, None
        )

    return jax.jit(fn)


def inner_join_batched(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
    probe_rows: Optional[int] = None,
) -> Table:
    """Eager inner join, probe side processed in ``probe_rows`` batches
    (default: ``FUSED_PROBE_MAX_ROWS``, resolved at call time so tuning
    the fence threshold shrinks the batched chunks with it).

    The single-shot join at 100M×100M rows needs both sides, the sorted
    build words, AND the expanded output resident at once — past the HBM
    of one chip (observed: the v5e worker dies). This is the reference's
    own batching discipline (2 GB splits, row_conversion.cu:505-511)
    applied to the probe side: the build side is sorted ONCE and every
    probe batch binary-searches it, materializing only its own slice of
    the output. Equal batch shapes reuse one compiled executable."""
    from .copying import concatenate, slice_rows

    right_on = right_on or on
    pieces = list(
        inner_join_batches(left, right, on, right_on, probe_rows)
    )
    if not pieces:
        # empty output with the exact join schema — no build-side sort
        z = jnp.zeros((0,), jnp.int32)
        return _join_output(
            slice_rows(left, 0, 0), right, right_on, z, z,
            jnp.zeros((0,), jnp.bool_), jnp.zeros((0,), jnp.bool_),
        )
    return concatenate(pieces) if len(pieces) > 1 else pieces[0]


def inner_join_batches(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
    probe_rows: Optional[int] = None,
):
    """Streaming inner join: yields one result Table per probe chunk
    instead of concatenating them — the Spark operator model (plans
    consume ``Iterator[ColumnarBatch]``), and the bounded-memory output
    path: at no point is more than one chunk's output resident beyond
    what the consumer retains, so a join whose FULL output exceeds HBM
    can still stream through a downstream aggregation.

    Same safety properties as :func:`inner_join_batched` (fault-fenced
    probe sizes, HBM-planned chunks, skew re-splitting).

    Argument validation and the HBM-budget warning fire HERE, at call
    time — not on first iteration of the returned generator — so a
    caller that builds the iterator and defers consumption still gets
    errors at the faulty call site."""
    right_on = right_on or on
    out_row_bytes = None
    if probe_rows is None:
        # size the chunk from the HBM budget (round-4 VERDICT item 7:
        # capped/batched APIs plan memory instead of fixed constants),
        # bounded by the codegen-fault fence
        from ..utils import hbm

        plan = hbm.join_plan(left, right, on, right_on)
        if not plan["fits"]:
            # the fixed resident set (both tables + build words) alone
            # exceeds the budget: no probe size can save it. Proceed at
            # minimum chunks but say so — the reserve fraction is
            # conservative, so this is a warning, not a refusal.
            import warnings

            warnings.warn(
                "join inputs exceed the HBM budget before any probe "
                f"chunk ({plan['fixed_bytes']} fixed vs "
                f"{plan['budget_bytes']} budget); expect allocator "
                "pressure. Raise SPARK_RAPIDS_TPU_HBM_BUDGET_GB if the "
                "chip really has more.",
                stacklevel=2,
            )
        probe_rows = min(FUSED_PROBE_MAX_ROWS, plan["probe_rows"])
        out_row_bytes = plan["output_row_bytes"]
    if probe_rows <= 0:
        raise ValueError(f"probe_rows must be positive, got {probe_rows}")
    # key-dtype validation is also eager (raises TypeError on mixed
    # STRING/non-STRING pairs before any work is enqueued)
    left, right = _equalize_string_key_pads(left, right, on, right_on)
    return _inner_join_batches_gen(
        left, right, on, right_on, probe_rows, out_row_bytes
    )


def _inner_join_batches_gen(
    left, right, on, right_on, probe_rows, out_row_bytes
):
    from collections import deque

    from .copying import slice_rows

    n = left.row_count
    if n == 0 or right.row_count == 0:
        return
    # two jitted stages per chunk (NOT eager op-by-op: each eager
    # dispatch pays a full host<->device round trip). The jitted
    # helpers are cached at module level keyed
    # by the key columns / capacity bucket, so compile caches hit
    # across chunks, repetitions, AND separate calls.
    on_key = tuple(on)
    ron_key = tuple(right_on)
    perm_r, sorted_words = _batched_prep_fn(ron_key)(right)
    sorted_words = tuple(sorted_words)
    probe = _chunk_ranges_fn(on_key, False)
    if out_row_bytes is None:
        from ..utils import hbm

        out_row_bytes = hbm.row_bytes(left) + hbm.row_bytes(right)
    chunk_out_budget = max(
        probe_rows * 2 * out_row_bytes, MIN_CHUNK_OUT_BYTES
    )
    spans = deque(
        (s, min(s + probe_rows, n)) for s in range(0, n, probe_rows)
    )
    while spans:
        start, stop = spans.popleft()
        chunk = slice_rows(left, start, stop)
        lo, counts, _, total_dev = probe(sorted_words, chunk)
        total = int(total_dev)
        if total == 0:
            continue
        cap = max(32, 1 << (total - 1).bit_length())
        if cap * out_row_bytes > chunk_out_budget and stop - start > 1024:
            mid = (start + stop) // 2
            spans.appendleft((mid, stop))
            spans.appendleft((start, mid))
            continue
        padded = _batched_materialize_fn(ron_key, cap)(
            perm_r, lo, counts, chunk, right
        )
        yield slice_rows(padded, 0, total)


def left_join(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
) -> Table:
    """Eager left outer equi-join (fault-fenced: chunked probe above
    ``FUSED_PROBE_MAX_ROWS`` on accelerator backends)."""
    right_on = right_on or on
    perm_r, lo, counts, _ = _match_ranges_safe(left, right, on, right_on)
    total = int(jnp.sum(jnp.maximum(counts, 1)))
    left_idx, right_idx, matched, _ = _expand(
        perm_r, lo, counts, total, left_outer=True
    )
    return _join_output(left, right, right_on, left_idx, right_idx, matched, None)


def semi_join(left, right, on, right_on=None) -> Table:
    """Rows of ``left`` with at least one match (LEFT SEMI)."""
    from .filter import filter_table
    from .. import dtype as dt

    has = membership_mask(left, right, on, right_on)
    return filter_table(left, Column(has, dt.BOOL8, None))


def anti_join(left, right, on, right_on=None) -> Table:
    """Rows of ``left`` with no match (LEFT ANTI)."""
    from .filter import filter_table
    from .. import dtype as dt

    has = membership_mask(left, right, on, right_on)
    return filter_table(left, Column(jnp.logical_not(has), dt.BOOL8, None))


# ---------------------------------------------------------------------------
# full / right outer joins (round 3: VERDICT item 7)
# ---------------------------------------------------------------------------

def _resolve_col(table: Table, c: Union[int, str]) -> int:
    if isinstance(c, str):
        if table.names is None:
            raise ValueError(f"column name {c!r} on an unnamed table")
        return table.names.index(c)
    return c


def _coalesce_key(
    lc: Column, rc: Column, left_idx, right_idx, left_ok, right_ok
) -> Column:
    """Output key column under USING semantics: ``coalesce(l.k, r.k)`` —
    left's key for pair / left-unmatched rows, right's for
    right-unmatched rows (where no left row exists)."""
    if lc.dtype != rc.dtype:
        raise TypeError(
            f"outer-join key dtypes differ: {lc.dtype} vs {rc.dtype}"
        )
    lg = gather_table(Table([lc]), left_idx).columns[0]
    rg = gather_table(Table([rc]), right_idx).columns[0]
    m = left_ok.reshape(left_ok.shape + (1,) * (lg.data.ndim - 1))
    data = jnp.where(m, lg.data, rg.data)
    lval = jnp.logical_and(compute.valid_mask(lg), left_ok)
    rval = jnp.logical_and(compute.valid_mask(rg), right_ok)
    valid = jnp.where(left_ok, lval, rval)
    lengths = None
    if lg.lengths is not None or rg.lengths is not None:
        ll = lg.lengths if lg.lengths is not None else jnp.zeros_like(right_idx)
        rl = rg.lengths if rg.lengths is not None else jnp.zeros_like(right_idx)
        lengths = jnp.where(left_ok, ll, rl)
    return Column(data, lc.dtype, valid, lengths)


def _outer_output(
    left: Table,
    right: Table,
    left_on: Sequence[Union[int, str]],
    right_on: Sequence[Union[int, str]],
    left_idx,
    right_idx,
    left_ok,
    right_ok,
) -> Table:
    """Unified outer-join materialization: key columns coalesced, left
    non-keys masked by ``left_ok``, right non-keys (minus its join keys,
    like Spark USING) masked by ``right_ok``."""
    lkeys = [_resolve_col(left, c) for c in left_on]
    rkeys = [_resolve_col(right, c) for c in right_on]
    rkey_of = dict(zip(lkeys, rkeys))
    out_cols: list[Column] = []
    out_names: list[str] = []
    lnames = (
        list(left.names)
        if left.names
        else [f"l{i}" for i in range(left.num_columns)]
    )
    for j, c in enumerate(left.columns):
        if j in rkey_of:
            out_cols.append(
                _coalesce_key(
                    c, right.columns[rkey_of[j]],
                    left_idx, right_idx, left_ok, right_ok,
                )
            )
        else:
            out_cols.append(
                gather_table(Table([c]), left_idx, left_ok).columns[0]
            )
        out_names.append(lnames[j])
    for j, c in enumerate(right.columns):
        if j in rkeys:
            continue
        out_cols.append(
            gather_table(Table([c]), right_idx, right_ok).columns[0]
        )
        out_names.append(right.names[j] if right.names else f"r{j}")
    return Table(out_cols, out_names)


def _unmatched_right(left, right, on, right_on):
    """Bool mask over right rows with NO match in left (probe reversed).
    Null/invalid right keys never match, so they are unmatched — exactly
    the rows a FULL/RIGHT OUTER join must still emit."""
    _, _, counts, _ = _match_ranges_safe(right, left, right_on, on)
    return counts == 0


def right_join(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
) -> Table:
    """Eager RIGHT OUTER equi-join: inner pairs + unmatched right rows
    with a null left side (keys coalesced from the right)."""
    right_on = right_on or on
    perm_r, lo, counts, _ = _match_ranges_safe(left, right, on, right_on)
    total_in = int(jnp.sum(counts))
    run = _unmatched_right(left, right, on, right_on)
    n_run = int(jnp.sum(run))
    left_idx, right_idx, matched, _ = _expand(
        perm_r, lo, counts, total_in, left_outer=False
    )
    run_idx = jnp.nonzero(run, size=n_run)[0].astype(jnp.int32)
    left_idx = jnp.concatenate(
        [_left_index(left_idx), jnp.zeros((n_run,), jnp.int32)]
    )
    right_idx = jnp.concatenate([right_idx, run_idx])
    left_ok = jnp.concatenate(
        [jnp.ones((total_in,), jnp.bool_), jnp.zeros((n_run,), jnp.bool_)]
    )
    right_ok = jnp.concatenate(
        [jnp.ones((total_in,), jnp.bool_), jnp.ones((n_run,), jnp.bool_)]
    )
    return _outer_output(
        left, right, on, right_on, left_idx, right_idx, left_ok, right_ok
    )


def full_join(
    left: Table,
    right: Table,
    on: Sequence[Union[int, str]],
    right_on: Optional[Sequence[Union[int, str]]] = None,
) -> Table:
    """Eager FULL OUTER equi-join: inner pairs + unmatched left rows
    (null right side) + unmatched right rows (null left side)."""
    right_on = right_on or on
    perm_r, lo, counts, _ = _match_ranges_safe(left, right, on, right_on)
    total_pairs = int(jnp.sum(jnp.maximum(counts, 1)))  # inner + left-unmatched
    run = _unmatched_right(left, right, on, right_on)
    n_run = int(jnp.sum(run))
    left_idx, right_idx, matched, _ = _expand(
        perm_r, lo, counts, total_pairs, left_outer=True
    )
    run_idx = jnp.nonzero(run, size=n_run)[0].astype(jnp.int32)
    left_idx = jnp.concatenate(
        [_left_index(left_idx), jnp.zeros((n_run,), jnp.int32)]
    )
    right_idx = jnp.concatenate([right_idx, run_idx])
    left_ok = jnp.concatenate(
        [jnp.ones((total_pairs,), jnp.bool_), jnp.zeros((n_run,), jnp.bool_)]
    )
    right_ok = jnp.concatenate(
        [matched, jnp.ones((n_run,), jnp.bool_)]
    )
    return _outer_output(
        left, right, on, right_on, left_idx, right_idx, left_ok, right_ok
    )
