"""Wire-level dispatch for the embedded native runtime.

This module is what ``libspark_rapids_tpu.so`` imports when a native
caller (JNI bridge, C program, Spark executor) initializes the embedded
JAX runtime (src/cpp/jax_runtime.cpp). It is the TPU answer to the
reference's JNI entry points dispatching into device kernels
(RowConversionJni.cpp:24-66): host bytes come in over the C ABI, columns
are built on the XLA backend, the op runs on device, and result columns
travel back as host bytes.

The wire format mirrors the reference's dtype marshaling: parallel
(type id, scale) int arrays (RowConversionJni.cpp:56-61), little-endian
fixed-width data buffers (FLOAT64 as IEEE-754 doubles, BOOL8 as one 0/1
byte per value), and per-column 0/1 validity byte vectors. Variable-width
columns use Arrow layouts: STRING and LIST travel as int32
offsets[n+1] + concatenated payload (for LIST the scale slot carries the
child type id). The row transpose itself stays fixed-width-only — the
same gate the reference enforces at row_conversion.cu:514-516.
"""

from __future__ import annotations

import json
from typing import Optional, Sequence

import jax
import numpy as np

from . import dtype as dt
from . import pipeline, plancheck, planops
from . import plan as plan_mod
from .column import Column, Table
from .utils import buckets, faults, flight, lockcheck, log, metrics, profiler, spill


def _wire_np(d: dt.DType) -> np.dtype:
    """Host wire numpy dtype of a fixed-width column."""
    if not d.is_fixed_width:
        raise TypeError(f"wire format: fixed-width types only, got {d}")
    if d.id == dt.TypeId.FLOAT64:
        # device storage is the uint64 bit pattern; the wire carries
        # doubles (same bytes, different view)
        return np.dtype(np.float64)
    return np.dtype(d.storage_dtype)


def _padded_from_offsets(
    data: bytes, num_rows: int, child_np: np.dtype, label: str,
    pad_rows: Optional[int] = None,
):
    """Arrow offsets+payload wire buffer -> ((n, pad) matrix, lengths).

    Shared by the STRING and LIST branches: int32 offsets[num_rows+1]
    followed by the concatenated payload values, decoded into the
    padded-matrix device layout. Offsets are untrusted wire input and
    validated up front: a corrupt buffer with negative or non-monotonic
    offsets would otherwise yield negative lengths and a silently wrong
    row mask (``arange < lens`` is all-False for a negative length, so
    payload bytes would land in the WRONG rows without any error).

    ``pad_rows`` sizes the matrix's ROW dimension directly at the shape
    bucket: the old decode built an (n, pad) matrix and then re-padded
    it to the bucket — a second multi-MB alloc + copy per column on the
    wire hot path. Constant-width payloads (every length == pad, the
    dictionary-code/fixed-id shape) take a bulk-reshape fast path that
    skips the row mask entirely."""
    if len(data) < 4 * (num_rows + 1):
        raise ValueError(
            f"{label} wire buffer holds {len(data)} bytes, "
            f"{4 * (num_rows + 1)} needed for {num_rows + 1} offsets"
        )
    offs = np.frombuffer(data, np.int32, num_rows + 1)
    lens = np.diff(offs).astype(np.int32)
    if int(offs[0]) != 0 or (num_rows and bool((lens < 0).any())):
        raise ValueError(
            f"{label} wire offsets corrupt: must start at 0 and be "
            f"non-decreasing (first={int(offs[0])}, "
            f"min diff={int(lens.min()) if num_rows else 0})"
        )
    need = 4 * (num_rows + 1) + child_np.itemsize * int(offs[-1])
    if len(data) < need:
        raise ValueError(
            f"{label} wire buffer holds {len(data)} bytes, offsets "
            f"require {need}"
        )
    flat = np.frombuffer(
        data, child_np, count=int(offs[-1]), offset=4 * (num_rows + 1)
    )
    pad = max(int(lens.max()) if num_rows else 1, 1)
    rows = max(num_rows, pad_rows or 0)
    mat = np.zeros((rows, pad), child_np)
    if num_rows and int(offs[-1]) == num_rows * pad:
        # constant-width payload: the flat buffer IS the row-major
        # matrix — one bulk copy instead of mask build + fancy index
        mat[:num_rows] = flat.reshape(num_rows, pad)
    else:
        mask = np.arange(pad)[None, :] < lens[:, None]
        mat[:num_rows][mask] = flat
    return mat, lens


class _SerializePass:
    """Scratch state for ONE wire-serialize pass over a table.

    The STRING/LIST branch needs an ``(n, pad)`` boolean row mask per
    column; a multi-column table re-derives byte-identical ``arange``
    rows and re-allocates the mask buffer for every column of the same
    shape. One pass object caches the ``arange`` per pad width and
    reuses ONE mask buffer per ``(n, pad)`` shape (refilled in place —
    each column's mask is consumed before the next is built). Saved
    allocations are counted in ``wire.serialize.saved_bytes``."""

    __slots__ = ("_aranges", "_masks")

    def __init__(self):
        self._aranges = {}
        self._masks = {}

    def arange(self, pad: int) -> np.ndarray:
        a = self._aranges.get(pad)
        if a is None:
            a = self._aranges[pad] = np.arange(pad)
        return a

    def row_mask(self, lens: np.ndarray, pad: int) -> np.ndarray:
        buf = self._masks.get((lens.shape[0], pad))
        if buf is None:
            buf = self._masks[(lens.shape[0], pad)] = np.empty(
                (lens.shape[0], pad), np.bool_
            )
        else:
            metrics.bytes_add("wire.serialize.saved_bytes", buf.nbytes)
        np.less(self.arange(pad)[None, :], lens[:, None], out=buf)
        return buf


def _start_transfers(t: Table) -> None:
    """Start the transfer of every device leaf of ``t``, so that they
    cross together and a read waits for one that is already under way
    (a host-backed leaf has none to start). A pure read: the table's
    leaves stay as they are."""
    for c in t.columns:
        for leaf in (c.data, c.validity, c.lengths):
            if leaf is not None and not isinstance(leaf, np.ndarray):
                leaf.copy_to_host_async()


def _padded_to_offsets(
    mat: np.ndarray, lens: np.ndarray, ctx: Optional[_SerializePass] = None
) -> memoryview:
    """(n, pad) matrix + lengths -> the offsets+payload wire buffer.

    ONE buffer is made for both and each is written into it once: the
    cumsum into its head, the payload, row-major from whatever layout
    ``mat`` has, into its tail."""
    n, pad = lens.shape[0], mat.shape[1]
    total = int(lens.sum(dtype=np.int64))
    head = 4 * (n + 1)
    buf = np.empty(head + total * mat.dtype.itemsize, np.uint8)
    offs = buf[:head].view(np.int32)
    offs[0] = 0
    np.cumsum(lens, out=offs[1:])
    payload = buf[head:].view(mat.dtype)
    if n and total == n * pad:
        # constant-width rows (every length == pad): the matrix IS the
        # payload — skip the row mask + fancy gather outright. Counted
        # as saved serialize bytes: the mask buffer was never built.
        if ctx is not None:
            metrics.bytes_add("wire.serialize.saved_bytes", n * pad)
        np.copyto(payload.reshape(n, pad), mat)
    else:
        if ctx is not None:
            mask = ctx.row_mask(lens, pad)
        else:
            mask = np.arange(pad)[None, :] < lens[:, None]
        payload[:] = mat[mask]
    return buf.data


def _wire_validity(valid: Optional[bytes], num_rows: int):
    if valid is None:
        return None
    return np.frombuffer(valid, np.uint8, num_rows).astype(np.bool_)


def _pad_host(arr: np.ndarray, total: Optional[int]) -> np.ndarray:
    """Zero-pad a host buffer's row dimension to ``total`` rows BEFORE
    upload — padding to the shape bucket on the host side costs no XLA
    compile and makes every upload within a bucket the same shape."""
    if total is None or arr.shape[0] == total:
        return arr
    out = np.zeros((total,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class _HostCol:
    """One wire column decoded to HOST storage buffers, not yet
    uploaded — the staging unit of the per-table batched transfer
    (``_upload_host_columns``). ``data`` is already in the DEVICE
    storage dtype (FLOAT64 carried as its uint64 bit pattern, the
    encode_storage rule) so the upload is a pure copy."""

    __slots__ = ("dtype", "data", "validity", "lengths")

    def __init__(self, dtype, data, validity=None, lengths=None):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.lengths = lengths


def _host_column_from_wire(
    type_id: int, scale: int, data: Optional[bytes],
    valid: Optional[bytes], num_rows: int,
    pad_to: Optional[int] = None,
) -> _HostCol:
    """Decode one wire column to host numpy buffers (no device touch)."""
    if metrics.enabled():
        metrics.bytes_add(
            "wire.bytes_in",
            (len(data) if data is not None else 0)
            + (len(valid) if valid is not None else 0),
        )
        metrics.counter_add("wire.columns_in")
    if dt.TypeId(type_id) == dt.TypeId.LIST:
        # LIST wire convention: the scale slot carries the CHILD type id
        # (scale is meaningless for LIST); payload per _padded_from_offsets.
        child = dt.DType(dt.TypeId(scale))
        mat, lens = _padded_from_offsets(
            data, num_rows, np.dtype(child.storage_dtype), "LIST",
            pad_rows=pad_to,
        )
        v = _wire_validity(valid, num_rows)
        return _HostCol(
            dt.DType(dt.TypeId.LIST),
            mat,
            None if v is None else _pad_host(v, pad_to),
            _pad_host(lens, pad_to),
        )
    if dt.TypeId(type_id) == dt.TypeId.STRING:
        # STRING wire convention (the Arrow string layout cudf's JNI
        # marshals): offsets + concatenated UTF-8 bytes.
        mat, lens = _padded_from_offsets(
            data, num_rows, np.dtype(np.uint8), "STRING", pad_rows=pad_to,
        )
        v = _wire_validity(valid, num_rows)
        return _HostCol(
            dt.STRING,
            mat,
            None if v is None else _pad_host(v, pad_to),
            _pad_host(lens, pad_to),
        )
    d = dt.DType(dt.TypeId(type_id), scale)
    if d.id == dt.TypeId.DECIMAL128:
        # 16 little-endian bytes/value on the wire -> (n, 2) u64 limbs
        arr = np.frombuffer(
            data, dtype=np.uint64, count=2 * num_rows
        ).reshape(num_rows, 2)
    else:
        arr = np.frombuffer(data, dtype=_wire_np(d), count=num_rows)
    v = (
        None
        if valid is None
        else np.frombuffer(valid, dtype=np.uint8, count=num_rows).astype(
            np.bool_
        )
    )
    arr = _pad_host(arr, pad_to)
    # the one FLOAT64 bit-view rule, shared with encode_storage
    from .column import storage_host_view

    arr = storage_host_view(arr, d)
    return _HostCol(d, arr, None if v is None else _pad_host(v, pad_to))


def _upload_host_columns(hcols: Sequence[_HostCol]) -> list:
    """Upload a whole table's host buffers in ONE batched transfer.

    ``jax.device_put`` on the flat leaf list dispatches every buffer
    together (the reference uploads a ColumnarBatch as one contiguous
    HtoD copy, not one cudaMemcpy per column); the per-column path cost
    one transfer per data/validity/lengths buffer. Transfers saved by
    batching are counted in ``wire.upload.batched``."""
    import jax

    leaves = []
    for h in hcols:
        leaves.append(h.data)
        if h.validity is not None:
            leaves.append(h.validity)
        if h.lengths is not None:
            leaves.append(h.lengths)
    dev = jax.device_put(leaves) if leaves else []
    if metrics.enabled() and len(leaves) > 1:
        metrics.counter_add("wire.upload.batched", len(leaves) - 1)
    it = iter(dev)
    cols = []
    for h in hcols:
        d = next(it)
        if d.dtype != h.data.dtype:
            # x64 disabled: a silent int64->int32 downgrade would
            # corrupt values AND misreport the type id on download
            # (the shared encode_storage guard, batched-upload flavor)
            from .column import x64_downgrade_error

            raise x64_downgrade_error(
                d.dtype, h.data.dtype,
                "LIST children" if h.dtype.id == dt.TypeId.LIST
                else "types",
            )
        v = next(it) if h.validity is not None else None
        lens = next(it) if h.lengths is not None else None
        cols.append(Column(d, h.dtype, v, lens))
    return cols


def _column_from_wire(
    type_id: int, scale: int, data: Optional[bytes],
    valid: Optional[bytes], num_rows: int,
    pad_to: Optional[int] = None,
) -> Column:
    return _upload_host_columns(
        [_host_column_from_wire(type_id, scale, data, valid, num_rows,
                                pad_to)]
    )[0]


def _column_to_wire(
    c: Column, rows: Optional[int] = None,
    ctx: Optional[_SerializePass] = None,
):
    """(type_id, scale, data, valid | None), each buffer ``bytes`` or a
    byte ``memoryview`` (``_as_wire``).

    LIST columns use the convention documented in _column_from_wire:
    scale = child type id, data = int32 offsets then child values.

    ``rows`` slices a shape-bucket-padded column back to its logical
    row count on the HOST side (after the device fetch) — the padding
    never reaches the wire and the slice costs no XLA compile.
    ``ctx`` is the per-serialize-pass scratch (mask-buffer reuse).
    """
    out = _column_to_wire_impl(c, rows, ctx)
    if metrics.enabled():
        nbytes = _wire_column_bytes(out[2], out[3])
        metrics.bytes_add("wire.bytes_out", nbytes)
        metrics.counter_add("wire.columns_out")
        if _host_backed(c):
            metrics.bytes_add("wire.bytes_out.host", nbytes)
            metrics.counter_add("wire.columns_out.host")
        view = _view_bytes(out[0], out[2], out[3])
        if view:
            metrics.bytes_add("wire.bytes_out.view", view)
            metrics.counter_add("wire.columns_out.view")
    return out


def _wire_column_bytes(data, valid) -> int:
    return len(data) + (len(valid) if valid is not None else 0)


_HOST_WRITTEN = (int(dt.TypeId.STRING), int(dt.TypeId.LIST))


def _view_bytes(type_id, data, valid) -> int:
    """Of one wire column's bytes, those no host copy touched: they
    reached the frame as a view of the array they arrived in
    (``_as_wire``). A STRING's or LIST's data is a view too, but of a
    buffer the host wrote (``_padded_to_offsets``), and counts as the
    copy it is."""
    n = 0
    if isinstance(data, memoryview) and type_id not in _HOST_WRITTEN:
        n += len(data)
    if isinstance(valid, memoryview):
        n += len(valid)
    return n


def wire_view_bytes(wire) -> int:
    """``_view_bytes`` over a wire 5-tuple's columns: what the
    session's ``replies_out`` counts beside the reply's bytes."""
    type_ids, _, datas, valids, _ = wire
    return sum(map(_view_bytes, type_ids, datas, valids))


def _host_backed(c: Column) -> bool:
    """The column's leaves are host memory already (a mesh stage's
    result, ``planmesh._gather_prefix``): serialising it transfers
    nothing."""
    return isinstance(c.data, np.ndarray)


def _reply_host_bytes(t: Table, wire) -> tuple:
    """``(bytes, bytes serialised from host-backed columns)`` of ``t``'s
    wire 5-tuple ``wire``."""
    total = host = 0
    for c, d, v in zip(t.columns, wire[2], wire[3]):
        n = _wire_column_bytes(d, v)
        total += n
        if _host_backed(c):
            host += n
    return total, host


def _host_rows(arr: np.ndarray, rows: Optional[int]) -> np.ndarray:
    return arr if rows is None else arr[:rows]


def _own_memory(host: np.ndarray) -> bool:
    """``host``'s bytes lie in memory numpy allocated for an array (its
    own, or that of the array it is a view of) and nobody else's: not a
    device buffer ``np.asarray`` aliased (the CPU backend), not a
    caller's ``bytearray``."""
    while isinstance(host.base, np.ndarray):
        host = host.base
    return host.base is None and host.flags.owndata


def _as_wire(host: np.ndarray):
    """``host``'s bytes in C order, as the frame takes them: a byte view
    of the array where it lies that way in memory of its own (the view
    keeps it alive, and nobody writes to it after this read); ``bytes``,
    one copy, of anything else. On the TPU ``np.asarray`` of a device
    leaf is such an array, and outlives the device buffer it was read
    from (``table_free``, ``table_reclaim``, a donating plan)."""
    if host.flags.c_contiguous and _own_memory(host):
        return host.reshape(-1).view(np.uint8).data
    return host.tobytes()


def _column_to_wire_impl(
    c: Column, rows: Optional[int] = None,
    ctx: Optional[_SerializePass] = None,
):
    valid = None
    if c.validity is not None:
        valid = _host_rows(np.asarray(c.validity), rows)
        if valid.dtype != np.bool_:
            valid = valid.astype(np.uint8)
        valid = _as_wire(valid)
    if c.dtype.id in (dt.TypeId.STRING, dt.TypeId.LIST):
        is_list = c.dtype.id == dt.TypeId.LIST
        return (
            int(c.dtype.id),
            int(c.list_child_dtype.id) if is_list else 0,
            _padded_to_offsets(
                _host_rows(np.asarray(c.data), rows),
                _host_rows(np.asarray(c.lengths), rows)
                .astype(np.int32, copy=False),
                ctx,
            ),
            valid,
        )
    return (
        int(c.dtype.id.value),
        int(c.dtype.scale),
        _as_wire(_host_rows(np.asarray(c.data), rows)),
        valid,
    )


def _table_from_wire(
    type_ids: Sequence[int],
    scales: Sequence[int],
    datas: Sequence[Optional[bytes]],
    valids: Sequence[Optional[bytes]],
    num_rows: int,
    pad_to: Optional[int],
) -> Table:
    """One wire-deserialize pass -> a (possibly host-padded) Table.
    Host decode per column, then the whole table's buffers cross to the
    device as ONE batched ``jax.device_put`` pytree transfer. A wire
    decode is pure (the caller's bytes are never consumed), so the
    ``serde`` fault site retries transient failures here freely."""

    def attempt():
        faults.inject("serde")
        return _table_from_wire_impl(
            type_ids, scales, datas, valids, num_rows, pad_to
        )

    return faults.run_with_retry(attempt, "wire.in")


def _table_from_wire_impl(
    type_ids, scales, datas, valids, num_rows, pad_to
) -> Table:
    prof = profiler.session_active()
    nbytes = (
        sum(len(d) for d in datas if d is not None)
        if (prof or flight.enabled()) else 0
    )
    if flight.enabled():
        flight.record("I", "wire.in", nbytes)
    t0 = _time.perf_counter() if prof else 0.0
    with metrics.span("wire.deserialize"):
        cols = _upload_host_columns([
            _host_column_from_wire(t, s, d, v, num_rows, pad_to=pad_to)
            for t, s, d, v in zip(type_ids, scales, datas, valids)
        ])
    if prof:
        profiler.note_serde("in", _time.perf_counter() - t0, nbytes)
    tbl = Table(cols, logical_rows=num_rows if pad_to is not None else None)
    if pad_to is not None:
        buckets.note_padded(tbl)
    return tbl


def _table_to_wire(t: Table):
    """One wire-serialize pass -> the 5-tuple every wire entry returns
    (shape-bucket padding sliced away host-side; one shared
    ``_SerializePass`` scratch across the table's columns). Each buffer
    is ``bytes`` or a byte view of host memory the view keeps alive
    (``_as_wire``): the daemon's frames take either as it is, the C
    ABI's entries answer through ``_wire_bytes``. Pure reads of device
    buffers, so the ``serde`` fault site retries here too."""

    def attempt():
        faults.inject("serde")
        return _table_to_wire_impl(t)

    return faults.run_with_retry(attempt, "wire.out")


def _wire_bytes(wire):
    """``wire`` with every buffer as ``bytes``: what the native runtime
    takes (``src/cpp/jax_runtime.cpp`` holds ``PyBytes_Check`` on each
    and copies it into a buffer of its own)."""
    type_ids, scales, datas, valids, rows = wire

    def own(bufs):
        # bytes(b) of a bytes is that object: nothing is copied twice
        return [None if b is None else bytes(b) for b in bufs]

    return type_ids, scales, own(datas), own(valids), rows


def _table_to_wire_impl(t: Table):
    out_t, out_s, out_d, out_v = [], [], [], []
    ctx = _SerializePass()
    prof = profiler.session_active()
    t0 = _time.perf_counter() if prof else 0.0
    with metrics.span("wire.serialize"):
        # the wait for the device apart from the host's copy: the first
        # read below would wait all the same, under the copy's name.
        # Only a live span waits up front; with every plane off the
        # transfers wait for the device themselves, as the copies did
        wait = metrics.span("wire.serialize.wait")
        if wait is not metrics.NULL_SPAN:
            with wait:
                jax.block_until_ready([
                    b for c in t.columns
                    for b in (c.data, c.validity, c.lengths)
                    if b is not None
                ])
        with metrics.span("wire.serialize.copy"):
            _start_transfers(t)
            for c in t.columns:
                ti, s, d, v = _column_to_wire(c, t.logical_rows, ctx)
                out_t.append(ti)
                out_s.append(s)
                out_d.append(d)
                out_v.append(v)
    if prof or flight.enabled():
        nbytes = sum(len(d) for d in out_d if d is not None)
        if flight.enabled():
            flight.record("I", "wire.out", nbytes)
        if prof:
            profiler.note_serde(
                "out", _time.perf_counter() - t0, nbytes
            )
    return out_t, out_s, out_d, out_v, int(t.logical_row_count)


def table_op_wire(
    op_json: str,
    type_ids: Sequence[int],
    scales: Sequence[int],
    datas: Sequence[Optional[bytes]],
    valids: Sequence[Optional[bytes]],
    num_rows: int,
):
    """C-ABI entry: bytes in, bytes out.

    Returns (out_type_ids, out_scales, out_datas, out_valids, out_rows).
    """
    op = json.loads(op_json)
    pad_to = None
    # pad only when the op can actually take the bucketed path — a
    # non-bucketable op would pay the padded upload AND a device unpad
    # slice for nothing
    if buckets.enabled() and planops.op_bucketable(op):
        pad_to = buckets.bucket_for(num_rows)
    tbl = _table_from_wire(
        type_ids, scales, datas, valids, num_rows, pad_to
    )
    result = planops.dispatch(op, tbl)
    return _wire_bytes(_table_to_wire(result))


def _plan_pad_to(ops, num_rows: int) -> Optional[int]:
    """Host-side pad target for a plan's wire upload: pad only when the
    FIRST segment can consume the padding (a fused segment, or a 1-op
    segment with a bucketed runner) — the table_op_wire gate applied at
    segment granularity, so a plan opening with e.g. a lone slice
    doesn't pay a padded upload just to unpad on the exact path;
    malformed entries fall through to run_plan's loud validation."""
    if not (buckets.enabled() and ops and isinstance(ops[0], dict)):
        return None
    segs = plan_mod.segment_plan(ops)
    if segs and (
        segs[0][0] == "fused" or planops.op_bucketable(segs[0][1][0])
    ):
        return buckets.bucket_for(num_rows)
    return None


def table_plan_wire(
    plan_json: str,
    type_ids: Sequence[int],
    scales: Sequence[int],
    datas: Sequence[Optional[bytes]],
    valids: Sequence[Optional[bytes]],
    num_rows: int,
):
    """C-ABI plan entry: ``plan_json`` is a JSON LIST of ops executed
    as a fused plan (plan.py) over ONE wire table — upload once, every
    fusable run costs one executable launch, download once. Returns the
    same 5-tuple as ``table_op_wire``. The uploaded table is consumed
    by construction (nothing else holds a wire table), so the first
    fused segment donates its buffers — the chain updates HBM in place
    instead of doubling peak (``hbm.donated_bytes``)."""
    ops = json.loads(plan_json)
    if not isinstance(ops, list):
        raise TypeError("table_plan_wire: plan must be a JSON list of ops")
    # static analysis BEFORE the upload: a plan that cannot run costs
    # zero wire bytes, zero compiles (plancheck.PlanCheckError names the
    # op index + reason and subclasses ValueError)
    schema = plancheck.schema_from_wire(type_ids, scales)
    report = plancheck.check_plan(ops, schema=schema, rows=int(num_rows))
    pad_to = _plan_pad_to(ops, num_rows)
    with profiler.maybe_session(
        ops, label="plan_wire", schema=schema, bucket=pad_to,
        static=report,
    ):
        tbl = _table_from_wire(
            type_ids, scales, datas, valids, num_rows, pad_to,
        )
        result = plan_mod.run_plan(ops, tbl, donate_input=True)
        return _wire_bytes(_table_to_wire(result))


def table_stream_wire(plan_json: str, batches: Sequence) -> list:
    """Streaming C-ABI entry: drive a whole plan-per-batch stream
    through the pipelined dispatch plane from ONE call.

    ``batches`` is a sequence of ``(type_ids, scales, datas, valids,
    num_rows)`` wire tuples; each runs the same ``plan_json`` op list
    and the returned list carries one ``table_op_wire``-shaped 5-tuple
    per batch (every buffer ``bytes``), in input order. With ``SPARK_RAPIDS_TPU_PIPELINE`` on,
    batch N+1's wire decode and batch N-1's wire encode run on
    background workers while batch N's fused-plan executable runs on
    the calling thread (pipeline.run_stream); with the pipeline off
    this is exactly a loop of ``table_plan_wire`` — byte-identical
    results and error surfacing either way. Each batch's decoded table
    is consumed by its plan run, so fused chains donate
    (``hbm.donated_bytes``)."""
    ops = json.loads(plan_json)
    if not isinstance(ops, list):
        raise TypeError(
            "table_stream_wire: plan must be a JSON list of ops"
        )
    # static analysis against the first batch's wire schema before any
    # batch decodes or the pipeline spins up; an empty stream still gets
    # the structural walk
    batches = list(batches)
    schema = None
    bucket = None
    if batches:
        first = batches[0]
        schema = plancheck.schema_from_wire(first[0], first[1])
        report = plancheck.check_plan(
            ops, schema=schema, rows=int(first[4]),
        )
        bucket = _plan_pad_to(ops, int(first[4]))
    else:
        report = plancheck.check_plan(ops)

    def decode(batch):
        type_ids, scales, datas, valids, num_rows = batch
        return _table_from_wire(
            type_ids, scales, datas, valids, num_rows,
            _plan_pad_to(ops, num_rows),
        )

    def compute(tbl):
        return plan_mod.run_plan(ops, tbl, donate_input=True)

    with profiler.maybe_session(
        ops, label="stream", batches=len(batches), schema=schema,
        bucket=bucket, static=report,
    ):
        with metrics.span(
            "stream", batches=len(batches), depth=pipeline.depth()
        ):
            return pipeline.run_stream(
                batches, decode, compute,
                lambda t: _wire_bytes(_table_to_wire(t)),
            )


def platform() -> str:
    """Active XLA backend platform name."""
    import jax

    return jax.devices()[0].platform


# ---------------------------------------------------------------------------
# Device-resident table handles (round-3 VERDICT item 4)
#
# The reference passes jlong pointers to DEVICE-resident cudf tables
# between JNI calls with no host copy in between
# (RowConversionJni.cpp:31,54). The wire path above copies host->device
# per op; these functions give native callers the same chaining
# capability: a table id maps to a Table whose buffers stay on the XLA
# backend, ops consume and produce ids, and bytes only cross the
# boundary at upload/download.
# ---------------------------------------------------------------------------

import atexit
import itertools
import threading
import time as _time

_RESIDENT: dict = {}
# table id -> allocation provenance (span stack, rows, timestamp): what
# the exit-time leak report prints for every handle still live — the
# RMM leak report's "where was this allocated" role. Populated only
# when a telemetry plane is on (metrics/flight/REFCOUNT_DEBUG), so the
# shipped-disabled path stays two dict ops.
_RESIDENT_META: dict = {}
# Lock + atomic counter: Spark executors call through the JNI bridge
# from many threads (the GilGuard path), and the GIL can switch between
# a read-increment pair — an unsynchronized counter could hand two
# threads the same table id. RLock because the SIGTERM-handler flush
# path reaches leak_report() (a flight-dump exit section) on the main
# thread and must not self-deadlock mid-_resident_put. Tracked: rank 0
# of the sanctioned registry->session->scheduler->spill order.
_RESIDENT_LOCK = lockcheck.make_rlock("registry.resident")
_NEXT_TABLE_ID = itertools.count(1)


def _provenance_on() -> bool:
    from .utils import config

    return (
        metrics.enabled()
        or flight.enabled()
        or bool(config.get_flag("REFCOUNT_DEBUG"))
    )


def _unknown_id_error(table_id, live: int) -> KeyError:
    """The labeled miss every resident entry raises: names the id AND
    the live count so a use-after-free reads as one (a bare dict miss
    cost a round-6 debugging session distinguishing "never uploaded"
    from "double freed")."""
    return KeyError(
        f"unknown or already-freed device table id {int(table_id)} "
        f"({live} table(s) live)"
    )


def _resident_peek(table_id: int):
    """Registry entry for ``table_id`` WITHOUT resolving a pending: a
    Table, or a ``pipeline.Pending`` still computing. A SPILLED entry
    (utils/spill.py) transparently repages back to the device here —
    access is what promotes a cold table. Raises the labeled KeyError
    on a miss."""
    with _RESIDENT_LOCK:
        t = _RESIDENT.get(int(table_id))
        live = len(_RESIDENT)
        if isinstance(t, spill.SpilledTable):
            t = spill.repage_locked(int(table_id))
    if t is None:
        raise _unknown_id_error(table_id, live)
    spill.flush_events()
    spill.touch(int(table_id))
    return t


def _resident_get(table_id: int) -> Table:
    """Resolved Table for ``table_id`` — THE blocking point of the
    pipelined plane: a pending entry is waited for here, with any
    worker error replayed synchronously so the originating op's own
    exception surfaces (pipeline.Pending.resolve)."""
    t = _resident_peek(table_id)
    if isinstance(t, pipeline.Pending):
        t = t.resolve()
        with _RESIDENT_LOCK:
            # swap the settled Table in so later gets skip the handle
            # (unless the id was freed while we waited)
            if int(table_id) in _RESIDENT:
                _RESIDENT[int(table_id)] = t
        spill.note_put(int(table_id), t)
    metrics.counter_add("resident.get")
    return t


def _resident_put(t) -> int:
    """Register a Table (or a ``pipeline.Pending`` still computing it)
    and return its id. Pending entries count as live — backpressure and
    the leak report both see in-flight results."""
    tid = next(_NEXT_TABLE_ID)
    is_pending = isinstance(t, pipeline.Pending)
    rows = None if is_pending else int(t.logical_row_count)
    meta = None
    if _provenance_on():
        meta = {
            "rows": rows,
            "columns": None if is_pending else len(t.columns),
            "allocated_under": list(metrics.span_stack()),
            "age_anchor_ns": _time.perf_counter_ns(),
        }
        if is_pending:
            meta["pending"] = t.label
        sid = profiler.current_session_id()
        if sid is not None:
            # which profiled plan run allocated this table: the leak
            # report names the session, the session report the leak
            meta["session"] = sid
    with _RESIDENT_LOCK:
        _RESIDENT[tid] = t
        if meta is not None:
            _RESIDENT_META[tid] = meta
        live = len(_RESIDENT)
    log.log("DEBUG", "handles", "resident_put", table_id=tid,
            rows=rows, live=live)
    # resident.live's high-water mark is the leak-report analog: a chain
    # that frees what it allocates returns to the pre-chain value while
    # high_water records the peak resident set
    metrics.counter_add("resident.put")
    metrics.gauge_set("resident.live", live)
    if flight.enabled():
        flight.record("C", "resident.live", live)
    if not is_pending:
        # spill tracking + proactive pressure: a put that carries the
        # device tier past the HBM budget evicts the coldest entries
        spill.note_put(tid, t)
    return tid


def table_upload_wire(
    type_ids: Sequence[int],
    scales: Sequence[int],
    datas: Sequence[Optional[bytes]],
    valids: Sequence[Optional[bytes]],
    num_rows: int,
) -> int:
    """Host bytes -> device-resident table; returns its id. With shape
    bucketing on, the resident buffers are padded to the row-count
    bucket (host-side, before upload) and the table carries its logical
    row count — a chain of bucketed ops then reuses one compiled
    executable per bucket with no repadding."""
    pad_to = buckets.bucket_for(num_rows) if buckets.enabled() else None
    return _resident_put(
        _table_from_wire(type_ids, scales, datas, valids, num_rows, pad_to)
    )


# table id -> in-flight pipelined ops READING that id (pruned as they
# settle). A donate-consume of an id must terminally settle these
# before its executable deletes the buffers: without the barrier,
# op1=[A] then op2=[A, donate] on two workers could delete A's device
# arrays out from under op1's running dispatch (or its later replay) —
# an error the synchronous ordering (op1 completes before op2 starts)
# can never produce.
_RESIDENT_READERS: dict = {}


def _capture_inputs(
    table_ids: Sequence[int], donate: bool, reader=None,
    pin: bool = False,
) -> tuple:
    """Atomically snapshot the input entries at CALL time (Tables or
    Pendings) -> ``(inputs, donate_barrier)``.

    The capture is what makes the async chain pattern safe: a caller
    may ``table_free`` an input right after enqueueing the op that
    consumes it — the op holds its own reference, exactly as if it had
    completed before the free (the synchronous ordering). Unknown ids
    raise the labeled KeyError synchronously (all ids validated BEFORE
    the donated input is consumed, so a bad rest id leaves it intact).

    One lock acquisition covers validation, the donate-consume, the
    barrier snapshot AND registering ``reader`` (the op's own not-yet-
    enqueued Pending) against the ids it captured: a concurrent
    donate-consume of the same id therefore either sees this reader in
    its barrier or ordered itself first (in which case THIS capture
    fails with the labeled KeyError) — there is no window where a
    reader runs unprotected.

    Spilled inputs repage inside the same lock hold, so the captured
    objects are always device Tables (or Pendings). ``pin=True``
    additionally pins the non-donated ids against eviction atomically
    with the capture — the SYNCHRONOUS dispatch paths use it (no
    reader Pending exists there to make the eviction check see them);
    the caller must ``spill.unpin_ids`` the same ids when done."""
    ids = [int(t) for t in table_ids]
    took = False
    with _RESIDENT_LOCK:
        live = len(_RESIDENT)
        for t in ids:
            if t not in _RESIDENT:
                raise _unknown_id_error(t, live)
        objs = []
        for t in ids:
            o = _RESIDENT[t]
            if isinstance(o, spill.SpilledTable):
                o = spill.repage_locked(t)
            objs.append(o)
        barrier = []
        if donate:
            _RESIDENT.pop(ids[0])
            _RESIDENT_META.pop(ids[0], None)
            spill.note_free(ids[0])
            barrier = [
                p for p in _RESIDENT_READERS.pop(ids[0], ())
                if not p.done()
            ]
            live = len(_RESIDENT)
            took = True
        if reader is not None:
            for t in (ids[1:] if donate else ids):
                lst = _RESIDENT_READERS.setdefault(t, [])
                lst[:] = [p for p in lst if not p.done()]
                lst.append(reader)
        if pin:
            spill.pin_ids(ids[1:] if donate else ids)
        for t in (ids[1:] if donate else ids):
            spill.touch(t)
    spill.flush_events()
    metrics.counter_add("resident.get", len(ids))
    if took:
        log.log("DEBUG", "handles", "resident_take", table_id=ids[0],
                live=live)
        metrics.counter_add("resident.free")
        metrics.gauge_set("resident.live", live)
        if flight.enabled():
            flight.record("C", "resident.live", live)
    return objs, barrier


def _run_resident_op(
    op: dict, inputs: list, donate: bool, name: str, barrier=(),
):
    """The shared (sync or worker-side) body of ``table_op_resident``:
    resolve pending inputs, dispatch — through the donated single-op
    executable when the input was consumed — and return the result.
    ``barrier`` holds still-running readers of the donated input; they
    must be terminally settled (later replays included) before the
    donated executable may delete its buffers."""
    tables = pipeline.materialize_inputs(inputs)
    out = None
    if donate:
        for p in barrier:
            p.settle_terminally()
        out = plan_mod.run_donated(op, tables[0], name)
    if out is None:
        out = planops.dispatch(op, tables[0], tables[1:])
    return out


def table_op_resident(
    op_json: str, table_ids: Sequence[int], donate: bool = False
) -> int:
    """Run one op over resident tables; the result STAYS resident.

    No host transfer happens here — chaining filter -> join -> groupby
    costs upload + download once, not per op.

    ``donate=True`` declares ``table_ids[0]`` CONSUMED: the id is freed
    now (equivalent to op + table_free, but the op may then donate the
    input's HBM buffers to its executable and update them in place —
    ``hbm.donated_bytes``). The caller must not use the id again.

    With ``SPARK_RAPIDS_TPU_PIPELINE`` on this enqueues and returns the
    result id immediately; ``table_download_wire``/``table_num_rows``
    are the blocking points, and any worker error is replayed
    synchronously there so the op's own exception surfaces unchanged.
    """
    if not table_ids:
        raise ValueError("table_op_resident needs at least one input")
    op = json.loads(op_json)
    name = str(op.get("op", "?")) if isinstance(op, dict) else "?"
    if pipeline.enabled():
        # donated work is at-most-once once its own dispatch starts
        # (the input may be consumed by a partial run): the worker's
        # post-consumption error is authoritative; input-materialize
        # failures stay replayable (pipeline.DependencyFailed). The
        # Pending is built FIRST so _capture_inputs can register it as
        # a reader atomically with the capture; the captured state
        # lands in `cell` before the enqueue makes the work runnable.
        cell: dict = {}

        def work():
            return _run_resident_op(
                op, cell["inputs"], donate, name, cell["barrier"]
            )

        pending = pipeline.Pending(
            work, "op." + name, replayable=not donate
        )
        cell["inputs"], cell["barrier"] = _capture_inputs(
            table_ids, donate, reader=pending
        )
        return _resident_put(pipeline.enqueue(pending))
    # synchronous path: pin the surviving inputs for the dispatch (no
    # reader Pending exists here for the eviction check to see)
    inputs, barrier = _capture_inputs(table_ids, donate, pin=True)
    try:
        return _resident_put(_run_resident_op(op, inputs, donate, name,
                                              barrier))
    finally:
        spill.unpin_ids(table_ids[1:] if donate else table_ids)


def _static_check_resident_plan(ops, table_ids: Sequence[int]):
    """Plan-time analysis for the resident entry: schemas come from the
    registry (a peek — no Pending resolution, so an in-flight input
    degrades the walk to structural validation instead of blocking the
    enqueue). Raises plancheck.PlanCheckError before any input capture,
    pin, or pipeline enqueue. Returns ``(report, head_schema)`` so the
    caller can key the profile session's plan-stats record."""

    def settled(tid):
        t = _resident_peek(int(tid))
        return None if isinstance(t, pipeline.Pending) else t

    head = settled(table_ids[0])
    rest = []
    for tid in table_ids[1:]:
        t = settled(tid)
        rest.append(
            (plancheck.schema_of_table(t), int(t.logical_row_count))
            if t is not None
            else (None, None)
        )
    head_schema = (
        plancheck.schema_of_table(head) if head is not None else None
    )
    report = plancheck.check_plan(
        ops,
        schema=head_schema,
        rows=int(head.logical_row_count) if head is not None else None,
        rest=rest,
        names=head.names if head is not None else None,
    )
    return report, head_schema


def table_plan_resident(
    plan_json: str, table_ids: Sequence[int], donate: bool = False
) -> int:
    """Run a whole PLAN (a JSON list of ops) over resident tables; the
    result stays resident. ``table_ids[0]`` is the chain input; the
    remaining ids feed multi-table segment-boundary ops (join/concat —
    explicit ``"rest"`` indices into this list, or sequential
    consumption; see plan._take_rest). Fusable runs execute as ONE
    cached executable each (plan.py), so an N-op chain costs one
    launch per segment instead of N dispatches.

    ``donate=True`` consumes ``table_ids[0]`` (freed now) and lets the
    plan's first fused segment donate its buffers; later segments
    always donate their plan-owned intermediates. Enqueues and returns
    immediately when the pipeline is on (see ``table_op_resident``)."""
    if not table_ids:
        raise ValueError("table_plan_resident needs at least one input")
    ops = json.loads(plan_json)
    if not isinstance(ops, list):
        raise TypeError(
            "table_plan_resident: plan must be a JSON list of ops"
        )
    report, head_schema = _static_check_resident_plan(ops, table_ids)
    cell: dict = {}

    def work():
        # the session opens INSIDE the work closure so it scopes the
        # actual execution — on a pipeline worker when enqueued, on the
        # caller when synchronous — not the enqueue-and-return
        with profiler.maybe_session(
            ops, label="plan_resident", schema=head_schema,
            static=report,
        ):
            tables = pipeline.materialize_inputs(cell["inputs"])
            for p in cell["barrier"]:
                p.settle_terminally()
            return plan_mod.run_plan(
                ops, tables[0], tables[1:], donate_input=donate
            )

    if pipeline.enabled():
        # capture + reader registration are atomic (see
        # table_op_resident); the enqueue comes after the cell is set
        pending = pipeline.Pending(work, "plan", replayable=not donate)
        cell["inputs"], cell["barrier"] = _capture_inputs(
            table_ids, donate, reader=pending
        )
        return _resident_put(pipeline.enqueue(pending))
    cell["inputs"], cell["barrier"] = _capture_inputs(
        table_ids, donate, pin=True
    )
    try:
        return _resident_put(work())
    finally:
        spill.unpin_ids(table_ids[1:] if donate else table_ids)


# table id -> count of table_download_wire serializers currently
# reading that id's buffers. table_free never touches buffers, so a
# plain free under an active download is safe (the download holds its
# own Table reference) — but table_reclaim DELETES device buffers and
# must drain these readers first, exactly like the pipelined-reader
# barrier. Registered atomically with the registry lookup so a reclaim
# that popped the id either sees this read or ordered itself first.
_RESIDENT_ACTIVE_READS: dict = {}
_RESIDENT_READS_CV = lockcheck.make_condition(_RESIDENT_LOCK)


def table_download_wire(table_id: int):
    """C-ABI entry: resident table -> the wire 5-tuple of table_op_wire,
    every buffer ``bytes`` (``table_download_views`` has the rest)."""
    return _wire_bytes(table_download_views(table_id))


def table_download_views(table_id: int):
    """Resident table -> the wire 5-tuple of table_op_wire (shape-bucket
    padding sliced away host-side; the wire never sees it), its buffers
    as ``_table_to_wire`` leaves them: ``bytes``, or byte views of host
    memory that outlive the table (the daemon's download; a frame sends
    either). One of the two BLOCKING points of the pipelined plane: a
    pending chain is waited for here and any worker failure is replayed
    synchronously so the originating op's labeled error raises from
    this call. Raises the labeled KeyError on an unknown or
    already-freed id."""
    tid = int(table_id)
    with _RESIDENT_LOCK:
        t = _RESIDENT.get(tid)
        if isinstance(t, spill.SpilledTable):
            t = spill.repage_locked(tid)
        live = len(_RESIDENT)
        if t is not None:
            _RESIDENT_ACTIVE_READS[tid] = (
                _RESIDENT_ACTIVE_READS.get(tid, 0) + 1
            )
            spill.touch(tid)
    spill.flush_events()
    if t is None:
        raise _unknown_id_error(tid, live)
    try:
        if isinstance(t, pipeline.Pending):
            t = t.resolve()
            with _RESIDENT_LOCK:
                # swap the settled Table in so later gets skip the
                # handle (unless the id was freed while we waited)
                if tid in _RESIDENT:
                    _RESIDENT[tid] = t
        metrics.counter_add("resident.get")
        return _table_to_wire(t)
    finally:
        with _RESIDENT_READS_CV:
            n = _RESIDENT_ACTIVE_READS.get(tid, 1) - 1
            if n > 0:
                _RESIDENT_ACTIVE_READS[tid] = n
            else:
                _RESIDENT_ACTIVE_READS.pop(tid, None)
            _RESIDENT_READS_CV.notify_all()


def table_num_rows(table_id: int) -> int:
    """Logical row count — the other blocking point (see
    ``table_download_wire``)."""
    return int(_resident_get(table_id).logical_row_count)


def table_free(table_id: int) -> None:
    """Release a resident id. A still-pending entry is dropped without
    waiting (the enqueued op keeps its own input references and simply
    completes unobserved); a pending that already FAILED logs the
    dropped error — the caller chose to never hit a blocking point, so
    this WARN is the only trace the op ever broke. Raises the labeled
    KeyError naming the id and live count on an unknown or
    already-freed id."""
    with _RESIDENT_LOCK:
        t = _RESIDENT.pop(int(table_id), None)
        gone = t is None
        _RESIDENT_META.pop(int(table_id), None)
        readers = _RESIDENT_READERS.pop(int(table_id), ())
        live = len(_RESIDENT)
    if gone:
        raise _unknown_id_error(table_id, live)
    # drops spill tracking; for a spilled entry this also releases the
    # host/disk backing (no orphaned spill files)
    spill.note_free(int(table_id), t)
    if isinstance(t, pipeline.Pending):
        if not any(not p.done() for p in readers):
            # fire-and-forget: nothing downstream captured this handle
            # and no blocking point remains — a failure (already
            # landed or still to come) must log itself; when an
            # in-flight consumer DID capture it, error surfacing is
            # delegated to that consumer's blocking point (the normal
            # enqueue -> free(input) chain idiom)
            t.orphan()
            if t.failed_nowait():
                log.log(
                    "WARN", "handles", "freed_failed_pending",
                    table_id=int(table_id), stage=t.label,
                )
                if flight.enabled():
                    flight.record("I", "pipeline.freed_failed", t.label)
    log.log("DEBUG", "handles", "table_free", table_id=int(table_id),
            live=live)
    metrics.counter_add("resident.free")
    metrics.gauge_set("resident.live", live)
    if flight.enabled():
        flight.record("C", "resident.live", live)


def _column_device_arrays(col) -> list:
    """The column's device buffers (data + validity + LIST lengths)."""
    out = []
    for name in ("data", "validity", "lengths"):
        a = getattr(col, name, None)
        if a is not None and hasattr(a, "delete"):
            out.append(a)
    return out


def table_reclaim(table_id: int) -> int:
    """Serving-teardown free: release a resident id AND return its HBM
    to the device now. Returns the approximate bytes reclaimed.

    ``table_free`` only drops the registry reference — safe under
    concurrent readers because each holds its own Table reference — but
    a multi-tenant daemon tearing a session down needs the bytes back
    while OTHER tenants keep running, which means deleting device
    buffers that an in-flight pipelined reader may still dereference.
    That is exactly the donate-consume hazard, so this settles through
    the same barrier before touching anything: (1) every registered
    pipelined reader of the id is terminally settled (later replays
    included, ``Pending.settle_terminally``), (2) in-flight
    ``table_download_wire`` serializers of the id drain, and only then
    (3) the buffers are deleted — skipping any buffer shared with a
    still-live resident table (an aliasing op output), and tolerating
    buffers an executable already consumed by donation. Like donation,
    the caller owns the id: no OTHER thread may still be synchronously
    dispatching ops over it (the serving scheduler guarantees this by
    draining a session's in-flight work before teardown reclaims).
    Raises the labeled KeyError on an unknown or already-freed id."""
    tid = int(table_id)
    with _RESIDENT_LOCK:
        t = _RESIDENT.pop(tid, None)
        gone = t is None
        _RESIDENT_META.pop(tid, None)
        readers = _RESIDENT_READERS.pop(tid, ())
        live = len(_RESIDENT)
    if gone:
        raise _unknown_id_error(table_id, live)
    for p in readers:
        # the donate barrier: a still-running (or failed-but-
        # replayable) reader would dereference the buffers we are about
        # to delete — run it to terminal settlement NOW
        p.settle_terminally()
    if isinstance(t, spill.SpilledTable):
        # already off the device: release the host/disk backing and
        # credit the device bytes the table would have re-occupied
        nbytes = spill.note_free(tid, t)
        metrics.counter_add("resident.free")
        metrics.bytes_add("resident.reclaimed_bytes", nbytes)
        metrics.gauge_set("resident.live", live)
        if flight.enabled():
            flight.record("C", "resident.live", live)
        log.log("DEBUG", "handles", "table_reclaim", table_id=tid,
                live=live, nbytes=nbytes)
        return nbytes
    spill.note_free(tid)
    if isinstance(t, pipeline.Pending):
        t.orphan()  # no blocking point remains for this handle
        t.wait_settled()
        settled = t.value_nowait()
        if settled is None:
            # the producing op failed: there are no buffers to reclaim,
            # and table_free's fire-and-forget WARN is the only trace
            if t.failed_nowait():
                log.log(
                    "WARN", "handles", "reclaimed_failed_pending",
                    table_id=tid, stage=t.label,
                )
            metrics.counter_add("resident.free")
            metrics.gauge_set("resident.live", live)
            if flight.enabled():
                flight.record("C", "resident.live", live)
            return 0
        t = settled
    # drain in-flight wire serializers of this id (they registered
    # atomically with their registry lookup; the pop above makes new
    # ones impossible, so this wait terminates)
    with _RESIDENT_READS_CV:
        while _RESIDENT_ACTIVE_READS.get(tid):
            _RESIDENT_READS_CV.wait()
    from .utils import hbm

    try:
        nbytes = int(hbm.table_bytes(t))
    # srt: allow-broad-except(diagnostic sizing only; reclaim proceeds with nbytes=0)
    except Exception:
        nbytes = 0
    # never delete a buffer another live table can still see: an op
    # output may alias its input outright (e.g. single-table concat
    # returns the input Table), and settled pending entries count
    shared = set()
    with _RESIDENT_LOCK:
        others = list(_RESIDENT.values())
    for o in others:
        if isinstance(o, pipeline.Pending):
            o = o.value_nowait()
            if o is None:
                continue
        if isinstance(o, spill.SpilledTable):
            continue  # holds no device buffers
        for c in o.columns:
            for a in _column_device_arrays(c):
                shared.add(id(a))
    for c in t.columns:
        for a in _column_device_arrays(c):
            if id(a) in shared:
                continue
            try:
                a.delete()
            # srt: allow-broad-except(already consumed by a donated executable or no explicit delete; the reference drop reclaims it)
            except Exception:
                pass
    log.log("DEBUG", "handles", "table_reclaim", table_id=tid,
            live=live, nbytes=nbytes)
    metrics.counter_add("resident.free")
    metrics.bytes_add("resident.reclaimed_bytes", nbytes)
    metrics.gauge_set("resident.live", live)
    if flight.enabled():
        flight.record("C", "resident.live", live)
    return nbytes


def resident_table_count() -> int:
    """Live resident tables (leak-report analog for device tables)."""
    with _RESIDENT_LOCK:
        return len(_RESIDENT)


def leak_report() -> list:
    """Tables still resident, each with the span stack that allocated
    it — the RMM leak report's role for device table handles. JSON-able;
    embedded in the flight dump as the ``resident_leaks`` section and
    printed at exit when non-empty and a telemetry plane is on."""
    with _RESIDENT_LOCK:
        items = [
            (tid, _RESIDENT[tid], dict(_RESIDENT_META.get(tid) or {}))
            for tid in sorted(_RESIDENT)
        ]
    now = _time.perf_counter_ns()
    out = []
    for tid, t, meta in items:
        # never resolve a pending here: the leak report runs at exit
        # and must not replay abandoned work just to size it
        pending = isinstance(t, pipeline.Pending)
        if pending:
            settled = t.value_nowait()
            if settled is not None:
                t, pending = settled, False
        spilled = isinstance(t, spill.SpilledTable)
        if spilled:
            logical = int(t.rows)
        else:
            logical = None if pending else int(t.logical_row_count)
        rec = {
            "table_id": tid,
            "rows": logical,
            "logical_rows": logical,
            "columns": t.num_columns if spilled
            else (None if pending else len(t.columns)),
            "allocated_under": meta.get("allocated_under", []),
        }
        if pending:
            rec["pending"] = t.label
        if spilled:
            # a spilled leak holds host RAM or a disk file, not HBM —
            # say which tier so the postmortem reads correctly
            rec["residency"] = t.state
            rec["approx_bytes"] = int(t.nbytes)
        if meta.get("session"):
            rec["session"] = meta["session"]
        anchor = meta.get("age_anchor_ns")
        if anchor is not None:
            rec["age_s"] = round((now - anchor) / 1e9, 3)
        if not pending and not spilled:
            try:
                from .utils import hbm

                rec["approx_bytes"] = int(hbm.table_bytes(t))
            # srt: allow-broad-except(best-effort sizing for the leak report; listing tables must never fail)
            except Exception:
                pass
        out.append(rec)
    return out


def _leak_report_at_exit() -> None:  # pragma: no cover - atexit path
    """The RMM-leak-report-at-shutdown analog: WARN (ungated when a
    telemetry plane is on — a leak with no trace wasted a round-5
    debugging session) for every table a dead process left resident."""
    if not _RESIDENT or not _provenance_on():
        return
    import sys as _sys

    leaks = leak_report()
    print(
        f"[srt][leak][WARN] {len(leaks)} device table(s) still resident "
        "at exit:",
        file=_sys.stderr,
        flush=True,
    )
    for rec in leaks:
        under = "/".join(rec["allocated_under"]) or "<no span>"
        print(
            f"[srt][leak][WARN]   table_id={rec['table_id']} "
            f"logical_rows={rec['logical_rows']} cols={rec['columns']} "
            f"bytes~{rec.get('approx_bytes', '?')} "
            f"allocated_under={under}",
            file=_sys.stderr,
            flush=True,
        )


atexit.register(_leak_report_at_exit)
# the flight dump carries the same record, so a postmortem reads one file
flight.register_exit_section("resident_leaks", leak_report)
# the spill tier operates UNDER this registry's lock: one lock decides
# eviction vs capture vs reclaim ordering (utils/spill.py)
spill.bind_registry(
    _RESIDENT_LOCK, _RESIDENT, _RESIDENT_READERS, _RESIDENT_ACTIVE_READS
)
