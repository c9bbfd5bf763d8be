"""Length-prefixed frame codec for the serving daemon's wire protocol.

One frame is::

    u32_be total_len | u32_be header_len | header (UTF-8 JSON) | buffers

``total_len`` covers everything after itself. The header is a plain
JSON object carrying the command / response fields plus per-batch
buffer metadata; the raw column buffers follow concatenated, in batch
order, data-then-validity per column — exactly the byte strings of the
runtime bridge's wire 5-tuple ``(type_ids, scales, datas, valids,
num_rows)``, so the daemon reuses ``_table_from_wire`` /
``_table_to_wire`` with no re-encoding.

A batch is described in the header as::

    {"type_ids": [...], "scales": [...], "num_rows": n,
     "lens": [[data_len, valid_len_or_-1], ...]}

with ``-1`` meaning "no buffer follows" (a NULL-free column's validity,
or an empty data buffer encoded as length 0 vs. absent as -1).

Hello and command headers may carry ``deadline_s`` (float seconds):
on hello it sets the session's default request deadline, on a
``stream`` / ``plan`` command it bounds that one request — the server
turns it into a ``faults.CancelToken`` checked between plan segments
and stream batches, answering ``deadline_exceeded`` when it elapses.

Hello and command headers may also carry ``traceparent``: the
W3C-style trace-context header (``utils/tracing.py`` —
``00-<32-hex trace_id>-<16-hex span_id>-01``). The client stamps it
per request when the trace plane is on; the server joins the incoming
trace (fresh hop span id, same trace id) and activates it as the
ambient context for the request, so every span/instant either side
records into its flight ring carries the same trace id and
``tools/tracequery.py`` can merge the per-process dumps into one
request timeline. A malformed header is ignored, never an error.

``send_frame`` / ``recv_frame`` take the name of the span the caller
wants the socket time under (``serving.send`` / ``serving.recv`` on the
daemon's side, ``client.send`` / ``client.recv`` on the client's). The
daemon's ``recv`` opens once the length prefix has arrived, so a client
that is thinking between commands is not in it; the client's includes
the wait for the daemon's reply.

**A frame is received once.** ``recv_frame`` reads the two length words
and the header as the few bytes they are, checks them, and only then
allocates ONE buffer of exactly the payload's size and lets the socket
write into it (``recv_into``). What it returns, ``(header, payload)``,
is the header's ``dict`` and a byte-format (``'B'``) ``memoryview`` of
that buffer (an empty view for a frame with no buffers), and
``batch_from_parts`` hands every column's data and validity on as a
slice of it: a view, never a copy. ``len()`` of either is bytes. A
caller that needs ``bytes`` semantics (hashing, ``json``) takes
``bytes(view)`` at that place.

**The ownership rule:** a frame's buffer is written once, by
``recv_into``, and never reused or mutated; whoever holds a view keeps
it alive. There is no pooled or per-connection receive buffer: an
uploaded table's ``np.frombuffer`` arrays over the frame go to
``jax.device_put``, which may alias host memory (the CPU backend) or
read it after the call returns (the TPU), so a later frame on the same
connection must never be able to change a resident table or a stream's
batch in flight.

**The send side of the rule:** a reply's buffer is ``bytes`` or a byte
view of host memory that the view keeps alive, written by nobody after
it was filled: the read-only host array a device leaf was downloaded
into or the mesh gather's buffer (``runtime_bridge._as_wire``), or the
one buffer a LIST's or STRING's offsets and payload were written into
(``_padded_to_offsets``). ``send_frame`` hands either to ``sendall`` as
it is. A view is taken only of memory that is the host's own: never of
a device buffer that ``np.asarray`` aliased, which ``table_reclaim`` or
a donating plan may delete before the frame is sent.
"""

from __future__ import annotations

import json
import socket
import struct
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..utils import metrics

# hard ceiling on one frame: a corrupt / hostile length prefix must
# fail loudly instead of allocating the universe
MAX_FRAME_BYTES = 1 << 30

_U32 = struct.Struct(">I")


class ProtocolError(RuntimeError):
    """Malformed frame: bad length prefix, truncated payload, or a
    header that is not a JSON object."""


def _recv_new(sock, n: int) -> memoryview:
    """Read exactly ``n`` bytes into ONE buffer made for them and return
    it as a byte view, or raise ConnectionError on EOF. The buffer is
    uninitialised memory the socket writes once (no zero fill, no chunk
    list to join); nothing else ever writes to it. Each call asks for
    all that is left and waits for it (``MSG_WAITALL``): one system
    call a frame where nothing interrupts it, not one a segment."""
    view = memoryview(np.empty(n, dtype=np.uint8))
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], 0, socket.MSG_WAITALL)
        if not k:
            raise ConnectionError(
                f"connection closed mid-frame ({got}/{n} bytes)"
            )
        got += k
    return view


def send_frame(sock, header: dict, buffers: Sequence[bytes] = (),
               span: str = "serving.send") -> None:
    """Serialize and send one frame (single ``sendall`` for the prefix +
    header; buffers follow individually to avoid concatenating large
    payloads host-side), timed under the caller's ``span``."""
    hdr = json.dumps(header, separators=(",", ":")).encode()
    total = 4 + len(hdr) + sum(len(b) for b in buffers)
    if total > MAX_FRAME_BYTES:
        raise ProtocolError(
            f"frame of {total} bytes exceeds MAX_FRAME_BYTES "
            f"({MAX_FRAME_BYTES})"
        )
    with metrics.span(span):
        sock.sendall(_U32.pack(total) + _U32.pack(len(hdr)) + hdr)
        for b in buffers:
            if b:
                sock.sendall(b)


def _recv_prefix(sock) -> int:
    total = _U32.unpack(_recv_new(sock, 4))[0]
    if total < 4 or total > MAX_FRAME_BYTES:
        raise ProtocolError(f"bad frame length {total}")
    return total


def _recv_body(sock, total: int) -> Tuple[dict, memoryview]:
    """Header, then payload, each checked before the next is allocated:
    the payload's buffer holds nothing else, so it starts on the
    allocator's alignment and no header has to be cut off its front."""
    hdr_len = _U32.unpack(_recv_new(sock, 4))[0]
    if hdr_len > total - 4:
        raise ProtocolError(
            f"header length {hdr_len} exceeds frame body {total - 4}"
        )
    raw = bytes(_recv_new(sock, hdr_len))
    try:
        header = json.loads(raw.decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ProtocolError(f"undecodable frame header: {e}")
    if not isinstance(header, dict):
        raise ProtocolError(
            f"frame header must be a JSON object, got {type(header).__name__}"
        )
    return header, _recv_new(sock, total - 4 - hdr_len)


def recv_frame(sock, span: str = "serving.recv",
               include_wait: bool = False) -> Tuple[dict, memoryview]:
    """Receive one frame -> ``(header, payload)`` where ``payload`` is a
    byte view of the ONE buffer the frame's concatenated buffers were
    received into (the module docstring's ownership rule). The caller's
    ``span`` opens once the length prefix is here: it times the frame's
    bytes coming off the socket, not the wait for a peer to speak.
    ``include_wait=True`` opens it before the prefix instead — the
    client's side, where the wait for the reply IS the request."""
    if include_wait:
        with metrics.span(span):
            return _recv_body(sock, _recv_prefix(sock))
    total = _recv_prefix(sock)
    with metrics.span(span):
        return _recv_body(sock, total)


# ---------------------------------------------------------------------------
# batch <-> (meta, buffers)
# ---------------------------------------------------------------------------


def _as_buffer(b):
    """``b`` as ``sendall`` takes it: a byte view of a reply's host
    memory (``runtime_bridge._as_wire``) goes to the socket as it is,
    anything else as ``bytes`` (which ``bytes`` already is)."""
    return b if isinstance(b, memoryview) else bytes(b)


def batch_to_parts(batch) -> Tuple[dict, List[bytes]]:
    """Wire 5-tuple -> (header meta dict, ordered buffer list)."""
    type_ids, scales, datas, valids, num_rows = batch
    lens = []
    buffers: List[bytes] = []
    for d, v in zip(datas, valids):
        dl = -1 if d is None else len(d)
        vl = -1 if v is None else len(v)
        lens.append([dl, vl])
        if d is not None:
            buffers.append(_as_buffer(d))
        if v is not None:
            buffers.append(_as_buffer(v))
    return (
        {
            "type_ids": [int(t) for t in type_ids],
            "scales": [int(s) for s in scales],
            "num_rows": int(num_rows),
            "lens": lens,
        },
        buffers,
    )


def batch_from_parts(meta: dict, payload, offset: int):
    """(header meta, payload, offset) -> (wire 5-tuple, next offset).
    Every buffer of the tuple is a byte view of ``payload`` (a received
    frame's, or any bytes-like): slicing moves no byte."""
    payload = memoryview(payload)
    try:
        type_ids = meta["type_ids"]
        scales = meta["scales"]
        num_rows = int(meta["num_rows"])
        lens = meta["lens"]
    except (KeyError, TypeError, ValueError) as e:
        raise ProtocolError(f"malformed batch meta: {e}")
    if not (len(type_ids) == len(scales) == len(lens)):
        raise ProtocolError(
            f"batch meta arity mismatch: {len(type_ids)} type_ids, "
            f"{len(scales)} scales, {len(lens)} lens"
        )
    datas: List[Optional[memoryview]] = []
    valids: List[Optional[memoryview]] = []
    for dl, vl in lens:
        if dl < 0:
            datas.append(None)
        else:
            if offset + dl > len(payload):
                raise ProtocolError("truncated batch payload")
            datas.append(payload[offset:offset + dl])
            offset += dl
        if vl < 0:
            valids.append(None)
        else:
            if offset + vl > len(payload):
                raise ProtocolError("truncated batch payload")
            valids.append(payload[offset:offset + vl])
            offset += vl
    return (type_ids, scales, datas, valids, num_rows), offset


def view_bytes(batches, payload: memoryview) -> int:
    """Bytes of ``batches``' buffers that are views of ``payload``'s
    buffer and not copies: a slice's ``obj`` is the one object that
    exports the memory."""
    own = payload.obj
    return sum(
        len(b) for batch in batches for b in (*batch[2], *batch[3])
        if isinstance(b, memoryview) and b.obj is own
    )


def batches_to_parts(batches) -> Tuple[List[dict], List[bytes]]:
    """Many wire 5-tuples -> (meta list, one ordered buffer list)."""
    metas: List[dict] = []
    buffers: List[bytes] = []
    for b in batches:
        m, bufs = batch_to_parts(b)
        metas.append(m)
        buffers.extend(bufs)
    return metas, buffers


def batches_from_parts(metas, payload) -> list:
    """(meta list, payload) -> list of wire 5-tuples over views of it."""
    out = []
    offset = 0
    for m in metas:
        b, offset = batch_from_parts(m, payload, offset)
        out.append(b)
    return out
