"""A frame is received once (``serving/frames.py``).

Held here: a frame dribbled over a socket in pieces that straddle every
boundary comes back whole, at payload sizes around the old 1 MiB chunk
and with absent (``-1``), empty (``0``) and validity buffers; the
payload ``recv_frame`` returns and every buffer ``batch_from_parts``
hands on are views of ONE receive buffer, not copies; **a view pins its
frame**: a table uploaded through a view survives every later frame on
its connection, and a pipelined stream answers as the serial path does;
the refusals are the tree's own and come before the payload is
allocated; ``frames.bytes_in.view`` equals ``frames.bytes_in`` and the
session's ``stats`` carry ``frames_in.view_share`` 1.0. The wire format
is spelled out by hand below, apart from ``send_frame``: it may not
change by a byte.
"""

import json
import socket
import struct
import threading

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.serving import frames
from spark_rapids_jni_tpu.utils import config, metrics

I64 = int(dt.TypeId.INT64)
MIB = 1 << 20
SIZES = (0, 1, MIB - 1, MIB, MIB + 1, 3 * MIB + 5)
SHAPES = ("data_only", "with_validity", "absent_and_empty")


@pytest.fixture(autouse=True)
def _metrics_on():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("METRICS")
    metrics.reset()


def _bytes(n: int, salt: int) -> bytes:
    return np.random.default_rng(n + 31 * salt).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _codec_batch(size: int, shape: str):
    """A wire 5-tuple whose buffers hold ``size`` bytes in all. The
    codec reads no type: only ``lens`` decide where a buffer ends."""
    a = size // 3
    if shape == "data_only":
        datas = [_bytes(a, 1), _bytes(size - a, 2)]
        valids = [None, None]
    elif shape == "with_validity":
        b = size // 5
        datas = [_bytes(a, 1), _bytes(size - a - 2 * b, 2)]
        valids = [_bytes(b, 3), _bytes(b, 4)]
    else:  # a -1 (no data buffer at all) and a 0 (an empty one)
        datas = [None, b"", _bytes(size - a, 2)]
        valids = [_bytes(a, 3), None, None]
    n = len(datas)
    return ([I64] * n, [0] * n, datas, valids, 7)


def _spell_frame(header: dict, buffers) -> tuple:
    """The frame's bytes by the format's own words, and its boundaries:
    ``u32 total | u32 hdr_len | header | buffers``."""
    hdr = json.dumps(header, separators=(",", ":")).encode()
    body = b"".join(buffers)
    raw = (struct.pack(">I", 4 + len(hdr) + len(body))
           + struct.pack(">I", len(hdr)) + hdr + body)
    bounds = [4, 8, 8 + len(hdr)]
    for b in buffers:
        bounds.append(bounds[-1] + len(b))
    return raw, bounds


def _dribble(sock, raw: bytes, bounds) -> None:
    """Send ``raw`` cut one byte before and one byte after every
    boundary, so a piece straddles each of them."""
    cuts = sorted({c for b in bounds for c in (b - 1, b + 1)
                   if 0 < c < len(raw)})
    for lo, hi in zip([0] + cuts, cuts + [len(raw)]):
        sock.sendall(raw[lo:hi])


def _through_a_socket(raw: bytes, bounds):
    a, b = socket.socketpair()
    with a, b:
        t = threading.Thread(target=_dribble, args=(a, raw, bounds))
        t.start()
        try:
            return frames.recv_frame(b)
        finally:
            t.join(timeout=60)
            assert not t.is_alive()


def _same(got, want) -> bool:
    return (got is None) == (want is None) and (
        got is None or bytes(got) == want)


# ---------------------------------------------------------------------------
# (a) round trip, dribbled
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("size", SIZES)
def test_a_dribbled_frame_comes_back_whole(size, shape):
    batch = _codec_batch(size, shape)
    meta, buffers = frames.batch_to_parts(batch)
    assert sum(len(b) for b in buffers) == size
    header = {"cmd": "upload", "batch": meta, "note": "é" * (size % 7)}
    raw, bounds = _spell_frame(header, buffers)
    got_header, payload = _through_a_socket(raw, bounds)
    assert got_header == header
    assert len(payload) == size and bytes(payload) == b"".join(buffers)
    back, end = frames.batch_from_parts(got_header["batch"], payload, 0)
    assert end == size
    assert back[0] == batch[0] and back[1] == batch[1] and back[4] == 7
    assert all(_same(g, w) for g, w in zip(back[2], batch[2]))
    assert all(_same(g, w) for g, w in zip(back[3], batch[3]))


def test_send_frame_writes_the_spelled_format():
    meta, buffers = frames.batch_to_parts(_codec_batch(1000, "with_validity"))
    header = {"cmd": "upload", "batch": meta}
    raw, _ = _spell_frame(header, buffers)
    a, b = socket.socketpair()
    with a, b:
        frames.send_frame(a, header, buffers)
        a.shutdown(socket.SHUT_WR)
        got = b""
        while chunk := b.recv(1 << 16):
            got += chunk
    assert got == raw


# ---------------------------------------------------------------------------
# (b) no copy
# ---------------------------------------------------------------------------


def _u8(view) -> np.ndarray:
    return np.frombuffer(view, np.uint8)


@pytest.mark.parametrize("shape", SHAPES)
def test_every_buffer_is_a_view_of_the_one_receive_buffer(shape):
    batch = _codec_batch(MIB + 1, shape)
    meta, buffers = frames.batch_to_parts(batch)
    raw, bounds = _spell_frame({"batch": meta}, buffers)
    header, payload = _through_a_socket(raw, bounds)
    # the payload IS the buffer the socket wrote: a byte view of an
    # array that owns exactly the payload's bytes and nothing else
    assert isinstance(payload, memoryview) and payload.format == "B"
    buf = payload.obj
    assert isinstance(buf, np.ndarray) and buf.flags.owndata
    assert buf.nbytes == len(payload) == MIB + 1
    (_, _, datas, valids, _), _ = frames.batch_from_parts(
        header["batch"], payload, 0)
    # wire order: data then validity, column by column
    held = [b for pair in zip(datas, valids) for b in pair if b is not None]
    assert len(held) == len(buffers)
    for b in held:
        assert isinstance(b, memoryview) and b.format == "B"
        assert b.obj is buf
        if len(b):
            assert np.shares_memory(_u8(b), buf)
    assert frames.view_bytes(
        [(None, None, datas, valids, 7)], payload) == MIB + 1
    # in order and adjacent: the views tile the buffer
    at = buf.__array_interface__["data"][0]
    for b in held:
        if len(b):
            assert _u8(b).__array_interface__["data"][0] == at
        at += len(b)


def test_an_empty_payload_is_an_empty_view():
    raw, bounds = _spell_frame({"cmd": "stats"}, [])
    header, payload = _through_a_socket(raw, bounds)
    assert header == {"cmd": "stats"}
    assert isinstance(payload, memoryview) and len(payload) == 0
    assert frames.batches_from_parts([], payload) == []


def test_a_copy_is_not_counted_as_a_view():
    raw, bounds = _spell_frame({}, [b"x" * 64])
    _, payload = _through_a_socket(raw, bounds)
    view, copy = payload[:32], memoryview(bytes(payload[32:]))
    batch = (None, None, [view, copy], [None, None], 4)
    assert frames.view_bytes([batch], payload) == 32


# ---------------------------------------------------------------------------
# (c) a view pins its frame
# ---------------------------------------------------------------------------

CHAIN = [{"op": "sort_by", "keys": [{"column": 0}]}]


def _table(n: int, seed: int):
    rng = np.random.default_rng(seed)
    k = rng.integers(-10**12, 10**12, n, dtype=np.int64)
    v = rng.integers(-10**6, 10**6, n, dtype=np.int64)
    valid = (rng.integers(0, 4, n) > 0).astype(np.uint8)
    return ([I64, I64], [0, 0], [k.tobytes(), v.tobytes()],
            [None, valid.tobytes()], n)


def _norm(wire):
    t, s, d, v, n = wire
    return ([int(x) for x in t], [int(x) for x in s],
            [None if x is None else bytes(x) for x in d],
            [None if x is None else bytes(x) for x in v], int(n))


def _serial(batch):
    return _norm(rb.table_plan_wire(json.dumps(CHAIN), *batch))


def test_a_resident_table_survives_later_frames_on_its_connection():
    first = _table(4096, seed=1)
    with serving.serve() as srv:
        with serving.Client(srv.port, name="pins") as c:
            tid = c.upload(first)
            others = []
            for i, n in enumerate((8192, 16384, 40000)):
                later = _table(n, seed=10 + i)
                assert _norm(c.stream(CHAIN, [later])[0]) == _serial(later)
                others.append((c.upload(later), later))
            assert _norm(c.download(tid)) == _norm(first)
            # and the later ones did not land on one another either
            for t, want in others:
                assert _norm(c.download(t)) == _norm(want)
    assert rb.resident_table_count() == 0


def test_a_reply_read_through_views_outlives_the_next_reply():
    """The client's half: a downloaded batch is views of ITS reply's
    buffer, which the next reply on the socket must not touch."""
    small, big = _table(1000, seed=3), _table(30000, seed=4)
    with serving.serve() as srv:
        with serving.Client(srv.port, name="replies") as c:
            a, b = c.upload(small), c.upload(big)
            got_small = c.download(a)
            assert all(isinstance(x, memoryview) for x in got_small[2])
            got_big = c.download(b)
            c.stream(CHAIN, [big])
            assert _norm(got_small) == _norm(small)
            assert _norm(got_big) == _norm(big)


def test_a_pipelined_stream_answers_as_the_serial_path_does():
    """Two stream requests go down the socket before either reply is
    read: the second frame is received while the first one's batches
    may still be on their way to the device."""
    first = [_table(n, seed=20 + n) for n in (3000, 5000, 7000)]
    second = [_table(n, seed=40 + n) for n in (20000, 30000)]
    with serving.serve(queue_depth=4) as srv:
        with serving.Client(srv.port, name="pipelined") as c:
            for batches in (first, second):
                metas, buffers = frames.batches_to_parts(batches)
                frames.send_frame(
                    c._sock,
                    {"cmd": "stream", "plan": CHAIN, "batches": metas},
                    buffers,
                )
            for batches in (first, second):
                resp, payload = frames.recv_frame(c._sock)
                assert resp.get("ok"), resp
                got = frames.batches_from_parts(resp["results"], payload)
                assert [_norm(g) for g in got] == [
                    _serial(b) for b in batches]


# ---------------------------------------------------------------------------
# (d) the refusals, and when they come
# ---------------------------------------------------------------------------


def _u32(n: int) -> bytes:
    return struct.pack(">I", n)


def _refused(monkeypatch, raw: bytes):
    """Feed ``raw`` then EOF; return the error and the sizes of every
    buffer ``recv_frame`` asked for before it raised."""
    asked = []
    real = frames._recv_new

    def recording(sock, n):
        asked.append(n)
        return real(sock, n)

    monkeypatch.setattr(frames, "_recv_new", recording)
    a, b = socket.socketpair()
    with a, b:
        a.sendall(raw)
        a.shutdown(socket.SHUT_WR)
        with pytest.raises((frames.ProtocolError, ConnectionError)) as e:
            frames.recv_frame(b)
    return e.value, asked


@pytest.mark.parametrize(
    "total", [0, 1, 2, 3, frames.MAX_FRAME_BYTES + 1, 0xFFFFFFFF])
def test_a_bad_length_prefix_is_refused_before_anything_is_read(
        monkeypatch, total):
    err, asked = _refused(monkeypatch, _u32(total) + b"\0" * 64)
    assert isinstance(err, frames.ProtocolError)
    assert f"bad frame length {total}" in str(err)
    assert asked == [4]


@pytest.mark.parametrize("total,hdr_len", [
    (4, 1), (20, 17), (frames.MAX_FRAME_BYTES, frames.MAX_FRAME_BYTES - 3),
])
def test_a_header_longer_than_its_frame_is_refused_unallocated(
        monkeypatch, total, hdr_len):
    err, asked = _refused(monkeypatch, _u32(total) + _u32(hdr_len))
    assert isinstance(err, frames.ProtocolError)
    assert f"header length {hdr_len} exceeds frame body" in str(err)
    assert asked == [4, 4]


@pytest.mark.parametrize("hdr,why", [
    (b"\xff\xfe{", "undecodable frame header"),
    (b"{not json", "undecodable frame header"),
    (b"[1,2]", "must be a JSON object, got list"),
    (b"7", "must be a JSON object, got int"),
])
def test_a_bad_header_is_refused_before_the_payload_is_allocated(
        monkeypatch, hdr, why):
    payload = b"p" * 4096
    raw = (_u32(4 + len(hdr) + len(payload)) + _u32(len(hdr))
           + hdr + payload)
    err, asked = _refused(monkeypatch, raw)
    assert isinstance(err, frames.ProtocolError) and why in str(err)
    assert asked == [4, 4, len(hdr)]


@pytest.mark.parametrize("stage,cut,got_of_n", [
    ("prefix", 2, "(2/4 bytes)"),
    ("header length", 4 + 3, "(3/4 bytes)"),
    ("header", 8 + 5, "(5/11 bytes)"),
    ("payload", 8 + 11 + 100, "(100/4096 bytes)"),
])
def test_eof_mid_frame_is_a_connection_error(
        monkeypatch, stage, cut, got_of_n):
    raw, _ = _spell_frame({"cmd": "x"}, [b"p" * 4096])
    assert raw[8:19] == b'{"cmd":"x"}'
    err, _ = _refused(monkeypatch, raw[:cut])
    assert isinstance(err, ConnectionError), stage
    assert "connection closed mid-frame" in str(err)
    assert got_of_n in str(err)


def test_eof_between_frames_is_a_connection_error(monkeypatch):
    err, asked = _refused(monkeypatch, b"")
    assert isinstance(err, ConnectionError) and "(0/4 bytes)" in str(err)


@pytest.mark.parametrize("meta,why", [
    ({"type_ids": [I64], "scales": [0], "num_rows": 1,
      "lens": [[16, -1]]}, "truncated batch payload"),
    ({"type_ids": [I64], "scales": [0], "num_rows": 1,
      "lens": [[8, 1]]}, "truncated batch payload"),
    ({"type_ids": [I64, I64], "scales": [0], "num_rows": 1,
      "lens": [[8, -1]]}, "arity mismatch"),
    ({"type_ids": [I64]}, "malformed batch meta"),
])
def test_a_batch_that_overruns_its_payload_is_refused(meta, why):
    with pytest.raises(frames.ProtocolError, match=why):
        frames.batch_from_parts(meta, memoryview(b"12345678"), 0)


# ---------------------------------------------------------------------------
# (e) the counter that says it engaged
# ---------------------------------------------------------------------------


def test_every_byte_in_was_handed_on_as_a_view():
    names = ["frames.bytes_in", "frames.bytes_in.view"]
    batches = [_table(n, seed=n) for n in (2000, 3000)]
    sent = sum(len(b) for t in batches for b in (*t[2], *t[3])
               if b is not None)
    with serving.serve() as srv:
        with serving.Client(srv.port, name="counted") as c:
            before = metrics.counter_values(names)
            c.stream(CHAIN, batches)
            after = metrics.counter_values(names)
            moved = {k: after[k] - before[k] for k in names}
            assert moved["frames.bytes_in"] == sent
            assert moved["frames.bytes_in.view"] == sent
            c.upload(batches[0])
            doc = [s for s in c.stats()["sessions"]
                   if s["name"] == "counted"][0]
    up = sum(len(b) for b in (*batches[0][2], *batches[0][3])
             if b is not None)
    assert doc["bytes_in"] == sent + up
    assert doc["frames_in"] == {
        "bytes": sent + up, "view_bytes": sent + up, "view_share": 1.0}


def test_a_session_that_received_no_payload_has_no_frames_in():
    with serving.serve() as srv:
        with serving.Client(srv.port, name="quiet") as c:
            doc = [s for s in c.stats()["sessions"]
                   if s["name"] == "quiet"][0]
    assert doc["bytes_in"] == 0 and "frames_in" not in doc
