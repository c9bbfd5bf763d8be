"""A reply's leaves cross once, together, and reach the frame as views
(``runtime_bridge._table_to_wire``, PR 48).

Held here: (a) the 5-tuple is byte for byte what the formulation it
replaced gives (kept below as the plain reference: ``np.asarray(...)
.tobytes()``, ``offs.tobytes() + mat.tobytes()``), for every column
kind, with and without validity and ``logical_rows`` padding; (b) a
matrix that arrives F-contiguous, as the TPU hands a 2-D leaf over, is
written row-major; (c) a view is handed over only of memory that is the
host's own; (d) a reply's buffers outlive ``table_free``,
``table_reclaim`` and a donating plan; (e) a download is a pure read
and launches no program; (f) every device leaf's transfer is started
once, before the first read; (g) the C ABI's entries answer ``bytes``;
(h) ``wire.*_out.view`` and the session's ``replies_out`` count the
bytes no host copy touched; (i) a reply of views goes through a frame and decodes to the
same table.
"""

import json
import socket
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import rows as rows_mod
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.serving import frames
from spark_rapids_jni_tpu.serving.session import Session
from spark_rapids_jni_tpu.utils import config, metrics

N = 131


@pytest.fixture(autouse=True)
def _metrics_on():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("METRICS")
    metrics.reset()


# ---------------------------------------------------------------------------
# the formulation this PR replaced, as the plain reference
# ---------------------------------------------------------------------------


def _ref_padded(mat, lens):
    offs = np.zeros((lens.shape[0] + 1,), np.int32)
    np.cumsum(lens, out=offs[1:])
    if lens.shape[0] and int(offs[-1]) == lens.shape[0] * mat.shape[1]:
        return offs.tobytes() + mat.tobytes()
    mask = np.arange(mat.shape[1])[None, :] < lens[:, None]
    return offs.tobytes() + mat[mask].tobytes()


def _ref_column(c: Column, rows):
    def cut(a):
        return a if rows is None else a[:rows]

    valid = (
        None if c.validity is None
        else cut(np.asarray(c.validity)).astype(np.uint8).tobytes()
    )
    if c.dtype.id in (dt.TypeId.STRING, dt.TypeId.LIST):
        scale = (
            int(c.list_child_dtype.id) if c.dtype.id == dt.TypeId.LIST else 0
        )
        data = _ref_padded(
            cut(np.asarray(c.data)),
            cut(np.asarray(c.lengths)).astype(np.int32),
        )
        return int(c.dtype.id), scale, data, valid
    return (int(c.dtype.id), int(c.dtype.scale),
            cut(np.asarray(c.data)).tobytes(), valid)


def _ref_wire(t: Table):
    cols = [_ref_column(c, t.logical_rows) for c in t.columns]
    return ([c[0] for c in cols], [c[1] for c in cols],
            [c[2] for c in cols], [c[3] for c in cols],
            int(t.logical_row_count))


def _as_bytes(wire):
    t, s, d, v, n = wire
    return (list(t), list(s), [None if x is None else bytes(x) for x in d],
            [None if x is None else bytes(x) for x in v], n)


# ---------------------------------------------------------------------------
# one column of every kind
# ---------------------------------------------------------------------------

FIXED = {
    "int8": (np.int8, None), "int32": (np.int32, None),
    "int64": (np.int64, None), "float32": (np.float32, None),
    "decimal32": (np.int32, dt.DType(dt.TypeId.DECIMAL32, -3)),
    "decimal64": (np.int64, dt.DType(dt.TypeId.DECIMAL64, -8)),
}
KINDS = sorted(FIXED) + [
    "bool8", "float64", "decimal128", "string", "string_const",
    "list", "list_const", "list_const64",
]


def _column(kind: str, nulls: bool) -> Column:
    rng = np.random.default_rng(len(kind) + 7 * nulls)
    valid = (np.arange(N) % 5 != 0) if nulls else None
    mask = jnp.asarray(valid) if nulls else None
    if kind in FIXED:
        npdt, d = FIXED[kind]
        if np.issubdtype(npdt, np.integer):
            info = np.iinfo(npdt)
            arr = rng.integers(info.min, info.max, N, dtype=npdt)
        else:
            arr = (rng.normal(size=N) * 1e3).astype(npdt)
        return Column.from_numpy(arr, valid, dtype=d)
    if kind == "bool8":
        return Column.from_numpy(rng.integers(0, 2, N) > 0, valid)
    if kind == "float64":
        return Column.from_numpy(rng.normal(size=N) * 1e6, valid)
    if kind == "decimal128":
        return Column.from_decimal128(
            [None if nulls and i % 5 == 0 else (i - 60) * 10**25
             for i in range(N)], -2)
    if kind == "string":
        return Column.from_strings(
            [None if nulls and i % 5 == 0 else "s" * (i % 9)
             for i in range(N)])
    if kind == "list":
        return Column.from_list_of_lists(
            [None if nulls and i % 5 == 0 else list(range(i % 6))
             for i in range(N)], dt.INT32)
    # constant width (every length == pad): a null row keeps its length,
    # as the to_rows_list shape does
    if kind == "string_const":
        mat = rng.integers(97, 123, (N, 7), dtype=np.uint8)
        return Column(jnp.asarray(mat), dt.STRING, mask,
                      jnp.full((N,), 7, jnp.int32))
    if kind == "list_const":
        rows = rows_mod.to_rows_list(Table([
            Column.from_numpy(rng.integers(-9, 9, N).astype(np.int32)),
            Column.from_numpy(rng.integers(-9, 9, N).astype(np.int64)),
        ]))
        return Column(rows.data, rows.dtype, mask, rows.lengths)
    if kind == "list_const64":
        # an 8-byte child behind 4 * (N + 1) bytes of offsets, N even:
        # the payload starts off its own alignment
        mat = rng.integers(-10**12, 10**12, (N - 1, 3), dtype=np.int64)
        return Column(jnp.asarray(mat), dt.DType(dt.TypeId.LIST),
                      None if mask is None else mask[:N - 1],
                      jnp.full((N - 1,), 3, jnp.int32))
    raise KeyError(kind)


def _host(c: Column) -> Column:
    """``c`` with every leaf a numpy array that owns its memory, read
    only (what ``np.asarray`` of a TPU leaf is)."""
    def own(x):
        arr = np.array(x)
        arr.flags.writeable = False
        return arr

    return jax.tree_util.tree_map(own, c)


def _table(kind: str, nulls: bool, logical) -> Table:
    c = _column(kind, nulls)
    return Table([c], logical_rows=logical)


# ---------------------------------------------------------------------------
# (a) byte for byte the formulation it replaced
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("logical", [None, 100, 0])
@pytest.mark.parametrize("nulls", [True, False], ids=["nulls", "no_nulls"])
@pytest.mark.parametrize("kind", KINDS)
def test_wire_is_byte_for_byte_the_formulation_it_replaced(
        kind, nulls, logical):
    t = _table(kind, nulls, logical)
    want = _ref_wire(t)
    assert _as_bytes(rb._table_to_wire(t)) == want
    # and over leaves that own their memory, which go as views
    host = Table([_host(c) for c in t.columns], logical_rows=logical)
    got = rb._table_to_wire(host)
    assert _as_bytes(got) == want
    assert all(isinstance(b, memoryview) and b.format == "B" and b.ndim == 1
               for b in (*got[2], *got[3]) if b is not None)


@pytest.mark.parametrize("kind", ["list_const", "string", "decimal128"])
def test_a_2d_device_leaf_is_downloaded_by_no_program(kind):
    # the relayout is the host's one copy (``_padded_to_offsets``): no
    # executable is built or launched for it, so a download allocates
    # nothing on the device
    t = _table(kind, True, None)
    names = ["compile_cache.miss", "compile_cache.hit"]
    c0 = metrics.counter_values(names)
    first = _as_bytes(rb._table_to_wire(t))
    again = _as_bytes(rb._table_to_wire(t))
    assert metrics.counter_values(names) == c0
    assert first == again == _ref_wire(t)


# ---------------------------------------------------------------------------
# (b) an F-contiguous matrix is written row-major
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("child", [np.uint8, np.int32, np.int64])
@pytest.mark.parametrize("ragged", [False, True], ids=["const", "ragged"])
def test_an_f_contiguous_matrix_gives_row_major_payload(child, ragged):
    rng = np.random.default_rng(3)
    n, pad = 64, 5
    c_mat = rng.integers(0, 100, (n, pad)).astype(child)
    f_mat = np.asfortranarray(c_mat)
    assert f_mat.flags.f_contiguous and not f_mat.flags.c_contiguous
    lens = (rng.integers(0, pad + 1, n) if ragged
            else np.full(n, pad)).astype(np.int32)
    want = _ref_padded(c_mat, lens)
    for ctx in (None, rb._SerializePass()):
        got = rb._padded_to_offsets(f_mat, lens, ctx)
        assert isinstance(got, memoryview) and bytes(got) == want
    head = 4 * (n + 1)
    flat = np.frombuffer(bytes(got)[head:], child)
    if not ragged:
        assert (flat.reshape(n, pad) == c_mat).all()


def test_offsets_and_payload_share_one_buffer():
    c = _host(_column("list_const", False))
    _, _, (data,), _, _ = rb._table_to_wire(Table([c]))
    head = 4 * (N + 1)
    whole = np.frombuffer(data, np.uint8)
    assert whole.base is not None and len(data) == head + c.data.size
    offs = np.frombuffer(data, np.int32, N + 1)
    assert offs[0] == 0 and (np.diff(offs) == c.data.shape[1]).all()
    assert not np.shares_memory(whole, c.data)  # the one copy


# ---------------------------------------------------------------------------
# (c) a view only of memory that is the host's own
# ---------------------------------------------------------------------------


def test_a_leaf_that_aliases_a_buffer_is_copied():
    backing = bytearray(np.arange(N, dtype=np.int64).tobytes())
    vbacking = bytearray(np.ones(N, np.bool_).tobytes())
    data = np.frombuffer(backing, np.int64)
    valid = np.frombuffer(vbacking, np.bool_)
    assert not rb._own_memory(data) and not rb._own_memory(data[:5])
    wire = rb._table_to_wire(Table([Column(data, dt.INT64, valid)]))
    was = _as_bytes(wire)
    assert isinstance(wire[2][0], bytes) and isinstance(wire[3][0], bytes)
    backing[:8] = b"\xff" * 8
    vbacking[0] = 0
    assert _as_bytes(wire) == was


def test_memory_of_its_own_goes_as_a_view_and_slices_with_it():
    own = np.arange(N, dtype=np.int64)
    assert rb._own_memory(own) and rb._own_memory(own[:7])
    assert rb._own_memory(own.reshape(-1).view(np.uint8))
    t = Table([Column(own, dt.INT64)], logical_rows=100)
    _, _, (data,), _, _ = rb._table_to_wire(t)
    assert isinstance(data, memoryview) and len(data) == 800
    assert np.shares_memory(np.frombuffer(data, np.uint8), own)
    # not C-contiguous: one copy, in C order
    strided = Column(np.arange(2 * N, dtype=np.int64)[::2], dt.INT64)
    _, _, (data,), _, _ = rb._table_to_wire(Table([strided]))
    assert isinstance(data, bytes)
    assert data == np.arange(0, 2 * N, 2, dtype=np.int64).tobytes()


def test_a_device_leaf_is_a_view_only_where_asarray_owns_its_copy():
    c = _column("int64", True)
    host = np.asarray(c.data)
    _, _, (data,), (valid,), _ = rb._table_to_wire(Table([c]))
    assert isinstance(data, memoryview) == rb._own_memory(host)
    assert isinstance(valid, memoryview) == rb._own_memory(host)
    assert bytes(data) == host.tobytes()


# ---------------------------------------------------------------------------
# (d) a reply outlives its table
# ---------------------------------------------------------------------------


def _upload(t: Table) -> int:
    return rb.table_upload_wire(*_as_bytes(rb._table_to_wire(t)))


@pytest.mark.parametrize("end", ["free", "reclaim", "donate"])
@pytest.mark.parametrize("entry", ["views", "wire"])
def test_a_downloaded_reply_reads_the_same_after_its_table_is_gone(
        entry, end):
    t = Table([_column(k, True) for k in
               ("int64", "float64", "bool8", "list_const", "string")])
    tid = _upload(t)
    download = (rb.table_download_views if entry == "views"
                else rb.table_download_wire)
    wire = download(tid)
    was = _as_bytes(wire)
    assert was == _ref_wire(t)
    if end == "free":
        rb.table_free(tid)
    elif end == "reclaim":
        assert rb.table_reclaim(tid) > 0
    else:
        out = rb.table_plan_resident(
            json.dumps([{"op": "slice", "start": 1, "stop": 50},
                        {"op": "cast", "column": 0, "type_id":
                         int(dt.TypeId.INT32)}]), [tid], donate=True)
        rb.table_num_rows(out)
        rb.table_reclaim(out)
    # churn the allocator over whatever was released
    junk = [jnp.arange(N * 8, dtype=jnp.int64) + i for i in range(8)]
    jax.block_until_ready(junk)
    assert _as_bytes(wire) == was


# ---------------------------------------------------------------------------
# (e) a download is a pure read of the table
# ---------------------------------------------------------------------------


def test_a_resident_list_table_downloads_twice_and_still_converts():
    cols = Table([
        Column.from_numpy(np.arange(N, dtype=np.int64)),
        Column.from_numpy(np.arange(N, dtype=np.int32) % 7,
                          np.arange(N) % 3 != 0),
    ])
    tid = _upload(cols)
    packed = rb.table_op_resident(json.dumps({"op": "to_rows"}), [tid])
    first = _as_bytes(rb.table_download_views(packed))
    table = rb._resident_get(packed)
    assert table.columns[0].data.ndim == 2
    again = _as_bytes(rb.table_download_views(packed))
    assert first == again
    assert rb._resident_get(packed).columns[0].data.ndim == 2
    back = rb.table_op_resident(json.dumps({
        "op": "from_rows",
        "type_ids": [int(dt.TypeId.INT64), int(dt.TypeId.INT32)],
        "scales": [0, 0],
    }), [packed])
    assert _as_bytes(rb.table_download_views(back)) == _ref_wire(cols)
    for i in (tid, packed, back):
        rb.table_free(i)


# ---------------------------------------------------------------------------
# (f) every transfer starts once, before the first read
# ---------------------------------------------------------------------------


class _Leaf:
    """A recording stand-in for a device leaf: what the download may
    ask of one (its ``shape``, ``copy_to_host_async``, the read)."""

    def __init__(self, name, arr, log):
        self.name, self._arr, self._log = name, np.array(arr), log
        self.shape, self.dtype = arr.shape, arr.dtype

    def copy_to_host_async(self):
        self._log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("read", self.name))
        return self._arr


def test_every_device_leaf_starts_once_before_the_first_read():
    log = []
    src = [_column(k, True) for k in ("int64", "list_const", "float32")]
    host_backed = _host(_column("int32", True))
    cols = []
    for i, c in enumerate(src):
        leaves = {
            f: None if getattr(c, f) is None
            else _Leaf(f"{i}.{f}", np.asarray(getattr(c, f)), log)
            for f in ("data", "validity", "lengths")
        }
        cols.append(Column(leaves["data"], c.dtype, leaves["validity"],
                           leaves["lengths"]))
    t = Table(cols + [host_backed])
    wire = rb._table_to_wire_impl(t)
    assert _as_bytes(wire) == _ref_wire(Table(src + [host_backed]))
    starts = [n for kind, n in log if kind == "start"]
    reads = [n for kind, n in log if kind == "read"]
    assert sorted(starts) == sorted([
        "0.data", "0.validity", "1.data", "1.validity", "1.lengths",
        "2.data", "2.validity"])
    assert len(set(starts)) == len(starts)
    assert log.index(("read", reads[0])) > max(
        i for i, (kind, _) in enumerate(log) if kind == "start")
    # each read once, in column order
    assert sorted(reads) == sorted(starts)
    assert [r[0] for r in reads] == sorted(r[0] for r in reads)
    # the stand-ins own their memory: everything went as a view
    assert all(isinstance(b, memoryview) for b in (*wire[2], *wire[3]))


def test_a_retried_pass_is_a_pure_read(monkeypatch):
    from spark_rapids_jni_tpu.utils import faults

    t = Table([_column("list_const", True), _column("int64", True)])
    want = _ref_wire(t)
    calls = []
    real = rb._table_to_wire_impl

    def flaky(tbl):
        calls.append(1)
        if len(calls) == 1:
            real(tbl)  # a whole pass, thrown away
            raise faults.TransientDeviceError("injected")
        return real(tbl)

    monkeypatch.setattr(rb, "_table_to_wire_impl", flaky)
    assert _as_bytes(rb._table_to_wire(t)) == want
    assert len(calls) == 2 and t.columns[0].data.ndim == 2


# ---------------------------------------------------------------------------
# (g) the C ABI answers bytes
# ---------------------------------------------------------------------------


def _all_bytes(wire) -> bool:
    return all(b is None or type(b) is bytes for b in (*wire[2], *wire[3]))


@pytest.mark.parametrize("entry", [
    "table_op_wire", "table_plan_wire", "table_stream_wire",
    "table_download_wire",
])
def test_the_c_abi_entries_answer_bytes(monkeypatch, entry):
    # every leaf as memory of its own, so that nothing is bytes by chance
    real = rb._table_to_wire

    def views(t):
        out = real(Table([_host(c) for c in t.columns],
                         logical_rows=t.logical_rows))
        assert any(isinstance(b, memoryview) for b in out[2])
        return out

    monkeypatch.setattr(rb, "_table_to_wire", views)
    t = Table([_column(k, True) for k in
               ("int64", "decimal128", "string", "list_const")])
    batch = _as_bytes(real(t))
    op = json.dumps({"op": "slice", "start": 0, "stop": N})
    if entry == "table_op_wire":
        out = rb.table_op_wire(op, *batch)
    elif entry == "table_plan_wire":
        out = rb.table_plan_wire(f"[{op}]", *batch)
    elif entry == "table_stream_wire":
        (out,) = rb.table_stream_wire(f"[{op}]", [batch])
    else:
        tid = rb.table_upload_wire(*batch)
        out = rb.table_download_wire(tid)
        assert not _all_bytes(rb.table_download_views(tid))
        rb.table_free(tid)
    assert _all_bytes(out)
    assert out == _ref_wire(t)


def test_wire_bytes_leaves_bytes_as_they_are():
    b = b"abc"
    out = rb._wire_bytes(([1], [0], [b, memoryview(b"de")], [None, b], 3))
    assert out[2][0] is b and out[2][1] == b"de" and type(out[2][1]) is bytes
    assert out[3] == [None, b]


# ---------------------------------------------------------------------------
# (h) the counters and the session's stats
# ---------------------------------------------------------------------------

_NAMES = ["wire.columns_out", "wire.bytes_out", "wire.columns_out.view",
          "wire.bytes_out.view", "wire.columns_out.host",
          "wire.bytes_out.host"]


@pytest.mark.parametrize("own", [
    (), ("int64",), ("string", "float64"), ("list_const", "bool8", "int8"),
])
def test_view_counters_count_exactly_what_went_as_a_view(own):
    kinds = ["int64", "string", "float64", "list_const", "bool8", "int8"]
    cols = [_host(_column(k, True)) if k in own else _column(k, True)
            for k in kinds]
    c0 = metrics.counter_values(_NAMES)
    wire = rb._table_to_wire(Table(cols))
    c1 = metrics.counter_values(_NAMES)
    moved = {k: c1[k] - c0[k] for k in _NAMES}
    _, _, datas, valids, _ = wire
    # a STRING's or LIST's one buffer is a memoryview the host wrote:
    # its bytes were copied once and are not counted
    fixed = [k not in ("string", "list_const") for k in kinds]
    untouched = [
        sum(len(b) for b in ((d,) if f else ()) + (v,)
            if isinstance(b, memoryview))
        for f, d, v in zip(fixed, datas, valids)
    ]
    assert moved["wire.columns_out"] == len(kinds)
    assert moved["wire.columns_out.view"] == sum(n > 0 for n in untouched)
    assert moved["wire.bytes_out.view"] == sum(untouched)
    assert sum(untouched) == rb.wire_view_bytes(wire)
    # memory of its own goes as a view, whatever the backend
    assert all(isinstance(d, memoryview)
               for k, d in zip(kinds, datas) if k in own)
    assert moved["wire.columns_out.view"] >= len(own)
    assert moved["wire.bytes_out.view"] < moved["wire.bytes_out"]


def test_session_doc_carries_replies_out():
    s = Session("s1", "t", 1.0, 1 << 20)
    assert "replies_out" not in s.to_doc()
    s.note_reply_out(10, 6)
    s.note_reply_out(2, 2)
    doc = s.to_doc()
    assert doc["bytes_out"] == 12
    assert doc["replies_out"] == {
        "bytes": 12, "view_bytes": 8, "view_share": 8 / 12}


def test_a_served_session_reports_replies_out():
    t = Table([_column(k, True) for k in ("int64", "list_const", "string")])
    batch = _as_bytes(rb._table_to_wire(t))
    ops = [{"op": "slice", "start": 0, "stop": 100}]
    with serving.Server(workers=2).start() as srv:
        with serving.Client(srv.port, timeout=600.0) as c:
            assert "replies_out" not in c.stats()["sessions"][0]
            (streamed,) = c.stream(ops, [batch])
            tid = c.upload(batch)
            down = c.download(tid)
            (doc,) = c.stats()["sessions"]
    want = _ref_wire(Table(t.columns, logical_rows=None))
    assert _as_bytes(down) == want
    assert streamed[4] == 100
    out = doc["replies_out"]
    sent = sum(len(b) for w in (streamed, down)
               for b in (*w[2], *w[3]) if b is not None)
    assert out["bytes"] == doc["bytes_out"] == sent
    assert out["view_share"] == out["view_bytes"] / out["bytes"]
    # a LIST's and a STRING's one buffer was written by the host: it
    # is never among the bytes no copy touched
    assert 0 <= out["view_bytes"] <= out["bytes"] - sum(
        len(w[2][i]) for w in (streamed, down) for i in (1, 2))


# ---------------------------------------------------------------------------
# (i) a reply of views through a frame
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("logical", [None, 100])
def test_a_reply_of_views_goes_through_a_frame(logical):
    t = Table([_host(_column(k, True)) for k in KINDS if k != "list_const64"],
              logical_rows=logical)
    wire = rb._table_to_wire(t)
    assert all(isinstance(b, memoryview) for b in wire[2])
    meta, buffers = frames.batch_to_parts(wire)
    assert all(any(b is w for w in (*wire[2], *wire[3])) for b in buffers)
    a, b = socket.socketpair()
    with a, b:
        th = threading.Thread(
            target=frames.send_frame, args=(a, {"batch": meta}, buffers))
        th.start()
        header, payload = frames.recv_frame(b)
        th.join()
    back, _ = frames.batch_from_parts(header["batch"], payload, 0)
    assert _as_bytes(back) == _as_bytes(wire)
    decoded = rb._table_from_wire(*back, None)
    assert _as_bytes(rb._table_to_wire(decoded)) == _as_bytes(wire)
