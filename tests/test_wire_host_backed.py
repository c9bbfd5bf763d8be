"""The wire over host-backed columns (``runtime_bridge._table_to_wire``).

A mesh stage hands the wire a table whose leaves are ``numpy`` buffers
(``planmesh._gather_prefix``). Held here: the 5-tuple is the one its
``jax.device_put`` twin gives, byte for byte, with and without
``logical_rows``, for fixed-width, BOOL8, FLOAT64, DECIMAL128, STRING
and LIST columns; a fixed-width column's data is a view of the buffer it
was given, not a copy, and goes through a frame as it is;
``wire.columns_out.host`` / ``wire.bytes_out.host`` count exactly the
host-backed columns; the served ``stream`` of a ``mesh=4`` session
reports ``mesh_reply`` in ``stats`` and a one-device session does not.
"""

import socket
import threading

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plan as plan_mod
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.serving import frames
from spark_rapids_jni_tpu.serving.session import Session
from spark_rapids_jni_tpu.utils import config, metrics

N = 257


@pytest.fixture(autouse=True)
def _metrics_on():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("METRICS")
    metrics.reset()


def _column(kind: str) -> Column:
    rng = np.random.default_rng(len(kind))
    valid = np.arange(N) % 5 != 0
    some = [i if ok else None for i, ok in enumerate(valid)]
    if kind == "int64":
        return Column.from_numpy(
            rng.integers(-10**15, 10**15, N, dtype=np.int64), valid)
    if kind == "int32_no_nulls":
        return Column.from_numpy(rng.integers(-9, 9, N).astype(np.int32))
    if kind == "bool8":
        return Column.from_numpy(rng.integers(0, 2, N) > 0, valid)
    if kind == "float64":
        return Column.from_numpy(rng.normal(size=N) * 1e6, valid)
    if kind == "decimal128":
        return Column.from_decimal128(
            [None if v is None else (v - 99) * 10**25 for v in some], -2)
    if kind == "string":
        return Column.from_strings(
            [None if v is None else "s" * (v % 9) for v in some])
    if kind == "list":
        return Column.from_list_of_lists(
            [None if v is None else list(range(v % 6)) for v in some],
            dt.INT32)
    raise KeyError(kind)


KINDS = ["int64", "int32_no_nulls", "bool8", "float64", "decimal128",
         "string", "list"]


def _host(c: Column) -> Column:
    """``c`` as the gather leaves it: every leaf a numpy array of the
    device storage dtype."""
    return jax.tree_util.tree_map(lambda x: np.array(x), c)


def _as_bytes(wire):
    t, s, d, v, n = wire
    return (t, s, [None if x is None else bytes(x) for x in d],
            [None if x is None else bytes(x) for x in v], n)


@pytest.mark.parametrize("logical", [None, 100, 0])
@pytest.mark.parametrize("kind", KINDS)
def test_host_backed_wire_equals_its_device_twin(kind, logical):
    dev = _column(kind)
    host = _host(dev)
    assert all(isinstance(x, np.ndarray) and not isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(host))
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(dev))
    got = rb._table_to_wire(Table([host], logical_rows=logical))
    want = rb._table_to_wire(Table([dev], logical_rows=logical))
    assert got[4] == want[4] == (N if logical is None else logical)
    # equal as the tuples they are, and as plain bytes
    assert got == want
    assert _as_bytes(got) == _as_bytes(want)


@pytest.mark.parametrize("kind", ["int64", "bool8", "float64", "decimal128"])
def test_fixed_width_data_is_the_given_buffer_not_a_copy(kind):
    host = _host(_column(kind))
    _, _, (data,), _, _ = rb._table_to_wire(Table([host], logical_rows=100))
    assert isinstance(data, memoryview) and data.format == "B"
    assert np.shares_memory(np.frombuffer(data, np.uint8), host.data)
    assert len(data) == 100 * host.data[0:1].nbytes
    # the device twin's holds equal bytes (a copy where np.asarray
    # aliases the device's buffer, a view of a host copy where it does
    # not: tests/test_wire_views.py)
    _, _, (twin,), _, _ = rb._table_to_wire(Table([_column(kind)]))
    assert bytes(twin)[:len(data)] == bytes(data)


def test_a_byte_view_goes_through_a_frame_as_it_is():
    host = _host(_column("int64"))
    wire = rb._table_to_wire(Table([host, _host(_column("string"))]))
    meta, buffers = frames.batch_to_parts(wire)
    assert buffers[0] is wire[2][0]  # no copy on the way to the socket
    assert all(isinstance(b, (bytes, memoryview)) for b in buffers)
    a, b = socket.socketpair()
    with a, b:
        t = threading.Thread(
            target=frames.send_frame, args=(a, {"batch": meta}, buffers))
        t.start()
        header, payload = frames.recv_frame(b)
        t.join()
    back, _ = frames.batch_from_parts(header["batch"], payload, 0)
    assert _as_bytes(back) == _as_bytes(wire)


def _moved(fn):
    names = ["wire.columns_out.host", "wire.columns_out",
             "wire.bytes_out.host", "wire.bytes_out"]
    c0 = metrics.counter_values(names)
    fn()
    c1 = metrics.counter_values(names)
    return {k: c1[k] - c0[k] for k in names}


@pytest.mark.parametrize("host_kinds", [
    (), ("int64",), ("string", "float64"), tuple(KINDS),
])
def test_host_counters_count_exactly_the_host_backed_columns(host_kinds):
    cols = [_host(_column(k)) if k in host_kinds else _column(k)
            for k in KINDS]
    t = Table(cols)
    wire = []
    moved = _moved(lambda: wire.append(rb._table_to_wire(t)))
    _, _, datas, valids, _ = wire[0]
    size = {
        k: len(d) + (0 if v is None else len(v))
        for k, d, v in zip(KINDS, datas, valids)
    }
    assert moved["wire.columns_out"] == len(KINDS)
    assert moved["wire.bytes_out"] == sum(size.values())
    assert moved["wire.columns_out.host"] == len(host_kinds)
    assert moved["wire.bytes_out.host"] == sum(size[k] for k in host_kinds)
    assert rb._reply_host_bytes(t, wire[0]) == (
        sum(size.values()), sum(size[k] for k in host_kinds))


def test_session_doc_carries_the_last_mesh_reply():
    s = Session("s1", "t", 1.0, 1 << 20)
    assert "mesh_reply" not in s.to_doc()
    s.note_mesh_reply(400, 100)
    s.note_mesh_reply(800, 800)
    assert s.to_doc()["mesh_reply"] == {
        "bytes": 800, "host_bytes": 800, "host_share": 1.0}
    s.note_mesh_reply(0, 0)  # an empty reply divides nothing
    assert s.to_doc()["mesh_reply"]["host_share"] == 0.0


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four virtual devices")
@pytest.mark.parametrize("mesh", [4, None])
def test_served_stream_reports_mesh_reply_for_a_mesh_session_only(mesh):
    rng = np.random.default_rng(7)
    n = 3000
    t = Table.from_pydict({
        "k": rng.integers(0, 50, n, dtype=np.int64),
        "p": rng.normal(size=n),
        "m": rng.integers(0, 4, n) > 0,
    })
    batch = rb._table_to_wire(t)
    ops = [{"op": "filter", "mask": 2},
           {"op": "partition", "kind": "hash", "keys": [0], "num": 4}]
    with serving.Server(workers=2).start() as srv:
        with serving.Client(srv.port, timeout=600.0, mesh=mesh) as c:
            outs = []
            moved = _moved(lambda: outs.extend(c.stream(ops, [batch])))
            (out,) = outs
            (doc,) = c.stats()["sessions"]
    want = rb._table_to_wire(plan_mod.run_plan(ops, t))
    assert _as_bytes(out) == _as_bytes(want)
    nbytes = sum(len(d) for d in out[2])
    if mesh:
        assert doc["mesh_reply"] == {
            "bytes": nbytes, "host_bytes": nbytes, "host_share": 1.0}
        # the reply's two columns, and nothing of the request's upload
        assert moved["wire.columns_out.host"] == 2
        assert moved["wire.bytes_out.host"] == nbytes
    else:
        assert "mesh_reply" not in doc
        assert moved["wire.columns_out.host"] == 0
        assert moved["wire.bytes_out.host"] == 0
