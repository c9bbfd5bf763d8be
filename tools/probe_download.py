"""What a reply's download is made of, on the chip (PR 48's probe).

Stand-alone: JAX, numpy and ``runtime_bridge``'s own selection
(``_own_memory``, ``_as_wire``, ``_padded_to_offsets``), outside the
daemon. Run it on a one-chip machine: ``python tools/probe_download.py``.
It prints, for c2r-r2c's shapes (``u8[4000000,56]`` packed rows; eight
fixed-width columns with validity), seconds a repeat on fresh device
arrays (``np.asarray`` caches its host copy):

* what ``np.asarray`` of a 1-D and of a 2-D device leaf is on the host
  (strides, ``owndata``, ``base``, writeable): the premises
  ``_as_wire`` selects on;
* the rows' transfer, the old ``tobytes()`` of what arrived, and the one
  ``_padded_to_offsets`` copy from the layout that arrived against the
  same from a row-major matrix;
* the device flatten PR 48 tried and deleted (``reshape(-1)`` before the
  transfer), for the record of what it would buy;
* the columns read one after the other against every transfer started
  first.

``PERF.md`` section 7 holds the readings.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import runtime_bridge as rb

N, W, REPS = 4_000_000, 56, 3


def fresh(i):
    m = jax.random.randint(jax.random.PRNGKey(i), (N, W), 0, 255, jnp.int32)
    return jax.block_until_ready(m.astype(jnp.uint8))


def columns(i):
    k = jax.random.PRNGKey(100 + i)
    kinds = [jnp.int64, jnp.uint64, jnp.int32, jnp.uint8, jnp.float32,
             jnp.int8, jnp.int32, jnp.int64]
    leaves = []
    for j, d in enumerate(kinds):
        v = jax.random.randint(jax.random.fold_in(k, j), (N,), 0, 100)
        leaves += [v.astype(d), v % 2 == 0]
    return jax.block_until_ready(leaves)


def clock(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def describe(name, h):
    print(name, "strides", h.strides, "owndata", h.flags.owndata,
          "base", type(h.base).__name__, "writeable", h.flags.writeable,
          "own_memory", rb._own_memory(h),
          "as_wire", type(rb._as_wire(h)).__name__, flush=True)


def main():
    jax.config.update("jax_enable_x64", True)
    print(jax.devices(), fresh(0).format, flush=True)
    describe("1-D i64", np.asarray(jnp.arange(N, dtype=jnp.int64) + 1))
    describe("1-D bool", np.asarray(jnp.arange(N) % 3 == 0))
    describe("2-D u8", np.asarray(fresh(1)))
    describe("2-D u64[n,2]",
             np.asarray(jnp.ones((N, 2), jnp.uint64) + jnp.uint64(1)))

    lens = np.full((N,), W, np.int32)
    flat = jax.jit(lambda m: m.reshape(-1))
    jax.block_until_ready(flat(fresh(2)))
    for i in range(REPS):
        h, read = clock(lambda: np.asarray(fresh(10 + i)))
        _, old = clock(h.tobytes)
        _, one = clock(lambda: rb._padded_to_offsets(h, lens))
        _, c_one = clock(lambda: rb._padded_to_offsets(
            np.ascontiguousarray(h), lens))
        m = fresh(20 + i)
        _, dev = clock(lambda: jax.block_until_ready(flat(m)))
        print("rows: transfer", read, "tobytes", old,
              "one copy as arrived", one, "ascontiguous + one copy", c_one,
              "device flatten", dev, flush=True)

    for i in range(REPS):
        ls = columns(i)
        hs, serial = clock(lambda: [np.asarray(x) for x in ls])
        _, copies = clock(lambda: [h.tobytes() for h in hs])
        ls = columns(10 + i)

        def together():
            for x in ls:
                x.copy_to_host_async()
            return [rb._as_wire(np.asarray(x)) for x in ls]

        _, overlapped = clock(together)
        print("columns: serial reads", serial, "tobytes", copies,
              "started together, as views", overlapped, flush=True)

    # a view outlives the device buffer it was read from
    x = jax.block_until_ready(jnp.arange(N, dtype=jnp.int64) * 3)
    v = rb._as_wire(np.asarray(x))
    want = bytes(v[:4096])
    x.delete()
    jax.block_until_ready([jnp.arange(N, dtype=jnp.int64) + i
                           for i in range(4)])
    print("view after delete: equal", bytes(v[:4096]) == want,
          "as", type(v).__name__, flush=True)


if __name__ == "__main__":
    main()
