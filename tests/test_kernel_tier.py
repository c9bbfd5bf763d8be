"""Kernel tier tests (kernels/registry.py): predicate matrix, byte
parity against the bucketed/exact path at the bucket edges, fallback
discipline under injected kernel faults, the <5 µs disabled-path gate,
and independent compile caching for kernel vs non-kernel callables.

Everything runs with ``interpret=True`` on the CPU tier — the same
kernel code the TPU compiles through Mosaic (kernels/__init__.py
``default_interpret``)."""

import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import dtype as dt
from spark_rapids_jni_tpu import plancheck as pc
from spark_rapids_jni_tpu import planops
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.kernels import registry
from spark_rapids_jni_tpu.utils import buckets, config, metrics

# the acceptance bucket edges: below / at / above a pow2 bucket
EDGES = (1023, 1024, 1025)

# kernels the chip's compiler refused (CHANGES.md PR 23), with their op
UNREGISTERED = (
    ("packed_sort", "sort_by"),
    ("hash_build_probe", "join"),
    ("hash_groupby", "groupby"),
)


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    for f in ("KERNELS", "FAULTS", "METRICS", "BUCKETS"):
        config.clear_flag(f)
    metrics.reset()


def _table(n, *, seed=0, null_vals=True, key_nulls=False, neg=True):
    """Two-column (k int64, v int64) table; v optionally nullable."""
    rng = np.random.default_rng(seed)
    lo = -1000 if neg else 0
    k = rng.integers(lo, 1000, n, dtype=np.int64)
    v = rng.integers(-50, 50, n, dtype=np.int64)
    kv = rng.random(n) > 0.2 if key_nulls else None
    vv = rng.random(n) > 0.3 if null_vals else None
    return Table(
        [Column.from_numpy(k, validity=kv),
         Column.from_numpy(v, validity=vv)],
        ["k", "v"],
    )


def _wire(t):
    """The padding-stripped wire 5-tuple — the byte-parity comparator
    (logical rows only; the bucket-padding region is free)."""
    return rb._table_to_wire(t)


def _ab(op, table, rest=()):
    """Dispatch once with kernels ON and once OFF; assert byte parity
    and return the ON-side wire tuple + the kernel counters."""
    config.set_flag("METRICS", "1")
    config.set_flag("KERNELS", "off")
    off = _wire(planops.dispatch(op, table, rest))
    metrics.reset()
    config.set_flag("KERNELS", "on")
    on = _wire(planops.dispatch(op, table, rest))
    ctr = dict(metrics.snapshot().get("counters", {}))
    assert on == off, f"kernel tier changed bytes for {op}"
    return on, ctr


def _launched(ctr):
    return int(ctr.get("kernel.launches", 0))


# ---------------------------------------------------------------------------
# predicate matrix
# ---------------------------------------------------------------------------


class TestPredicates:
    def test_registry_names_match_specs(self):
        assert registry.KERNEL_NAMES == frozenset(registry._REGISTRY)
        for name, spec in registry._REGISTRY.items():
            assert spec.name == name
            assert spec.ops, name
            assert callable(spec.applicable) and callable(spec.runner)

    def test_registry_matches_plancheck_rules(self):
        # the SRT012 parity triple, dynamically
        assert registry.KERNEL_NAMES == frozenset(pc._KERNEL_RULES)
        for kname, (opname, _) in pc._KERNEL_RULES.items():
            assert opname in registry._REGISTRY[kname].ops

    @pytest.mark.parametrize("kname,opname", UNREGISTERED)
    def test_refused_kernel_is_absent(self, kname, opname):
        # Mosaic refuses these kernels for a v5e (in-kernel gathers),
        # so they are no entries: nothing to try-and-catch on the chip
        assert kname not in registry._REGISTRY
        assert kname not in registry.KERNEL_NAMES
        assert kname not in pc._KERNEL_RULES
        assert registry.kernel_for_op(opname) == []

    def test_rows_predicates(self):
        t = _table(16)
        assert registry._a_row_pack({"op": "to_rows"}, t, ()) is None
        st = Table([Column.from_strings(["a", "b"])])
        assert "no fixed-width" in registry._a_row_pack(
            {"op": "to_rows"}, st, ())
        packed = planops.dispatch({"op": "to_rows"}, t, ())
        unp = {"op": "from_rows",
               "type_ids": [int(dt.TypeId.INT64)] * 2, "scales": [0, 0]}
        assert registry._a_row_unpack(unp, packed, ()) is None
        assert "legacy flat" in registry._a_row_unpack(unp, t, ())

    def test_plancheck_tags_and_kernel_ops(self):
        sch = [pc.ColType(dt.TypeId.INT64), pc.ColType(dt.TypeId.INT64)]
        rep = pc.analyze(
            [{"op": "sort_by", "keys": [{"column": 0}]},
             {"op": "groupby", "by": [0],
              "aggs": [{"column": 1, "agg": "sum"}]},
             {"op": "to_rows"}],
            schema=sch, rows=500,
        )
        tags = [e["kernel"] for e in rep["ops"]]
        assert tags == [None, None, "row_pack"]
        assert rep["kernel_ops"] == [2]
        txt = pc.render_report(rep)
        assert "~kernel:row_pack" in txt
        assert "~kernel:packed_sort" not in txt
        # a string key is statically ineligible, and stays untagged
        rep2 = pc.analyze(
            [{"op": "sort_by", "keys": [{"column": 0}]}],
            schema=[pc.ColType(dt.TypeId.STRING)], rows=10,
        )
        assert rep2["ops"][0]["kernel"] is None
        assert rep2["kernel_ops"] == []


# ---------------------------------------------------------------------------
# byte parity at the bucket edges
# ---------------------------------------------------------------------------


class TestParity:
    @pytest.mark.parametrize("n", EDGES)
    def test_sort_launches_no_kernel(self, n):
        t = _table(n, seed=n)
        op = {"op": "sort_by",
              "keys": [{"column": 0, "ascending": False}]}
        _, ctr = _ab(op, t)
        assert not any(k.startswith("kernel.") for k in ctr)

    @pytest.mark.parametrize("n", EDGES)
    def test_groupby_launches_no_kernel(self, n):
        t = _table(n, seed=n + 7)
        op = {"op": "groupby", "by": [0],
              "aggs": [{"column": 1, "agg": "sum"},
                       {"column": 1, "agg": "count"},
                       {"column": 1, "agg": "min"},
                       {"column": 1, "agg": "max"}]}
        _, ctr = _ab(op, t)
        assert not any(k.startswith("kernel.") for k in ctr)

    @pytest.mark.parametrize("how", ["inner", "semi", "anti"])
    def test_join_launches_no_kernel(self, how):
        rng = np.random.default_rng(5)
        bk = rng.permutation(4096)[:1000].astype(np.int64)
        r = Table([Column.from_numpy(bk),
                   Column.from_numpy(
                       rng.integers(0, 9, 1000, dtype=np.int64))],
                  ["k", "p"])
        l = _table(1023, seed=11, neg=False)
        op = {"op": "join", "on": [0], "how": how}
        _, ctr = _ab(op, l, [r])
        assert not any(k.startswith("kernel.") for k in ctr)

    @pytest.mark.parametrize("n", EDGES)
    def test_rows_round_trip_parity(self, n):
        t = _table(n, seed=n + 3)
        _, ctr = _ab({"op": "to_rows"}, t)
        assert _launched(ctr) == 1
        config.set_flag("KERNELS", "off")
        packed = planops.dispatch({"op": "to_rows"}, t, ())
        op = {"op": "from_rows",
              "type_ids": [int(dt.TypeId.INT64)] * 2, "scales": [0, 0]}
        _, ctr = _ab(op, packed)
        assert _launched(ctr) == 1

    def test_fuzz_small_buckets(self):
        """Many sizes across a shrunken bucket ladder: padding/occupancy
        masks exercised at every edge."""
        config.set_flag("BUCKETS", "8,64,512,2048")
        try:
            unp = {"op": "from_rows",
                   "type_ids": [int(dt.TypeId.INT64)] * 2,
                   "scales": [0, 0]}
            for n in (1, 7, 8, 9, 63, 65, 511, 513, 700):
                t = _table(n, seed=n)
                _, ctr = _ab({"op": "to_rows"}, t)
                assert _launched(ctr) == 1
                config.set_flag("KERNELS", "off")
                packed = planops.dispatch({"op": "to_rows"}, t, ())
                _, ctr = _ab(unp, packed)
                assert _launched(ctr) == 1
        finally:
            config.clear_flag("BUCKETS")
            buckets.cache_clear()

    def test_decline_adds_no_counters_for_uncovered_op(self):
        t = _table(64)
        config.set_flag("METRICS", "1")
        config.set_flag("KERNELS", "on")
        metrics.reset()
        planops.dispatch({"op": "filter", "mask": 1}, Table(
            [t.columns[0],
             Column.from_numpy(np.ones(64, dtype=np.bool_))]), ())
        ctr = metrics.snapshot().get("counters", {})
        assert not any(k.startswith("kernel.") for k in ctr)


# ---------------------------------------------------------------------------
# fallback discipline (chaos site "kernel")
# ---------------------------------------------------------------------------


class TestFallback:
    def test_injected_fault_falls_back_byte_identical(self):
        t = _table(1024, seed=2)
        op = {"op": "to_rows"}
        config.set_flag("KERNELS", "off")
        want = _wire(planops.dispatch(op, t, ()))
        config.set_flag("METRICS", "1")
        config.set_flag("KERNELS", "on")
        config.set_flag("FAULTS", "seed=3,kernel:permanent:1:1")
        live_before = len(rb._RESIDENT)
        metrics.reset()
        got = _wire(planops.dispatch(op, t, ()))
        ctr = metrics.snapshot().get("counters", {})
        assert got == want
        assert int(ctr.get("kernel.fallbacks", 0)) == 1
        assert int(ctr.get("kernel.launches", 0)) == 0
        # no leaked resident tables from the failed launch
        assert len(rb._RESIDENT) == live_before
        # the one-shot rule is spent: the next dispatch launches
        got2 = _wire(planops.dispatch(op, t, ()))
        assert got2 == want
        assert int(metrics.snapshot()["counters"].get(
            "kernel.launches", 0)) == 1

    def test_cancellation_propagates(self):
        from spark_rapids_jni_tpu.utils import faults

        t = _table(256, seed=4)
        config.set_flag("KERNELS", "on")
        # a permanent fault is swallowed into a fallback; Cancelled
        # must NOT be (cooperative cancellation wins over fallback)
        assert registry.dispatch_kernel(
            {"op": "to_rows"}, t, (), "to_rows"
        ) is not None
        with pytest.raises(faults.Cancelled):
            spec = registry._REGISTRY["row_pack"]

            def boom(op, table, rest):
                raise faults.Cancelled("stop")

            object.__setattr__(spec, "runner", boom)
            try:
                registry.dispatch_kernel(
                    {"op": "to_rows"}, t, (), "to_rows",
                )
            finally:
                object.__setattr__(
                    spec, "runner", registry._r_row_pack)


# ---------------------------------------------------------------------------
# gates: disabled-path cost + independent compile caching
# ---------------------------------------------------------------------------


class TestGates:
    def test_disabled_path_under_5us(self):
        config.set_flag("KERNELS", "off")
        t = _table(64)
        op = {"op": "to_rows"}
        registry.dispatch_kernel(op, t, (), "to_rows")  # warm the gate
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            registry.dispatch_kernel(op, t, (), "to_rows")
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, f"disabled path {per_call * 1e6:.2f}µs"

    def test_auto_gate_follows_the_platform_and_never_guesses(
        self, monkeypatch
    ):
        """``auto`` = on exactly when the default device's platform is
        "tpu"; a backend that cannot initialize raises instead of
        answering "not a TPU" (which would run kernels interpreted and
        call it a TPU run)."""
        import types

        import jax

        from spark_rapids_jni_tpu import kernels

        t = _table(64)
        op = {"op": "to_rows"}
        config.set_flag("KERNELS", "auto")
        assert registry.dispatch_kernel(op, t, (), "to_rows") is None
        assert kernels.default_interpret() is True

        fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5e")
        monkeypatch.setattr(jax, "devices", lambda *a: [fake])
        assert kernels.on_tpu() is True
        assert kernels.default_interpret() is False

        def broken(*a):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", broken)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            kernels.on_tpu()
