"""What two tenants of one daemon must not take from each other: the
device's launch order while a groupby is between its halves.

``bucketed.groupby_turn`` keeps a served groupby's two launches
together, so that another tenant's first half is enqueued behind this
one's second and never between them (PERF.md, PR 27: the spread of
stream-c2's runs).
"""

import json
import threading
import time

import numpy as np
import pytest

from spark_rapids_jni_tpu import bucketed, dtype as dt, serving
from spark_rapids_jni_tpu import runtime_bridge as rb
from spark_rapids_jni_tpu.utils import config

I64 = int(dt.TypeId.INT64)
B8 = int(dt.TypeId.BOOL8)

GROUP = {"op": "groupby", "by": [0], "aggs": [
    {"column": 1, "agg": "sum"}, {"column": 1, "agg": "count"}]}
FILTER = {"op": "filter", "mask": 2}
CAST = {"op": "cast", "column": 1, "type_id": int(dt.TypeId.FLOAT64)}


@pytest.fixture(autouse=True)
def _clean_flags():
    yield
    config.clear_flag("BUCKETS")
    config.clear_flag("METRICS")


# ---------------------------------------------------------------------------
# the groupby turn
# ---------------------------------------------------------------------------


@pytest.fixture
def patient(monkeypatch):
    """A loaded test machine must not look like a cold compile."""
    monkeypatch.setattr(bucketed, "_TURN_WAIT_S", 60.0)


def test_a_turn_excludes_the_next_tenant_until_it_ends(patient):
    inside, release, order = threading.Event(), threading.Event(), []

    def first():
        with bucketed.groupby_turn():
            order.append("first in")
            inside.set()
            release.wait(10)
            order.append("first out")

    def second():
        inside.wait(10)
        with bucketed.groupby_turn():
            order.append("second in")

    ts = [threading.Thread(target=f) for f in (first, second)]
    for t in ts:
        t.start()
    inside.wait(10)
    time.sleep(0.05)  # the second tenant is at the turn by now
    release.set()
    for t in ts:
        t.join(10)
    assert order == ["first in", "first out", "second in"]
    assert not bucketed._GROUPBY_TURN.locked()


def test_a_turn_orders_launches_and_never_withholds_service(monkeypatch):
    # behind a cold compile or a 2^23-row sort the next tenant goes
    # unordered after a bounded wait, and leaves the holder's turn alone
    monkeypatch.setattr(bucketed, "_TURN_WAIT_S", 0.02)
    with bucketed.groupby_turn():
        done = []

        def other():
            with bucketed.groupby_turn():
                done.append(bucketed._GROUPBY_TURN.locked())

        t = threading.Thread(target=other)
        t.start()
        t.join(10)
        assert done == [True]
        assert bucketed._GROUPBY_TURN.locked()  # still the holder's
    assert not bucketed._GROUPBY_TURN.locked()


def test_a_failed_launch_gives_the_turn_back():
    with pytest.raises(RuntimeError, match="launch"):
        with bucketed.groupby_turn():
            raise RuntimeError("launch failed")
    assert not bucketed._GROUPBY_TURN.locked()


def _run_resident(ops, cols, n):
    ids = [I64, I64, B8][: len(cols)]
    tid = rb.table_upload_wire(
        ids, [0] * len(cols), [c.tobytes() for c in cols],
        [None] * len(cols), n,
    )
    out = rb.table_plan_resident(json.dumps(ops), [tid])
    got = rb.table_download_wire(out)
    rb.table_free(tid)
    rb.table_free(out)
    return got


PLANS = {
    "per_op": ([GROUP], 1),
    "fused_tail": ([FILTER, GROUP], 1),
    "no_groupby": ([FILTER, CAST], 0),
    "groupby_then_more": ([FILTER, GROUP, CAST], 1),
}


@pytest.mark.parametrize("plan,turns", PLANS.values(), ids=PLANS.keys())
def test_both_launches_of_a_served_groupby_are_inside_one_turn(
    plan, turns, monkeypatch
):
    n = 100
    rng = np.random.default_rng(5)
    cols = [rng.integers(0, 7, n, dtype=np.int64),
            rng.integers(-5, 5, n, dtype=np.int64), np.ones(n, np.uint8)]
    config.set_flag("BUCKETS", "off")
    want = _run_resident(plan, cols, n)

    taken, held_at_second = [], []
    real_turn, real_reduce = bucketed.groupby_turn, bucketed._reduce_groups

    def turn():
        taken.append(1)
        return real_turn()

    def reduce_groups(state, num_groups):
        held_at_second.append(bucketed._GROUPBY_TURN.locked())
        return real_reduce(state, num_groups)

    monkeypatch.setattr(bucketed, "groupby_turn", turn)
    monkeypatch.setattr(bucketed, "_reduce_groups", reduce_groups)
    config.set_flag("BUCKETS", "16:2")
    assert _run_resident(plan, cols, n) == want
    assert len(taken) == turns
    assert held_at_second == [True] * turns
    assert not bucketed._GROUPBY_TURN.locked()


def _batch(seed: int, n: int = 300):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 11, n, dtype=np.int64)
    v = rng.integers(-100, 100, n, dtype=np.int64)
    m = (v > -50).astype(np.uint8)
    return ([I64, I64, B8], [0, 0, 0],
            [k.tobytes(), v.tobytes(), m.tobytes()], [None] * 3, n)


def _norm(wire):
    t, s, d, v, n = wire
    return ([int(x) for x in t], [int(x) for x in s],
            [None if x is None else bytes(x) for x in d],
            [None if x is None else bytes(x) for x in v], int(n))


@pytest.mark.parametrize("tenants", [2, 3])
def test_tenants_never_launch_between_each_others_halves(
    tenants, monkeypatch, patient
):
    """Two sessions streaming filter -> groupby through one daemon: every
    first half is followed by its own second half before any other
    tenant's first, and every answer is the serial one."""
    plan = [FILTER, GROUP]
    rounds = 6
    batches = [[_batch(100 * i + r) for r in range(rounds)]
               for i in range(tenants)]
    config.set_flag("BUCKETS", "16:2")
    want = [[_norm(rb.table_plan_wire(json.dumps(plan), *b)) for b in bs]
            for bs in batches]

    events, lock = [], threading.Lock()
    real_turn, real_reduce = bucketed.groupby_turn, bucketed._reduce_groups

    class Turn:
        def __enter__(self):
            self.cm = real_turn()
            self.cm.__enter__()
            with lock:
                events.append(("first", threading.get_ident()))

        def __exit__(self, *exc):
            return self.cm.__exit__(*exc)

    def reduce_groups(state, num_groups):
        time.sleep(0.01)  # the count read: room for the other tenant
        with lock:
            events.append(("second", threading.get_ident()))
        return real_reduce(state, num_groups)

    monkeypatch.setattr(bucketed, "groupby_turn", Turn)
    monkeypatch.setattr(bucketed, "_reduce_groups", reduce_groups)
    got, errs = [None] * tenants, []
    with serving.serve() as srv:

        def run(i):
            try:
                with serving.Client(srv.port, name=f"t{i}") as c:
                    got[i] = [_norm(c.stream(plan, [b])[0])
                              for b in batches[i]]
            except BaseException as e:  # pragma: no cover - diagnostics
                errs.append(e)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(tenants)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    assert not errs, errs
    assert got == want
    assert len(events) == 2 * tenants * rounds
    for (ka, ta), (kb, tb) in zip(events[0::2], events[1::2]):
        assert (ka, kb) == ("first", "second") and ta == tb
    assert rb.resident_table_count() == 0
