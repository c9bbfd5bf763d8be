"""The hash exchange under skewed keys (perfbench's ``ss-skew-zipf13``):
``filter -> partition(hash, item, 4)`` served to a ``mesh=4`` session on
four of the CPU's virtual devices, with the item key drawn from a
truncated Zipf distribution by ``perfbench/plugins/gen_zipf.py``.

What is held here: the served partition equals the plain reference
value for value, whatever the skew (the configuration's own Zipf(1.3),
one key holding over half the rows, every row on one key so that three
devices receive nothing) and with the hot key, and the device that owns
it, moving between consecutive batches of one session; a batch whose
rounded capacities the session has met builds nothing; the ``stats``
document's ``mesh_plan`` (the capacities the stage chose and what they
cost in padding) and the five counters beside it equal what the
reference's partition ids give by hand; uniform keys observe no skew.
The guarantee under all of it: every row of a key on the device
``pmod(murmur3(key, 42), 4)`` names, in input order, every kept row once.
"""

import copy
import json
import os

import jax
import numpy as np
import pytest

from perfbench import compare, datagen, reference, script
from perfbench.plugins import gen_zipf
from spark_rapids_jni_tpu import serving
from spark_rapids_jni_tpu.utils import buckets, config, metrics

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four virtual devices"
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 4
SKEW_FACTOR = 2.0  # SKEW_SPLIT_FACTOR's default: max recv over the mean
COUNTERS = [
    "mesh.exchange.slot_rows", "mesh.exchange.recv_rows",
    "mesh.gather.rows_read", "mesh.gather.rows_kept", "mesh.skew_observed",
    "compile_cache.miss", "shuffle.retries", "plan.mesh_fallbacks",
    "mesh.degraded", "plan.mesh_segments", "partition.rows_exchanged",
]


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "ss-skew-zipf13.json")
UNIFORM = load("configs", "ss-star-8m.json")
TRAFFIC = load("traffic", "exchange-mesh4.json")
ZIPF13 = CONFIG["tables"]["fact"]["columns"][0]["gen"]

# name -> (the item key's generator, rows a batch)
CASES = {
    "zipf13": (ZIPF13, 20000),
    "one_key_over_half": ({"kind": "zipf", "of": 10000, "s": 2.5}, 12000),
    "one_key_one_chip": ({"kind": "zipf", "of": 1, "s": 1.3}, 6000),
    "uniform": (UNIFORM["tables"]["fact"]["columns"][0]["gen"], 20000),
}


@pytest.fixture(autouse=True)
def _metrics_on():
    config.set_flag("METRICS", True)
    yield
    config.clear_flag("METRICS")


def _config(gen: dict, rows: int) -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg["tables"]["fact"]["columns"][0]["gen"] = gen
    cfg["rehearse_rows"] = {"fact": rows}
    return cfg


def _round(exact: int) -> int:
    """The next power of two, 16 at least: what the stage rounds to."""
    return max(16, 1 << (exact - 1).bit_length())


def _by_hand(batch) -> dict:
    """What the stage has to plan for ``batch``, from the reference's
    partition ids alone: receive rows a device, the two capacities and
    the sizes the counters count."""
    n = len(batch[0].values)
    keep = batch[4].values.astype(bool)
    h = reference.murmur3_long(batch[0].values).astype(np.int64)
    dest = np.mod(np.mod(h, SIZE) + SIZE, SIZE)  # 4 partitions, 4 devices
    recv = np.bincount(dest[keep], minlength=SIZE)
    # a shard is a contiguous block of the bucket of ceil(n / size) rows
    per = buckets.bucket_for(-(-n // SIZE))
    pair = max(
        np.bincount(dest[s:s + per][keep[s:s + per]], minlength=SIZE).max()
        for s in range(0, n, per)
    )
    cap = _round(int(recv.max()))
    return {
        "rows": n, "kept": int(keep.sum()), "recv": recv.tolist(),
        "cap": cap, "pair_cap": _round(int(pair)),
        "slot_rows": SIZE * cap,
        "pad_share": 1.0 - int(keep.sum()) / (SIZE * cap),
        "skewed": int(recv.max() > SKEW_FACTOR * recv.mean()),
        "hot_key": int(np.bincount(batch[0].values).argmax()),
        "hot_device": int(recv.argmax()),
    }


def _serve(cfg: dict, seed: int, variants: int):
    """Each variant's batch once, in order, through ONE ``mesh=4``
    session, the way ``perfbench.run`` sends it -> a record a request:
    the answers, the counters it moved, the session's ``stats``
    document behind it and the batch."""
    traffic = dict(TRAFFIC, variants=variants)
    data = script.Data(cfg, traffic, seed, rehearse=True)
    out = []
    with serving.Server(workers=2).start() as srv:
        with serving.Client(srv.port, timeout=600.0, mesh=SIZE) as c:
            s = script.Session(c, data, traffic["request"])
            for v in range(variants):
                c0 = metrics.counter_values(COUNTERS)
                got = s.request(v)
                c1 = metrics.counter_values(COUNTERS)
                (doc,) = [x for x in c.stats()["sessions"]
                          if x.get("mesh_devices") == SIZE]
                out.append({
                    "got": got, "env": data.env(v), "session": doc,
                    "moved": {k: c1[k] - c0[k] for k in COUNTERS},
                })
    return traffic, out


def _assert_equals_reference(traffic, rec) -> None:
    want = reference.run_request(traffic["request"], rec["env"])
    assert sorted(rec["got"]) == sorted(want) == ["parts"]
    r = compare.compare(rec["got"]["parts"], want["parts"],
                        traffic["answers"]["parts"], 0.0)
    assert r["mismatched"] == 0


def _assert_plan_and_counters(rec, hand) -> None:
    doc, moved = rec["session"], rec["moved"]
    assert doc["mesh_recv"]["rows"] == hand["recv"]
    plan = doc["mesh_plan"]
    assert (plan["cap"], plan["pair_cap"], plan["slot_rows"]) == (
        hand["cap"], hand["pair_cap"], hand["slot_rows"])
    assert plan["pad_share"] == pytest.approx(hand["pad_share"], abs=1e-12)
    assert moved["mesh.exchange.slot_rows"] == hand["slot_rows"]
    assert moved["mesh.exchange.recv_rows"] == hand["kept"]
    # the gather reads every column whole and cuts the prefixes on the host
    assert moved["mesh.gather.rows_read"] == hand["slot_rows"]
    assert moved["mesh.gather.rows_kept"] == hand["kept"]
    assert moved["mesh.skew_observed"] == hand["skewed"]
    assert moved["plan.mesh_segments"] == 1
    assert moved["partition.rows_exchanged"] == hand["rows"]
    for k in ("shuffle.retries", "plan.mesh_fallbacks", "mesh.degraded"):
        assert moved[k] == 0, k


@pytest.mark.parametrize("name", sorted(CASES))
def test_served_partition_equals_the_reference(name):
    gen, rows = CASES[name]
    traffic, (rec,) = _serve(_config(gen, rows), seed=2147483659, variants=1)
    _assert_equals_reference(traffic, rec)
    hand = _by_hand(rec["env"]["batch"])
    _assert_plan_and_counters(rec, hand)
    share = max(hand["recv"]) / hand["kept"]
    imbalance = rec["session"]["mesh_recv"]["imbalance"]
    assert imbalance == pytest.approx(share * SIZE)
    if name == "one_key_over_half":
        top = np.bincount(rec["env"]["batch"][0].values).max() / rows
        assert top > 0.5 and share > 0.5 and hand["skewed"] == 1
    elif name == "one_key_one_chip":
        # three devices receive nothing; the fourth every kept row
        assert sorted(hand["recv"]) == [0, 0, 0, hand["kept"]]
        assert imbalance == pytest.approx(SIZE) and hand["skewed"] == 1
    elif name == "uniform":
        assert imbalance < 1.1 and hand["skewed"] == 0
    else:
        assert share > 1 / 3


def test_the_hot_key_moves_between_batches_of_one_session():
    """Six batches of one session, a permutation of the keys each: the
    hot key differs from batch to batch and the device that owns it
    moves; every answer equals the reference; a batch whose rounded
    capacities the session has met before builds nothing, though its
    receive rows are its own."""
    traffic, recs = _serve(_config(ZIPF13, 20000), seed=11, variants=6)
    hands = [_by_hand(r["env"]["batch"]) for r in recs]
    assert len({h["hot_key"] for h in hands}) == len(hands)
    assert len({h["hot_device"] for h in hands}) > 1
    assert len({tuple(h["recv"]) for h in hands}) == len(hands)
    met = set()
    for rec, hand in zip(recs, hands):
        _assert_equals_reference(traffic, rec)
        _assert_plan_and_counters(rec, hand)
        caps = (hand["cap"], hand["pair_cap"])
        # the counts program is one for all; the exchange program one a
        # pair of capacities (this process may have built either before)
        built = rec["moved"]["compile_cache.miss"]
        assert built == 0 if caps in met else built <= 2
        met.add(caps)
    assert len(met) < len(hands)  # some batch did meet its capacities again


def test_mesh_plan_is_a_sibling_of_mesh_recv():
    """``mesh_plan`` is a field of its own beside ``mesh_recv``, which
    keeps its two keys: a reader of either finds what it found."""
    from spark_rapids_jni_tpu.serving.session import Session

    s = Session("s1", "t", 1.0, 1 << 20)
    assert "mesh_recv" not in s.to_doc() and "mesh_plan" not in s.to_doc()
    s.note_mesh_recv(np.array([3, 1, 0, 0]), 16, 32)
    doc = s.to_doc()
    assert doc["mesh_recv"] == {"rows": [3, 1, 0, 0], "imbalance": 3.0}
    assert doc["mesh_plan"] == {
        "cap": 16, "pair_cap": 32, "slot_rows": 64, "pad_share": 1.0 - 4 / 64}


# the generator ---------------------------------------------------------------


def _zipf_column(seed, n=1_000_000, spec=ZIPF13):
    return gen_zipf.make(spec, n, np.random.default_rng(seed), {}, np.int64)


def test_gen_zipf_same_seed_same_column():
    a, b = _zipf_column(5, 50000), _zipf_column(5, 50000)
    assert a.dtype == np.int64 and (a == b).all()


def test_gen_zipf_keys_lie_within_the_truncation():
    a = _zipf_column(6, 200000)
    assert a.min() >= 0 and a.max() < ZIPF13["of"]
    assert len(np.unique(a)) > ZIPF13["of"] // 4  # and the tail is there


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
def test_gen_zipf_top_rank_holds_one_over_h(seed):
    """The hottest key's share of a million rows is ``1 / H`` to 1%
    (H = the sum of ``k^-1.3`` over 10,000 ranks, 3.72: 26.9%), and the
    second's ``2^-1.3`` of that."""
    h = float((np.arange(1, ZIPF13["of"] + 1) ** -ZIPF13["s"]).sum())
    assert h == pytest.approx(3.72, abs=0.01)
    freq = np.sort(np.bincount(_zipf_column(seed)))[::-1] / 1_000_000
    assert freq[0] == pytest.approx(1 / h, rel=0.01)
    assert freq[1] == pytest.approx(2 ** -ZIPF13["s"] / h, rel=0.02)
    assert gen_zipf.weights(ZIPF13["of"], ZIPF13["s"])[0] == pytest.approx(1 / h)


def test_gen_zipf_another_rng_another_hot_key():
    hot = {int(np.bincount(_zipf_column(seed, 50000)).argmax())
           for seed in range(6)}
    assert len(hot) == 6


def test_the_configuration_s_table_is_the_control_s_but_for_the_key():
    """33-byte rows, the control's four other generators letter for
    letter: the two cells differ in one generator of one column."""
    mine, theirs = CONFIG["tables"]["fact"], UNIFORM["tables"]["fact"]
    assert mine["rows"] == theirs["rows"] == 8_000_000
    assert [c["type"] for c in mine["columns"]] == [
        "INT64", "INT64", "INT64", "FLOAT64", "BOOL8"]
    assert mine["columns"][1:] == theirs["columns"][1:]
    assert mine["columns"][0]["gen"] == {"kind": "zipf", "of": 10000, "s": 1.3}
    assert CONFIG["guarantees"]["float64_sum_tol"] == \
        UNIFORM["guarantees"]["float64_sum_tol"]
    t = datagen.make_table(mine, 1000, np.random.default_rng(3))
    assert sum(c.values.dtype.itemsize for c in t) == 33
