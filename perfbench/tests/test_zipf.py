"""The ``ss-skew-zipf13`` pieces, against values known from outside: the
truncated Zipf's weights, the generator's columns, and the share of the
kept rows the hottest of four hash partitions receives: the band the
configuration states, at the rehearsal size and at the real one."""

import json
import os

import numpy as np
import pytest

from perfbench import reference, script
from perfbench.plugins import gen_zipf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "ss-skew-zipf13.json")
TRAFFIC = load("traffic", "exchange-mesh4.json")
GEN = CONFIG["tables"]["fact"]["columns"][0]["gen"]


def recv_of(batch) -> np.ndarray:
    """Kept rows a partition: Spark's pmod(murmur3(item, 42), 4)."""
    keep = batch[4].values.astype(bool)
    h = reference.murmur3_long(batch[0].values).astype(np.int64)
    return np.bincount(np.mod(np.mod(h, 4) + 4, 4)[keep], minlength=4)


def test_weights_are_the_truncated_zipf_s():
    w = gen_zipf.weights(GEN["of"], GEN["s"])
    assert len(w) == 10000 and w.sum() == pytest.approx(1.0)
    h = sum(k ** -1.3 for k in range(1, 10001))  # 3.7216
    assert w[0] == pytest.approx(1 / h) and w[0] == pytest.approx(0.2687, abs=1e-4)
    assert w[:4].sum() == pytest.approx(0.487, abs=1e-3)
    assert (np.diff(w) < 0).all()


def test_a_batch_holds_the_weights():
    data = script.Data(CONFIG, TRAFFIC, 2147483659, rehearse=True)
    item = data.env(0)["batch"][0].values
    assert len(item) == CONFIG["rehearse_rows"]["fact"] == 15625
    assert item.dtype == np.int64 and 0 <= item.min() and item.max() < 10000
    freq = np.sort(np.bincount(item))[::-1] / len(item)
    assert freq[0] == pytest.approx(0.2687, abs=0.01)
    assert freq[1] == pytest.approx(0.2687 * 2 ** -1.3, abs=0.01)
    # the two variants' hot keys differ: a permutation a batch
    other = data.env(1)["batch"][0].values
    assert np.bincount(item).argmax() != np.bincount(other).argmax()


def test_the_hottest_partition_s_share_at_the_rehearsal_size():
    """Forty batches of twenty seeds: the hottest of four partitions
    receives 1.3-2.9 times the mean, 1.76 in the median; nine in ten lie
    where the stage's power-of-two capacity is one value (between a
    third and two thirds of the kept rows)."""
    shares = []
    for seed in range(20):
        data = script.Data(CONFIG, TRAFFIC, 2147483000 + seed, rehearse=True)
        for v in range(2):
            recv = recv_of(data.env(v)["batch"])
            shares.append(recv.max() / recv.sum())
    shares = np.array(shares)
    assert 0.31 < shares.min() and shares.max() < 0.72
    assert 0.40 < np.median(shares) < 0.49
    assert np.mean((shares > 0.33) & (shares <= 0.655)) >= 0.9
    assert len(np.unique(np.round(shares, 3))) > 30  # the hot set moves


@pytest.mark.parametrize("seed", [0, 2147483999])
def test_the_capacity_at_the_real_size(seed):
    """8,000,000 rows: 79.8% kept, the hottest partition within
    (2^21, 2^22] rows on both variants, so every device runs 2^22 row
    slots and 62% of them hold no row."""
    data = script.Data(CONFIG, TRAFFIC, seed, rehearse=False)
    for v in range(2):
        recv = recv_of(data.env(v)["batch"])
        assert 6_375_000 < recv.sum() < 6_392_000
        assert (1 << 21) < recv.max() <= (1 << 22)
        assert 1 - recv.sum() / (4 << 22) == pytest.approx(0.6195, abs=4e-4)
        assert recv.max() / recv.mean() > 1.4
