"""The rest of a run with the timed path broken underneath: an answer
altered where the client receives it, a compile inside the window, a
fallback counter that moves — each has to come out as not correct."""

import json

import numpy as np
import pytest

from perfbench import run


def drive(capsys, cell="ss-star-8m.resident-query"):
    rc = run.main(["--workload", cell, "--seed", "77", "--seconds", "1",
                   "--rehearse"])
    out = [x for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    return rc, json.loads(out[-1]), [json.loads(x) for x in out]


def test_sound_run_is_correct(capsys):
    rc, last, _ = drive(capsys)
    assert rc == 0 and last["correct"] is True


def test_one_altered_value_is_caught(capsys, monkeypatch):
    from spark_rapids_jni_tpu import serving

    real = serving.Client.download

    def download(self, table):
        tids, scales, datas, valids, n = real(self, table)
        first = np.frombuffer(datas[1], np.int64).copy()
        first[n // 2] += 1  # one sum(quantity) off by one
        return tids, scales, [datas[0], first.tobytes(), *datas[2:]], valids, n

    monkeypatch.setattr(serving.Client, "download", download)
    rc, last, lines = drive(capsys)
    assert rc == 0 and last["correct"] is False
    (check,) = [x["check"] for x in lines if "check" in x and "control" not in x]
    assert check["mismatched_values"] >= 1


def test_a_float64_sum_in_float32_is_caught(capsys, monkeypatch):
    from spark_rapids_jni_tpu import serving

    real = serving.Client.stream

    def stream(self, ops, batches, **kw):
        out = []
        for tids, scales, datas, valids, n in real(self, ops, batches, **kw):
            f32 = np.frombuffer(datas[3], np.float64).astype(np.float32)
            out.append((tids, scales, [*datas[:3], f32.astype(np.float64).tobytes()],
                        valids, n))
        return out

    monkeypatch.setattr(serving.Client, "stream", stream)
    rc, last, lines = drive(capsys, "ss-star-8m.stream-c2")
    assert rc == 0 and last["correct"] is False
    (check,) = [x["check"] for x in lines if "check" in x and "control" not in x]
    assert check["f64_sum_max_abs_err"] > check["f64_sum_limit"]


@pytest.mark.parametrize("counter", ["compile_cache.miss", "plan.fallbacks"])
def test_a_moving_counter_is_caught(capsys, monkeypatch, counter):
    from spark_rapids_jni_tpu import serving
    from spark_rapids_jni_tpu.utils import metrics

    real = serving.Client.plan
    calls = []

    def plan(self, *a, **kw):
        calls.append(1)
        if len(calls) > 1:  # not the warm-up: inside the window
            metrics.counter_add(counter)
        return real(self, *a, **kw)

    monkeypatch.setattr(serving.Client, "plan", plan)
    rc, last, _ = drive(capsys)
    assert rc == 0 and last["correct"] is False
