"""The runtime observability plane (utils/log.py): the
RMM_LOGGING_LEVEL role (reference pom.xml:82) — HBM plan decisions,
live handle counts, level gating."""

import numpy as np
import pytest

from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.utils import config, hbm, log


@pytest.fixture(autouse=True)
def _reset_flags(monkeypatch):
    # pin a known baseline: an exported SPARK_RAPIDS_TPU_*LOG_LEVEL in
    # the developer's shell must not flip these assertions
    monkeypatch.delenv("SPARK_RAPIDS_TPU_LOG_LEVEL", raising=False)
    monkeypatch.delenv("SPARK_RAPIDS_TPU_ALLOC_LOG_LEVEL", raising=False)
    log._WARNED_INVALID.clear()  # one-time warnings: once per TEST
    yield
    config.clear_flag("LOG_LEVEL")
    config.clear_flag("ALLOC_LOG_LEVEL")
    log._WARNED_INVALID.clear()


def _table(n=64):
    return Table(
        [
            Column.from_numpy(np.arange(n, dtype=np.int64)),
            Column.from_numpy(np.arange(n, dtype=np.int64)),
        ],
        ["k", "v"],
    )


def test_silent_by_default(capsys):
    log.log("ERROR", "general", "should not appear")
    hbm.join_plan(_table(), _table(), ["k"], ["k"])
    assert "[srt]" not in capsys.readouterr().err


def test_hbm_plan_decision_surfaces(capsys):
    config.set_flag("LOG_LEVEL", "INFO")
    hbm.join_plan(_table(), _table(), ["k"], ["k"])
    err = capsys.readouterr().err
    assert "[srt][hbm][INFO] join_plan" in err
    assert "probe_rows=" in err and "fits=" in err


def test_handle_counts_surface(capsys):
    from spark_rapids_jni_tpu import runtime_bridge as rb

    config.set_flag("ALLOC_LOG_LEVEL", "DEBUG")
    tid = rb._resident_put(_table(8))
    rb.table_free(tid)
    err = capsys.readouterr().err
    assert "[srt][handles][DEBUG] resident_put" in err
    assert "[srt][handles][DEBUG] table_free" in err
    assert "live=" in err


def test_alloc_level_overrides_only_alloc_channels(capsys):
    # ALLOC_LOG_LEVEL=DEBUG must open hbm/handles but leave the general
    # channel gated by LOG_LEVEL (still OFF)
    config.set_flag("ALLOC_LOG_LEVEL", "DEBUG")
    log.log("INFO", "general", "general-line")
    log.log("DEBUG", "hbm", "hbm-line")
    err = capsys.readouterr().err
    assert "general-line" not in err
    assert "hbm-line" in err


def test_level_ordering(capsys):
    config.set_flag("LOG_LEVEL", "WARN")
    log.log("ERROR", "probe", "e")
    log.log("WARN", "probe", "w")
    log.log("INFO", "probe", "i")
    err = capsys.readouterr().err
    assert "[srt][probe][ERROR] e" in err
    assert "[srt][probe][WARN] w" in err
    assert " i" not in err


def test_flag_documented():
    assert "LOG_LEVEL" in config.describe_flags()


def test_alloc_off_silences_even_under_debug(capsys):
    # the override works in the QUIET direction too
    config.set_flag("LOG_LEVEL", "DEBUG")
    config.set_flag("ALLOC_LOG_LEVEL", "OFF")
    log.log("DEBUG", "handles", "handle-line")
    log.log("DEBUG", "probe", "probe-line")
    err = capsys.readouterr().err
    assert "handle-line" not in err
    assert "probe-line" in err


def test_invalid_alloc_level_falls_back(capsys):
    config.set_flag("LOG_LEVEL", "INFO")
    config.set_flag("ALLOC_LOG_LEVEL", "VERBOSE")  # typo'd value
    log.log("INFO", "hbm", "hbm-line")
    assert "hbm-line" in capsys.readouterr().err


def test_invalid_log_level_warns_once_and_names_value(capsys):
    # the pre-fix behavior mapped a typo silently to OFF — the one user
    # who opted into logging got total silence with no indication why
    config.set_flag("LOG_LEVEL", "CHATTY")
    log.log("ERROR", "general", "first")
    err = capsys.readouterr().err
    assert "[srt][log][WARN]" in err
    assert "CHATTY" in err and "SPARK_RAPIDS_TPU_LOG_LEVEL" in err
    # one-time: a second gated call must not repeat the warning
    log.log("ERROR", "general", "second")
    assert "CHATTY" not in capsys.readouterr().err


def test_invalid_log_level_falls_back_to_default(capsys):
    # fallback target is the DECLARED default, not hardcoded OFF
    config.set_flag("LOG_LEVEL", "NOPE")
    assert not log.enabled("ERROR")
    assert log._resolve_level("general") == log.LEVELS[
        str(config.flag_default("LOG_LEVEL"))
    ]


def test_invalid_alloc_level_warns_once(capsys):
    config.set_flag("LOG_LEVEL", "INFO")
    config.set_flag("ALLOC_LOG_LEVEL", "VERBOSE")
    log.log("INFO", "hbm", "a")
    err = capsys.readouterr().err
    assert "SPARK_RAPIDS_TPU_ALLOC_LOG_LEVEL" in err
    assert "VERBOSE" in err
    log.log("INFO", "hbm", "b")
    assert "VERBOSE" not in capsys.readouterr().err
