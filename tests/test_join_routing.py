"""The 64-bit join-fault fence (round-4 VERDICT item 3).

The fused single-shot join graph killed the TPU worker at >= 32M rows
(ops/join.py, the comment above ``FUSED_PROBE_MAX_ROWS``), so above it the
eager join APIs must route through chunk-probed graphs automatically —
the reference never lets callers choose safety (its 2 GB batch split is
automatic, row_conversion.cu:476-479,505-511). These tests lower the
threshold and fake an accelerator backend to pin (a) that the routing
fires and (b) that the fenced results equal the fused-path oracle.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu.column import Column, Table
from spark_rapids_jni_tpu.ops import join as join_mod


@pytest.fixture
def fenced(monkeypatch):
    """Force the fence on: tiny threshold + pretend accelerator."""
    monkeypatch.setattr(join_mod, "FUSED_PROBE_MAX_ROWS", 7)
    monkeypatch.setattr(join_mod, "_on_accelerator", lambda: True)


def _tables(n_left=50, n_right=40, seed=0):
    rng = np.random.default_rng(seed)
    left = Table(
        [
            Column.from_numpy(rng.integers(0, 12, n_left, dtype=np.int64)),
            Column.from_numpy(np.arange(n_left, dtype=np.int64)),
        ],
        ["k", "lv"],
    )
    right = Table(
        [
            Column.from_numpy(rng.integers(0, 12, n_right, dtype=np.int64)),
            Column.from_numpy(np.arange(n_right, dtype=np.int64) * 10),
        ],
        ["k", "rv"],
    )
    return left, right


def _sorted_rows(t: Table):
    cols = [np.asarray(c.to_numpy()) for c in t.columns]
    rows = sorted(zip(*cols))
    return rows


def test_inner_join_routes_to_batched(fenced, monkeypatch):
    left, right = _tables()
    calls = {}
    real = join_mod.inner_join_batched

    def spy(*a, **k):
        calls["hit"] = True
        return real(*a, **k)

    monkeypatch.setattr(join_mod, "inner_join_batched", spy)
    out = join_mod.inner_join(left, right, ["k"])
    assert calls.get("hit"), "fence did not route inner_join to batched"
    # oracle: the fused path with the fence off
    monkeypatch.setattr(join_mod, "_on_accelerator", lambda: False)
    oracle = join_mod.inner_join(left, right, ["k"])
    assert out.names == oracle.names
    assert _sorted_rows(out) == _sorted_rows(oracle)


def test_small_tables_keep_fused_path(fenced, monkeypatch):
    left, right = _tables(n_left=5, n_right=5)

    def boom(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("small join must not take the batched path")

    monkeypatch.setattr(join_mod, "inner_join_batched", boom)
    join_mod.inner_join(left, right, ["k"])


@pytest.mark.parametrize(
    "api", ["left_join", "right_join", "full_join", "semi_join", "anti_join"]
)
def test_fenced_joins_match_fused_oracle(fenced, monkeypatch, api):
    left, right = _tables(seed=3)
    out = getattr(join_mod, api)(left, right, ["k"])
    monkeypatch.setattr(join_mod, "_on_accelerator", lambda: False)
    oracle = getattr(join_mod, api)(left, right, ["k"])
    assert out.names == oracle.names
    assert _sorted_rows(out) == _sorted_rows(oracle)


def test_fenced_counts_match(fenced, monkeypatch):
    left, right = _tables(seed=4)
    got_inner = int(join_mod.inner_join_count(left, right, ["k"]))
    got_left = int(join_mod.left_join_count(left, right, ["k"]))
    got_mask = np.asarray(join_mod.membership_mask(left, right, ["k"]))
    monkeypatch.setattr(join_mod, "_on_accelerator", lambda: False)
    assert got_inner == int(join_mod.inner_join_count(left, right, ["k"]))
    assert got_left == int(join_mod.left_join_count(left, right, ["k"]))
    np.testing.assert_array_equal(
        got_mask, np.asarray(join_mod.membership_mask(left, right, ["k"]))
    )


def test_fence_inert_under_jit(fenced, monkeypatch):
    """Tracers must fall through to the fused graph: the chunked probe
    helper raising under trace proves the fence never fired there."""
    import jax

    left, right = _tables(seed=5, n_left=53, n_right=41)  # fresh shapes
    oracle = int(join_mod.inner_join_count(left, right, ["k"]))

    def boom(*a, **k):  # pragma: no cover - failure path
        raise AssertionError("chunked probe must not fire under jit")

    monkeypatch.setattr(join_mod, "_chunk_ranges_fn", boom)
    fn = jax.jit(lambda l, r: join_mod.inner_join_count(l, r, ["k"]))
    assert int(fn(left, right)) == oracle


def test_fenced_masked_count_matches(fenced, monkeypatch):
    """Occupancy masks ride the chunked probe (no fence bypass)."""
    import jax.numpy as jnp

    left, right = _tables(seed=6)
    lv = jnp.asarray(np.arange(50) % 3 != 0)
    rv = jnp.asarray(np.arange(40) % 4 != 0)
    got = int(
        join_mod.inner_join_count(
            left, right, ["k"], left_valid=lv, right_valid=rv
        )
    )
    monkeypatch.setattr(join_mod, "_on_accelerator", lambda: False)
    assert got == int(
        join_mod.inner_join_count(
            left, right, ["k"], left_valid=lv, right_valid=rv
        )
    )


def test_streaming_join_batches_match_batched(monkeypatch):
    """inner_join_batches yields per-chunk pieces whose concatenation
    equals inner_join_batched (which is now defined by it)."""
    import numpy as np

    from spark_rapids_jni_tpu.ops.copying import concatenate

    left, right = _tables(n_left=300, n_right=200, seed=9)
    pieces = list(
        join_mod.inner_join_batches(left, right, ["k"], probe_rows=64)
    )
    assert len(pieces) >= 4  # genuinely streamed
    whole = join_mod.inner_join_batched(
        left, right, ["k"], probe_rows=64
    )
    got = concatenate(pieces)
    assert got.row_count == whole.row_count
    assert _sorted_rows(got) == _sorted_rows(whole)


def test_streaming_join_empty_sides():
    left, right = _tables(n_left=10, n_right=0)
    assert list(join_mod.inner_join_batches(left, right, ["k"])) == []


def test_batched_string_join_mismatched_pads():
    """String keys with different pad widths between sides must still
    match through the chunk-probed path (pre-r4 this returned 0 rows:
    positional word compare silently truncated to the narrower side)."""
    lvals = ["apple", "pear", "fig", "apple"]
    rvals = ["apple", "a-very-long-string-key", "fig"]
    left = Table(
        [Column.from_strings(lvals),
         Column.from_numpy(np.arange(4, dtype=np.int64))],
        ["k", "lv"],
    )
    right = Table(
        [Column.from_strings(rvals),
         Column.from_numpy(np.arange(3, dtype=np.int64))],
        ["k", "rv"],
    )
    assert left["k"].data.shape[1] != right["k"].data.shape[1]
    direct = join_mod.inner_join(left, right, ["k"])
    batched = join_mod.inner_join_batched(
        left, right, ["k"], probe_rows=2
    )
    assert batched.row_count == direct.row_count == 3

    def rows(t):
        return sorted(
            zip(
                t["k"].to_pylist(),
                np.asarray(t["lv"].to_numpy()).tolist(),
                np.asarray(t["rv"].to_numpy()).tolist(),
            )
        )

    assert rows(batched) == rows(direct)
    # the eager chunked-ranges path (outer joins, counts) too
    got = int(join_mod.inner_join_count(left, right, ["k"]))
    assert got == 3


def test_mixed_key_dtypes_rejected_both_paths():
    """ADVICE r4: the chunked eager path must reject STRING vs
    non-STRING key pairs like the fused path does, not silently zip-
    truncate the word comparison."""
    from spark_rapids_jni_tpu import dtype as dt
    import jax.numpy as jnp

    smat = jnp.asarray(
        np.frombuffer(b"abcdefgh", np.uint8).reshape(2, 4)
    )
    str_t = Table(
        [Column(smat, dt.STRING, None, jnp.full((2,), 4, jnp.int32))],
        ["k"],
    )
    int_t = Table(
        [Column.from_numpy(np.array([1, 2], dtype=np.int64))], ["k"]
    )
    with pytest.raises(TypeError, match="STRING vs non-STRING"):
        join_mod._equalize_string_key_pads(str_t, int_t, ["k"], ["k"])
    with pytest.raises(TypeError, match="STRING vs non-STRING"):
        # generator wrapper: must raise at CALL time, not first next()
        join_mod.inner_join_batches(str_t, int_t, ["k"], probe_rows=8)


def test_inner_join_batches_validates_at_call_time(fenced):
    left, right = _tables()
    with pytest.raises(ValueError, match="probe_rows"):
        join_mod.inner_join_batches(left, right, [0], probe_rows=0)
