"""The flag plane: one place every runtime knob is declared.

Mirrors the reference's config system (SURVEY.md §5.6): Maven ``-D``
properties are the single source of truth with defaults in pom.xml:79-100,
fanned out to Ant/CMake/sysprops and documented in CONTRIBUTING.md:57-70.
Here the single plane is ``SPARK_RAPIDS_TPU_*`` environment variables with
defaults declared below; Java callers set the same knobs as system
properties which the JNI shim exports into the embedded runtime's
environment (native/ runtime).

Flags (reference analog in parens):

* ``TRACE``            — profiler range annotations on/off
                         (``ai.rapids.cudf.nvtx.enabled``, pom.xml:85,200).
* ``METRICS``          — op-level metrics registry (utils/metrics.py),
                         the per-operator ``GpuMetric`` counters analog.
* ``METRICS_DUMP``     — path to write the metrics snapshot JSON at
                         process exit; setting it implies ``METRICS``.
* ``REFCOUNT_DEBUG``   — buffer-registry leak tracking with provenance
                         (``ai.rapids.refcount.debug``, pom.xml:86,199).
* ``ALLOC_LOG_LEVEL``  — allocation logging verbosity
                         (``RMM_LOGGING_LEVEL``, pom.xml:82).
* ``DISABLE_X64``      — refuse 64-bit device types (debug aid; the x64
                         guard in column.py raises when data would narrow).
* ``TEST_PLATFORM``    — test-suite backend selection (cpu | tpu);
                         the "GPU required" gate of ci/premerge-build.sh:20
                         inverted into an opt-in.
* ``NATIVE_LIB``       — explicit path to libspark_rapids_tpu.so
                         (NativeDepsLoader's resource-path override).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional

_PREFIX = "SPARK_RAPIDS_TPU_"


def _as_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


def _parse_kernels(v: str) -> str:
    got = v.strip().lower()
    if got not in ("on", "off", "auto"):
        # a typo'd A/B arm must fail loudly, not silently measure the
        # default routing under the wrong label
        raise ValueError(
            f"KERNELS must be on|off|auto, got {v!r}"
        )
    return got


def _parse_port(v: str) -> int:
    try:
        got = int(v.strip())
    except ValueError:
        raise ValueError(f"SERVE_PORT must be an integer, got {v!r}")
    if not (0 <= got <= 65535):
        # a silently-clamped port would bind somewhere the operator
        # never asked for; refuse instead
        raise ValueError(f"SERVE_PORT must be in [0, 65535], got {v!r}")
    return got


def _parse_positive_int(name: str):
    def parse(v: str) -> int:
        try:
            got = int(v.strip())
        except ValueError:
            raise ValueError(f"{name} must be an integer, got {v!r}")
        if got <= 0:
            raise ValueError(f"{name} must be > 0, got {v!r}")
        return got

    return parse


def _parse_fraction(name: str):
    def parse(v: str) -> float:
        try:
            got = float(v.strip())
        except ValueError:
            raise ValueError(f"{name} must be a float, got {v!r}")
        if not (0.0 < got <= 1.0):
            # a fraction outside (0, 1] silently hands one tenant more
            # than the whole device (or nothing at all)
            raise ValueError(f"{name} must be in (0, 1], got {v!r}")
        return got

    return parse


def _parse_nonneg_int(name: str):
    def parse(v: str) -> int:
        try:
            got = int(v.strip())
        except ValueError:
            raise ValueError(f"{name} must be an integer, got {v!r}")
        if got < 0:
            raise ValueError(f"{name} must be >= 0, got {v!r}")
        return got

    return parse


def _parse_nonneg_float(name: str):
    def parse(v: str) -> float:
        try:
            got = float(v.strip())
        except ValueError:
            raise ValueError(f"{name} must be a float, got {v!r}")
        if got < 0.0:
            raise ValueError(f"{name} must be >= 0, got {v!r}")
        return got

    return parse


def _parse_positive_float(name: str):
    def parse(v: str) -> float:
        try:
            got = float(v.strip())
        except ValueError:
            raise ValueError(f"{name} must be a float, got {v!r}")
        if got <= 0.0:
            raise ValueError(f"{name} must be > 0, got {v!r}")
        return got

    return parse


def _parse_fault_spec(v: str) -> str:
    """Validate a SPARK_RAPIDS_TPU_FAULTS plan
    (``[seed=N,]site:kind:prob[:count],...``) at flag-read time so a
    typo'd chaos plan fails loudly instead of silently injecting
    nothing. The compiled (seeded) form lives in utils/faults.py; the
    site and kind vocabularies are declared there."""
    from . import faults

    spec = v.strip()
    if spec:
        faults.parse_spec(spec)  # raises ValueError naming the env var
    return spec


def _parse_checkpoint_dir(v: str) -> str:
    """Validate SPARK_RAPIDS_TPU_CHECKPOINT_DIR at flag-read time: a
    whitespace-only value or a path that exists but is not a directory
    is a deployment mistake that would silently disable durability, so
    fail loudly (the directory itself is created lazily on first
    checkpoint)."""
    if v and not v.strip():
        raise ValueError(
            "SPARK_RAPIDS_TPU_CHECKPOINT_DIR must be a directory path, "
            f"got whitespace {v!r}"
        )
    path = v.strip()
    if path and os.path.exists(path) and not os.path.isdir(path):
        raise ValueError(
            f"SPARK_RAPIDS_TPU_CHECKPOINT_DIR={path!r} exists and is "
            "not a directory"
        )
    return path


@dataclasses.dataclass(frozen=True)
class Flag:
    name: str
    default: Any
    parse: Callable[[str], Any]
    doc: str

    @property
    def env_var(self) -> str:
        return _PREFIX + self.name


_FLAGS = {
    f.name: f
    for f in [
        Flag("TRACE", False, _as_bool, "profiler trace annotations"),
        Flag(
            "METRICS", False, _as_bool,
            "op-level metrics registry + spans (utils/metrics.py): op "
            "counts, wire bytes, timers, resident-handle high-water",
        ),
        Flag(
            "METRICS_DUMP", "", str,
            "path to write metrics.snapshot() JSON at process exit "
            "(atexit); a non-empty path implies METRICS",
        ),
        Flag("REFCOUNT_DEBUG", False, _as_bool, "buffer leak tracking"),
        Flag(
            "LOG_LEVEL", "OFF", str.upper,
            "runtime observability level (OFF|ERROR|WARN|INFO|DEBUG|"
            "TRACE) for every utils/log.py channel",
        ),
        Flag(
            "ALLOC_LOG_LEVEL", "OFF", str.upper,
            "allocation log level; overrides LOG_LEVEL for the "
            "hbm/handles channels (RMM_LOGGING_LEVEL analog)",
        ),
        Flag("DISABLE_X64", False, _as_bool, "refuse 64-bit device types"),
        Flag("TEST_PLATFORM", "cpu", str, "test backend (cpu|tpu)"),
        Flag("NATIVE_LIB", "", str, "explicit native library path"),
        Flag(
            "HBM_BUDGET_GB", 0.0, float,
            "per-chip HBM budget in GiB for the footprint planner "
            "(utils/hbm.py); 0 = backend default (v5e: 16)",
        ),
        Flag(
            "KERNELS", "auto", _parse_kernels,
            "Pallas kernel tier (kernels/registry.py): on = try every "
            "applicable hand-written kernel runner (interpret-mode off "
            "TPU, so tests/CI exercise the kernel code path on CPU) | "
            "off = never | auto (default) = only on a real TPU, where "
            "Mosaic compiles the kernels natively. Any kernel error or "
            "decline replays the op on the bucketed/exact path "
            "(metered kernel.fallbacks / kernel.declines) — the tier "
            "can change performance, never bytes",
        ),
        Flag(
            "FLIGHT", "", str,
            "flight recorder (utils/flight.py): off (default) | on = "
            "ring of 8192 events | an integer ring capacity. Records "
            "span begin/end, dispatch/wire/cache/retry events with "
            "monotonic timestamps + thread ids; ~100ns/event",
        ),
        Flag(
            "FLIGHT_DUMP", "", str,
            "path to write the flight-recorder tail JSON at process "
            "exit (atexit); a non-empty path implies FLIGHT",
        ),
        Flag(
            "BUCKETS", "", str,
            "shape-bucket spec for the dispatch plane (utils/buckets.py):"
            " '' = default geometric ladder (1024 x2 up to 8.4M rows), "
            "'floor:growth[:cap]', an explicit 'a,b,c' size list, or "
            "off|none|0 to disable pad-to-bucket batching",
        ),
        Flag(
            "PIPELINE", "", str,
            "pipelined dispatch plane (pipeline.py): off (default) = "
            "fully synchronous dispatch; an integer = pipeline depth "
            "(max batches in flight: wire serde on background workers "
            "overlapping device compute, resident ops enqueue and "
            "return ids immediately); on = default depth 2",
        ),
        Flag(
            "PROFILE", "", str,
            "query profiler (utils/profiler.py): on = auto-open a "
            "profile session around every table_plan_wire / "
            "table_plan_resident / table_stream_wire call, collecting "
            "per-segment compile/execute/serde/stall splits rendered "
            "by tools/explain.py; off (default) costs one cached "
            "generation compare per entry",
        ),
        Flag(
            "PROFILE_DUMP", "", str,
            "path to write finished profile sessions as JSON at "
            "process exit (atexit); a non-empty path implies PROFILE",
        ),
        Flag(
            "PLANSTATS", False, _as_bool,
            "plan-statistics store (utils/planstats.py): on = every "
            "profile session (and therefore every run_plan execution — "
            "PLANSTATS implies PROFILE-style auto-sessions and the "
            "metrics plane) appends one CRC-framed record keyed by "
            "plan fingerprint x schema x bucket, with per-segment "
            "observed times/rows/bytes, counter deltas, and drift "
            "findings vs plancheck's static predictions; off (default) "
            "costs one cached generation compare per dispatch",
        ),
        Flag(
            "PLANSTATS_DIR", "", str,
            "directory for plan-statistics store files "
            "(planstats-<host>-<pid>.wal); '' (default) = "
            "<tempdir>/srt-planstats. A non-empty path implies "
            "PLANSTATS. Files are NEVER swept at exit — history across "
            "processes is what the drift layer compares against",
        ),
        Flag(
            "PLANSTATS_ROTATE_MB", 64.0,
            _parse_positive_float("PLANSTATS_ROTATE_MB"),
            "per-process stats-store rotation threshold in MiB: past "
            "it the live WAL rotates to <name>.wal.1 (one old "
            "generation kept, older dropped) — bounded disk, "
            "crash-safe at every point",
        ),
        Flag(
            "DRIFT_ROWS_FACTOR", 4.0,
            _parse_positive_float("DRIFT_ROWS_FACTOR"),
            "cardinality drift threshold: a segment whose observed "
            "rows_out deviates from its history median by more than "
            "this factor (either direction) gets a typed drift "
            "finding and a drift.cardinality tick",
        ),
        Flag(
            "DRIFT_HBM_FACTOR", 2.0,
            _parse_positive_float("DRIFT_HBM_FACTOR"),
            "HBM drift threshold: a segment whose observed working-set "
            "proxy exceeds plancheck's static est_hbm_bytes by more "
            "than this factor gets a typed drift finding and a "
            "drift.hbm tick",
        ),
        Flag(
            "SKEW_SPLIT_FACTOR", 2.0,
            _parse_positive_float("SKEW_SPLIT_FACTOR"),
            "adaptive shuffle-skew threshold: after the two-phase "
            "counts pass, any destination whose planned recv rows "
            "exceed this factor x the mean gets its hot keys salted "
            "across sub-partitions (partial-agg before exchange, "
            "merge-agg after) so exchange capacity is sized from the "
            "post-split counts; disable the machinery wholesale with "
            "SKEW_SPLIT=0",
        ),
        Flag(
            "SKEW_SPLIT", True, _as_bool,
            "master switch for adaptive skew repartitioning on the "
            "mesh shuffle path; off = always size capacity from the "
            "raw per-destination counts (BENCH_r04 behaviour)",
        ),
        Flag(
            "SERVE_PORT", 0, _parse_port,
            "serving daemon (serving/server.py) localhost TCP port; "
            "0 (default) = OS-assigned ephemeral port, read back from "
            "Server.port",
        ),
        Flag(
            "SERVE_MAX_SESSIONS", 8,
            _parse_positive_int("SERVE_MAX_SESSIONS"),
            "serving daemon session-admission cap: a HELLO past this "
            "many live sessions gets a typed session_limit rejection",
        ),
        Flag(
            "SERVE_SESSION_HBM_FRACTION", 0.25,
            _parse_fraction("SERVE_SESSION_HBM_FRACTION"),
            "per-session HBM budget as a fraction of hbm.budget_bytes()"
            "; admission rejects (or queues behind in-flight work) any "
            "plan whose estimate exceeds the session's remainder",
        ),
        Flag(
            "SERVE_QUEUE_DEPTH", 16,
            _parse_positive_int("SERVE_QUEUE_DEPTH"),
            "serving daemon per-session scheduler queue depth; a "
            "request past it is shed with a typed BUSY response",
        ),
        Flag(
            "FAULTS", "", _parse_fault_spec,
            "deterministic fault-injection plan (utils/faults.py): "
            "'[seed=N,]site:kind:prob[:count],...' — site in "
            "dispatch|compile|serde|hbm_admit|serve_accept|spill|"
            "checkpoint|shuffle|collective|mesh, kind in "
            "transient|oom|permanent, prob in [0,1], count = max "
            "injections (0/absent = unlimited); '' (default) = off",
        ),
        Flag(
            "SPILL", False, _as_bool,
            "tiered memory hierarchy (utils/spill.py): on = resident "
            "tables gain a device|host|disk residency state with "
            "LRU-by-last-touch eviction under HBM pressure and "
            "transparent repage-on-access, so admission and OOM degrade "
            "to slower instead of shedding; off (default) costs one "
            "cached generation compare per registry access",
        ),
        Flag(
            "SPILL_DIR", "", str,
            "directory for disk-tier spill files (utils/spill.py); '' "
            "(default) = a per-process directory under the system temp "
            "dir; files this process wrote are swept at exit either way",
        ),
        Flag(
            "DURABLE", False, _as_bool,
            "durable serving plane (serving/durable.py): on = per-"
            "session write-ahead journal of namespace mutations with "
            "CRC-framed fsync'd records, table payloads checkpointed "
            "via the spill .npz serde, crash-safe restore + warm-start "
            "manifest replay before the listener accepts traffic; off "
            "(default) costs one cached generation compare per mutation",
        ),
        Flag(
            "CHECKPOINT_DIR", "", _parse_checkpoint_dir,
            "directory for durable serving checkpoints (journals, "
            "table payloads, warm-start manifest); '' (default) = "
            "<tempdir>/srt-checkpoint. Unlike SPILL_DIR this directory "
            "is NEVER swept at exit — checkpoints must survive the "
            "process to be worth writing",
        ),
        Flag(
            "HOST_SPILL_BUDGET_GB", 4.0,
            _parse_nonneg_float("HOST_SPILL_BUDGET_GB"),
            "host-RAM spill tier budget in GiB (utils/spill.py); past "
            "it the coldest host entries demote to the disk tier; 0 = "
            "skip the host tier and spill straight to disk",
        ),
        Flag(
            "RETRY_MAX", 3, _parse_nonneg_int("RETRY_MAX"),
            "max retries for a transient-classified failure at one "
            "dispatch/segment boundary (utils/faults.py); 0 disables "
            "retry, surfacing the typed error on the first failure",
        ),
        Flag(
            "RETRY_BASE_MS", 25.0,
            _parse_positive_float("RETRY_BASE_MS"),
            "base backoff for transient retries in milliseconds; "
            "attempt N sleeps ~base*2^(N-1) with deterministic jitter",
        ),
        Flag(
            "DEADLINE_DEFAULT_S", 0.0,
            _parse_nonneg_float("DEADLINE_DEFAULT_S"),
            "default per-request deadline in seconds for served "
            "requests whose hello/command frames carry none; 0 "
            "(default) = no deadline",
        ),
        Flag(
            "BREAKER_THRESHOLD", 5,
            _parse_positive_int("BREAKER_THRESHOLD"),
            "serving circuit breaker: consecutive transient-classified "
            "failures before the daemon flips to the typed Degraded "
            "shed state",
        ),
        Flag(
            "BREAKER_PROBE_S", 1.0,
            _parse_positive_float("BREAKER_PROBE_S"),
            "serving circuit breaker: seconds an OPEN breaker waits "
            "before letting one half-open probe through",
        ),
        Flag(
            "MESH_PROBE_S", 5.0,
            _parse_positive_float("MESH_PROBE_S"),
            "deadline in seconds for one MeshHealth heartbeat "
            "(parallel/mesh.py): an all-reduce that has not answered "
            "by then marks the probed mesh unhealthy and the "
            "degradation ladder drops to fewer devices",
        ),
        Flag(
            "TRACE_SLO_MS", 250.0,
            _parse_nonneg_float("TRACE_SLO_MS"),
            "slow-request SLO threshold in milliseconds for the trace "
            "plane's tail sampling (utils/tracing.py): a finished "
            "serving request at or over this duration — or one ending "
            "in a typed error — keeps its full span detail in the "
            "slow-request log; faster requests keep only the summary "
            "row. 0 keeps detail for every request",
        ),
        Flag(
            "TRACE_TOPK", 32,
            _parse_positive_int("TRACE_TOPK"),
            "slow-request log depth: the serving `trace` command "
            "returns the top-K finished requests by duration",
        ),
        Flag(
            "LOCKCHECK", False, _as_bool,
            "dynamic lock-order detector (utils/lockcheck.py): on = "
            "every tracked package lock records per-thread held sets "
            "and a global acquisition-order graph, reporting cycles "
            "(potential deadlocks), inversions of the sanctioned "
            "registry->session->scheduler->spill order, and locks held "
            "across device dispatch / blocking IO; off (default) costs "
            "one cached generation compare per acquisition",
        ),
    ]
}

# Test/runtime overrides set via set_flag (take precedence over env).
_overrides: dict = {}

# Monotonic counter bumped on every set_flag/clear_flag: the cache-
# invalidation key for hot-path gates (utils/metrics.py caches its
# enabled() verdict against it so a disabled instrumentation site costs
# an int compare, not an environ read per call). Environment-variable
# changes made mid-process after the first read are NOT observed by
# cached gates — set flags through this API (tests already must, since
# exported shell values are pinned per-process anyway).
_generation = 0


def generation() -> int:
    return _generation


def get_flag(name: str):
    """Current value of a declared flag (override > env > default)."""
    flag = _FLAGS[name]
    if name in _overrides:
        return _overrides[name]
    raw = os.environ.get(flag.env_var)
    if raw is None:
        return flag.default
    return flag.parse(raw)


def flag_is_set(name: str) -> bool:
    """True when the flag has an explicit value (override or env) as
    opposed to riding its declared default — for knobs where "set to
    the default value" and "unset" mean different things (e.g.
    ALLOC_LOG_LEVEL=OFF silences its channels; unset defers)."""
    flag = _FLAGS[name]
    return name in _overrides or flag.env_var in os.environ


def flag_default(name: str):
    """Declared default of a flag — the fallback target when an
    explicitly set value fails to parse (log.py's invalid-level path)."""
    return _FLAGS[name].default


def set_flag(name: str, value) -> None:
    global _generation
    if name not in _FLAGS:
        raise KeyError(f"unknown flag {name!r}")
    _overrides[name] = value
    _generation += 1


def clear_flag(name: str) -> None:
    global _generation
    _overrides.pop(name, None)
    _generation += 1


def describe_flags() -> str:
    """Human-readable flag table (the CONTRIBUTING.md:57-70 analog)."""
    lines = []
    for f in _FLAGS.values():
        lines.append(
            f"{f.env_var:<40} default={f.default!r:<10} {f.doc}"
        )
    return "\n".join(lines)


def place_compile_cache() -> None:
    """Give JAX's persistent compile cache a place, once, from outside.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it
    and nothing is set in code. Otherwise the cache lives at the fixed
    path ``<checkout>/.jax_cache`` — the path is part of the cache key,
    so it never carries a temp name, a pid or a time. Entry points
    (``chip_smoke.py``, ``perfbench/run.py``) call this before their
    first compile; the package itself never does."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    jax.config.update(
        "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
    )
