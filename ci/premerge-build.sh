#!/usr/bin/env bash
# Premerge gate — the ci/premerge-build.sh analog: runs on a TPU node,
# gates on accelerator presence (the nvidia-smi gate,
# premerge-build.sh:20), validates the pinned environment, builds the
# native shim with warnings-as-errors, runs the full test suite, the
# multi-chip dry run, and a bench smoke.
#
# Env:
#   REQUIRE_TPU=true|false   fail if no TPU visible (default true on CI)
#   PARALLEL_LEVEL           native build parallelism (default 4)
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

# Accelerator gate: the premerge tier needs the real chip the way the
# reference needs a GPU (`nvidia-smi` at premerge-build.sh:20).
if [[ "${REQUIRE_TPU:-true}" == "true" ]]; then
  python3 -c "import jax; ds = jax.devices(); assert ds and ds[0].platform != 'cpu', f'no accelerator: {ds}'; print('devices:', ds)"
fi

build/dependency-check

# Static analysis gate (the compute-sanitizer CI-discipline analog,
# static half): repo-invariant AST passes — env reads outside the
# config plane, broad excepts that bypass the faults taxonomy, hot-path
# env reads, wall clocks in replay-critical modules, retry on donated
# call sites, metric-name conventions. Exits nonzero on any finding not
# grandfathered in tools/srt_check_baseline.json; the one-line summary
# is the last line.
# SRT009 (implicit host-sync hazards in hot paths) rides the same gate.
python3 tools/srt_check.py

# Plan-literal gate: every plan literal in the smoke scripts must tag
# clean under the plan-time analyzer (the GpuOverrides analog) — a
# driver must never ship a plan the runtime would reject.
python3 tools/plancheck_literals.py ci/smoke-chaos.sh \
  ci/smoke-chaos-mesh.sh ci/smoke-spill.sh ci/smoke-restart.sh \
  ci/smoke-drift.sh ci/smoke-skew.sh ci/smoke-trace.sh \
  ci/smoke-kernels.sh

# Native build: forced reconfigure on CI (the
# -Dlibcudf.build.configure=true of premerge-build.sh:26).
NATIVE_BUILD_CONFIGURE=true SRT_WERROR=ON \
  CPP_PARALLEL_LEVEL="${PARALLEL_LEVEL:-4}" \
  bash spark-rapids-tpu-runtime/build-native.sh

# Quick tier (CPU-forced inside conftest; op surface + native codec +
# java facade structure). The slow distributed/mesh tier runs nightly;
# premerge covers those paths via the multichip dryrun below, keeping
# the gate's wall-clock bounded as coverage grows (the suite passed
# 600 tests / >1h this round).
python3 -m pytest tests/ -q -m "not slow"

# Multi-chip sharding must compile+run on a virtual 8-device mesh.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python3 -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# Single-chip flagship step compile check.
python3 -c "
from __graft_entry__ import entry
import jax
fn, args = entry()
jax.block_until_ready(jax.jit(fn)(*args))
print('entry OK')
"

# Chaos smoke: a served stream under a seeded fault plan must recover
# byte-identical with nonzero retry counters, the circuit breaker must
# trip and re-close via the background probe, and zero tables may leak.
bash ci/smoke-chaos.sh

# Mesh chaos smoke: a mesh-backed served stream under seeded
# shuffle/collective faults must replay exchanges to byte-identical
# results with nonzero shuffle.retries; persistent collective failure
# must walk the degradation ladder to the floor and fall back to the
# single-device exact path (served, not shed) with zero leaked tables.
bash ci/smoke-chaos-mesh.sh

# Spill smoke: a served stream with a device working set ~2x the
# (shrunk) HBM budget must complete byte-identical by spilling cold
# tables host->disk (zero sheds), re-promote them on re-access, and
# leak zero tables and zero spill files.
bash ci/smoke-spill.sh

# Restart smoke: a durable daemon SIGKILLed mid-stream must restore
# every session from its journals before accepting traffic — clients
# reconnect with resume tokens to byte-identical tables, replayed
# request ids apply nothing new, and replayed plans land on the
# manifest-warmed compile cache with zero misses.
bash ci/smoke-restart.sh

# Drift smoke: every run_plan execution under a stats dir must append
# a CRC-framed per-segment record; a seeded cardinality skew must land
# a typed drift finding; `explain --drift` must render the store as
# predicted-vs-observed percentiles.
bash ci/smoke-drift.sh

# Kernel tier smoke: the static report must tag kernel-eligible ops, a
# KERNELS=on dispatch stream must launch with byte parity vs off, a
# seeded kernel fault must fall back cleanly, and the kernel.<name>
# spans must survive the Perfetto trace merge.
bash ci/smoke-kernels.sh

# Trace smoke: a traced serving request over the 2-device mesh — with
# one client kill -9'd mid-stream — must leave per-process flight
# dumps that tracequery merges into ONE trace (client.rpc + admission
# + queue-wait + compile + per-segment execute + mesh exchange spans,
# one shared trace id across >= 2 processes), and the live `trace`
# command must return the slow-request log + Prometheus exposition.
bash ci/smoke-trace.sh

# Skew smoke: a seeded zipf stream through a plan carrying a
# `partition` op must run on the 8-device mesh byte-identical to the
# exact path; the adaptive splitter must fire (nonzero
# shuffle.skew_splits) and hold the planned max/mean recv ratio under
# SKEW_SPLIT_FACTOR; zero leaked tables; the decision must render as a
# typed DRIFT[skew] finding.
bash ci/smoke-skew.sh

# Benchmark smoke: every cell of BENCHMARK.json end to end at its
# rehearsal size on the CPU (four virtual devices for the mesh cells).
# A rehearsal prints no metric: the cells are measured on the chip.
for cell in $(python3 -c 'import json
for w in json.load(open("BENCHMARK.json"))["workloads"]: print(w["name"])'); do
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
    python3 -m perfbench.run --workload "$cell" --seed 0 --rehearse
done
