#!/usr/bin/env bash
# Nightly build — the ci/nightly-build.sh analog: clean rebuild of the
# native shim, full verification, packaged artifacts. Unlike premerge,
# starts from a clean build tree (`mvn clean package` analog).
set -euxo pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"

# Clean only the CMake outputs: build/ also holds the checked-in
# build-info and dependency-check scripts.
rm -rf build/CMakeCache.txt build/CMakeFiles build/Makefile \
  build/cmake_install.cmake build/libspark_rapids_tpu.so
build/dependency-check || true  # nightly reports drift but proceeds
NATIVE_BUILD_CONFIGURE=true SRT_WERROR=ON \
  CPP_PARALLEL_LEVEL="${PARALLEL_LEVEL:-4}" \
  bash spark-rapids-tpu-runtime/build-native.sh

# FULL suite nightly, slow distributed tier included
python3 -m pytest tests/ -q

XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python3 -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# Every cell of BENCHMARK.json end to end at its rehearsal size on the
# CPU (four virtual devices for the mesh cells). A rehearsal prints no
# metric: the cells are measured on the chip.
for cell in $(python3 -c 'import json
for w in json.load(open("BENCHMARK.json"))["workloads"]: print(w["name"])'); do
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
    python3 -m perfbench.run --workload "$cell" --seed 0 --rehearse
done
