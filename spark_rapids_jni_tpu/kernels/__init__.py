"""Hand-written Pallas TPU kernels for the framework's hot ops.

The reference hand-writes CUDA for exactly one Spark-specific hot path —
the row⇄columnar transpose (row_conversion.cu:48-304, shared-memory tiled,
warp ballots) — and gets everything else from libcudf's kernels. Here the
split is: XLA fusion covers most of the op library, and this package holds
explicit Pallas kernels for the paths where controlling VMEM tiling and
fusing multi-column passes matters:

* ``row_transpose`` — packed-row assembly/disassembly tiles (the CUDA
  kernel pair's TPU replacement; 48 KB shared memory -> VMEM blocks, warp
  ballots -> vectorized bit-weight reductions).
* ``hashing`` — fused multi-column Murmur3 table hashing in one VMEM pass.
* ``registry`` — the kernel tier: one dispatchable entry per accelerated
  inner loop, selected under ``SPARK_RAPIDS_TPU_KERNELS`` with
  exact-path-fallback discipline.

Every kernel has an ``interpret=`` escape hatch so the CPU test tier
(tests/conftest.py) exercises the same code path the TPU runs.

Kernel submodules import LAZILY (module ``__getattr__``): environments
whose jax build lacks Pallas support must still import this package —
the registry probes :func:`pallas_capability` and degrades every kernel
to a clean ``kernel.declines`` with a labeled warning instead of an
import-time failure.
"""

import importlib

import jax

_SUBMODULES = ("hashing", "registry", "row_transpose")


def on_tpu() -> bool:
    """True when the default backend's platform is ``"tpu"``. A backend
    that fails to initialize raises: answering False there would run
    every kernel in the interpreter and call it a TPU run."""
    return jax.devices()[0].platform == "tpu"


def default_interpret() -> bool:
    """Pallas ``interpret=`` default: Mosaic on TPU, interpreter elsewhere
    (the CPU test tier runs the same kernel code interpreted)."""
    return not on_tpu()


_capability: "tuple[bool, str] | None" = None


def pallas_capability() -> "tuple[bool, str]":
    """(available, detail): can this jax build load Pallas at all?

    Probed once, never raises — a missing/broken Pallas install answers
    ``(False, "<reason>")`` and the kernel tier declines every launch
    (kernels/registry.py) instead of failing at import time."""
    global _capability
    if _capability is None:
        try:
            importlib.import_module("jax.experimental.pallas")
            _capability = (True, "")
        # srt: allow-broad-except(capability probing must never raise; any import failure means "no Pallas" and the registry declines cleanly)
        except Exception as e:
            _capability = (
                False, f"jax.experimental.pallas: {type(e).__name__}: "
                f"{str(e)[:160]}",
            )
    return _capability


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))


__all__ = ["hashing", "registry", "row_transpose", "on_tpu",
           "default_interpret", "pallas_capability"]
