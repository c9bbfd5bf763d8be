"""The kernel tier: plan-selectable Pallas kernels with exact fallback.

The reference repo exists to house hand-written kernels too
Spark-specific for the general library (row_conversion.cu is the
survey-snapshot example). This registry is that tier for the TPU
backend: one entry per accelerated inner loop, each declaring

* the dispatch-plane op names it accelerates,
* an **applicability predicate** — dtypes and layouts — answering a
  decline *reason* (metered ``kernel.declines``) before any device
  work, and
* a **runner** that must be byte-identical to the bucketed/exact path
  over the logical rows (the shape-bucket semantics contract,
  bucketed.py): padding-region bytes are free, logical bytes are not.

Dispatch discipline mirrors ``bucketed.dispatch_bucketed``: the tier is
consulted first by ``planops._dispatch_once`` under the
``SPARK_RAPIDS_TPU_KERNELS=on|off|auto`` flag; any runner error — a
Mosaic lowering the current toolchain refuses, a seeded ``kernel``
chaos fault — is caught, metered as ``kernel.fallbacks``, and answered
with ``None`` so the caller replays the op on the existing path. The
tier can change performance, never bytes.

Only kernels the chip's compiler accepts are registered:
``tests/test_chip_compile.py`` compiles every entry for a described
v5e.

``KERNEL_NAMES`` is the SRT012 parity anchor: srt_check statically
cross-checks it against this module's ``_REGISTRY`` literal, plancheck's
``_KERNEL_RULES`` table, and the registered ``kernel`` metric
namespace, so a kernel added to one registry without the others fails
CI before it can ship.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

from .. import dtype as dt
from ..column import Table
from ..utils import buckets, config, faults, log, metrics, profiler
from . import pallas_capability

#: Every registered kernel — the SRT012 static parity anchor. Must
#: equal the ``_REGISTRY`` keys below and plancheck's ``_KERNEL_RULES``.
KERNEL_NAMES = frozenset({"row_pack", "row_unpack"})


class KernelDecline(Exception):
    """Internal: this op/shape opts out of the kernel tier (the
    bucketed/exact path runs). Carries the decline reason."""


# ---------------------------------------------------------------------------
# row_pack / row_unpack — the row⇄columnar transpose tiles
# ---------------------------------------------------------------------------


def _a_row_pack(op: dict, table: Table, rest) -> Optional[str]:
    for c in table.columns:
        if not c.dtype.is_fixed_width:
            return f"{c.dtype.id.name} column has no fixed-width row slot"
    return None


def _r_row_pack(op: dict, table: Table, rest) -> Table:
    from .. import rows as rows_mod

    t = buckets.unpad_table(table)
    return Table([rows_mod.to_rows_list(t, backend="pallas")])


def _a_row_unpack(op: dict, table: Table, rest) -> Optional[str]:
    if not table.columns or table.columns[0].dtype.id != dt.TypeId.LIST:
        return "legacy flat row buffer (host decode path)"
    for tid in op.get("type_ids", ()):
        if dt.TypeId(int(tid)) not in dt._WIDTHS:
            return "non-fixed-width target schema"
    return None


def _r_row_unpack(op: dict, table: Table, rest) -> Table:
    from .. import rows as rows_mod

    t = buckets.unpad_table(table)
    schema = [
        dt.DType(dt.TypeId(t_), s_)
        for t_, s_ in zip(op["type_ids"], op["scales"])
    ]
    return rows_mod.from_rows_list(t.columns[0], schema, backend="pallas")


# ---------------------------------------------------------------------------
# the registry + dispatch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One accelerated inner loop: op coverage + predicate + runner."""

    name: str
    ops: Tuple[str, ...]
    applicable: Callable[[dict, Table, Sequence[Table]], Optional[str]]
    runner: Callable[[dict, Table, Sequence[Table]], Table]
    doc: str


_REGISTRY = {
    "row_pack": KernelSpec(
        "row_pack", ("to_rows",), _a_row_pack, _r_row_pack,
        "columnar -> packed rows via the Pallas transpose tiles",
    ),
    "row_unpack": KernelSpec(
        "row_unpack", ("from_rows",), _a_row_unpack, _r_row_unpack,
        "packed rows -> columnar via the Pallas transpose tiles",
    ),
}

assert KERNEL_NAMES == frozenset(_REGISTRY), "KERNEL_NAMES drifted"

_BY_OP: dict = {}
for _spec in _REGISTRY.values():
    for _op_name in _spec.ops:
        _BY_OP.setdefault(_op_name, []).append(_spec)


def kernel_for_op(name: str):
    """The KernelSpecs covering a dispatch-plane op name (may be [])."""
    return list(_BY_OP.get(name, ()))


# flag gate, re-read only when the config generation moves — the
# disabled path is one int compare + one bool test (<5 µs contract)
_GEN = -1
_TRY = False


def _refresh_gate() -> None:
    global _GEN, _TRY
    g = config.generation()
    if g == _GEN:
        return
    mode = config.get_flag("KERNELS")
    if mode == "on":
        _TRY = True
    elif mode == "off":
        _TRY = False
    else:  # auto: only where Mosaic compiles natively
        from . import on_tpu

        _TRY = on_tpu()
    _GEN = g


_WARNED_CAPABILITY = False
_WARNED_KERNELS = set()


def dispatch_kernel(
    op: dict, table: Table, rest: Sequence[Table], name: str
) -> Optional[Table]:
    """Run one op through the kernel tier. Returns the (possibly
    padded) result Table, or None when no kernel applies / the flag is
    off / the launch failed — the caller then runs the bucketed/exact
    path. Never changes bytes, only performance."""
    global _WARNED_CAPABILITY
    _refresh_gate()
    if not _TRY:
        return None
    specs = _BY_OP.get(name)
    if specs is None:
        return None
    ok, why = pallas_capability()
    if not ok:
        metrics.counter_add("kernel.declines")
        if not _WARNED_CAPABILITY:
            _WARNED_CAPABILITY = True
            log.log(
                "WARN", "kernels", "pallas_unavailable", detail=why,
            )
        return None
    from .. import bucketed as bk

    for spec in specs:
        reason = spec.applicable(op, table, rest)
        if reason is not None:
            metrics.counter_add("kernel.declines")
            continue
        # the span makes each kernel its own flight-recorder/trace
        # track (nested inside dispatch.<op>); declines and fallbacks
        # are handled INSIDE it so they exit the span cleanly
        with metrics.span("kernel." + spec.name):
            try:
                faults.inject("kernel")
                out = spec.runner(op, table, rest)
            except (KernelDecline, bk._Decline):
                metrics.counter_add("kernel.declines")
                continue
            except (faults.Cancelled, faults.DeadlineExceeded):
                raise
            # srt: allow-broad-except(semantics-preserving fallback: the bucketed/exact path re-runs the op and raises the real error)
            except Exception as e:
                # the kernel tier must never change semantics: any
                # runner failure (Mosaic lowering refusal, seeded
                # chaos fault, shape surprise) replays on the exact
                # path, which raises the real error if the op itself
                # is at fault
                metrics.counter_add("kernel.fallbacks")
                profiler.note_fallback("kernel")
                if spec.name not in _WARNED_KERNELS:
                    _WARNED_KERNELS.add(spec.name)
                    log.log(
                        "WARN", "kernels", "kernel_runner_failed",
                        kernel=spec.name, op=name,
                        error=f"{type(e).__name__}: {str(e)[:200]}",
                    )
                return None
        metrics.counter_add("kernel.launches")
        return out
    return None
