"""Fault-tolerant distributed execution: lineage replay + mesh degradation.

The reference stack survives executor loss and shuffle-fetch failure
through Spark's task-retry and shuffle-recovery semantics (the plugin
layer the JNI jar serves): a lost shuffle block re-runs only the map
tasks that produced it, and a lost executor shrinks the pool without
killing the job. This module is that analog for the mesh tier:

* :func:`run_collective` — the retry boundary every host-side shard_map
  launch in the parallel tier routes through. The host wrapper's
  closure IS the recorded lineage: it captures the input shards and the
  partition spec (counts, capacities, splitters), so a transient
  collective failure re-runs only the failed exchange — never upstream
  work. Metered as ``shuffle.retries`` / ``shuffle.giveups``. Donated
  inputs are at-most-once (PR 10's doomed-replay rule): the raw error
  surfaces with ZERO retries because the launch may have consumed its
  buffers.
* :class:`MeshRunner` — the degradation ladder. A stage whose
  collective failures outlive the retry budget probes mesh health
  (:class:`~.mesh.MeshHealth` heartbeat with deadline), remeshes to the
  surviving device count (halving down the power-of-two ladder),
  re-plans partition capacity (the stage closure re-derives it from the
  host-side lineage at the new mesh size) and replays the stage on the
  smaller mesh — surfacing ``mesh.degraded`` instants instead of dying.
  Only below ``min_devices`` does it give up, with the typed
  :class:`~..utils.faults.Degraded` the serving tier catches to fall
  back to the single-device exact path.

Injection sites: ``shuffle`` (parallel/shuffle.py host wrappers),
``collective`` (distributed ops + planmesh stages), ``mesh`` (mesh
construction + health probe) — all through the seeded
``SPARK_RAPIDS_TPU_FAULTS`` grammar, so the whole ladder rehearses
deterministically on a CPU mesh.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

from ..utils import config, faults, flight, lockcheck, log, metrics
from .mesh import SHUFFLE_AXIS, MeshHealth, make_mesh


def run_collective(
    label: str,
    launch: Callable[[], object],
    site: str = "collective",
    donated: bool = False,
    max_retries: Optional[int] = None,
):
    """Run one host-side collective launch with lineage-replay retry.

    ``launch`` must be re-runnable from host state alone (the closure
    captures the sharded inputs + partition spec — the lineage), which
    every host wrapper in shuffle.py/distributed.py satisfies: nothing
    is consumed until the launch succeeds. ``donated=True`` declares
    the opposite — the launch may consume its input — and makes the
    boundary at-most-once: the first transient surfaces unchanged,
    zero retries (``shuffle.giveups`` still counts the loss).

    Retry policy is transient-only: an OOM collective re-fails at the
    same shape (capacity re-planning is the MeshRunner ladder's job,
    not a same-shape re-run), and permanent/cancel/deadline classes
    keep :func:`~..utils.faults.run_with_retry` semantics — they
    surface unchanged.
    """
    # the exchange span: timed, and trace-tagged on the flight ring, so
    # a merged trace shows every collective launch (and its retries —
    # same span, same trace: replay never mints a fresh trace id) under
    # the request that ran it
    with metrics.span(label):
        return _run_collective(label, launch, site, donated, max_retries)


def _run_collective(label, launch, site, donated, max_retries):
    attempt = 0
    while True:
        faults.check_cancel()
        try:
            faults.inject(site)
            return launch()
        except (faults.Cancelled, faults.DeadlineExceeded,
                faults.Degraded):
            raise
        except Exception as e:
            cls = faults.classify(e)
            if cls is not faults.TransientDeviceError:
                faults.note_error_class(e, label)
                raise
            if donated:
                # srt: allow-retry-donated(at-most-once gate: a donated launch surfaces its first transient unchanged — this branch precedes every retry)
                metrics.counter_add("shuffle.giveups")
                if flight.enabled():
                    flight.record("I", "shuffle.giveup", f"{label}:donated")
                raise
            limit = (
                faults.retry_max() if max_retries is None
                else int(max_retries)
            )
            if attempt >= limit:
                metrics.counter_add("shuffle.giveups")
                if flight.enabled():
                    flight.record(
                        "I", "shuffle.giveup", f"{label}:{attempt}"
                    )
                if isinstance(e, faults.FaultError):
                    raise
                raise cls(
                    f"{label}: collective retries exhausted after "
                    f"{attempt} attempt(s): "
                    f"{type(e).__name__}: {str(e)[:200]}"
                ) from e
            attempt += 1
            metrics.counter_add("shuffle.retries")
            faults.sleep_backoff(attempt, label, error=e)


class MeshRunner:
    """Owns a mesh and the ladder that shrinks it under persistent
    collective failure.

    ``run_stage(label, stage)`` runs ``stage(mesh)`` — a callable
    re-runnable from host-side lineage — through
    :func:`run_collective`. When a stage's transient failures outlive
    the retry budget, the runner walks down the device ladder: probe
    the candidate smaller mesh with a deadline heartbeat, remesh to the
    surviving count, and REPLAY the stage there (the stage re-derives
    shard layout and partition capacity from its captured inputs at the
    new size). Each step is metered (``mesh.degraded`` counter +
    flight instant). At ``min_devices`` with failures persisting, the
    typed :class:`~..utils.faults.Degraded` surfaces — the serving
    integration's signal to fall back to the single-device exact path
    instead of shedding the tenant.
    """

    def __init__(self, n_devices: Optional[int] = None,
                 axis: str = SHUFFLE_AXIS, min_devices: int = 1,
                 health: Optional[MeshHealth] = None):
        self.axis = axis
        self.requested = (
            len(jax.devices()) if n_devices is None else int(n_devices)
        )
        self.min_devices = max(int(min_devices), 1)
        self.health = health or MeshHealth()
        self._lock = lockcheck.make_lock("mesh.runner")
        self.mesh = make_mesh(self.requested, axis)
        self.degraded = False
        self.stages = 0
        self.replays = 0
        self.degradations = 0

    @property
    def n_devices(self) -> int:
        with self._lock:
            return int(self.mesh.shape[self.axis])

    def run_stage(self, label: str, stage: Callable[[object], object]):
        """Run ``stage(mesh)`` with retry + degradation-replay. The
        whole ladder — replays and degradations included — runs inside
        ONE trace-tagged ``mesh.stage`` span, so the ``mesh.replay`` /
        ``mesh.degraded`` instants are attributed to the ORIGINAL
        request's trace id (a replay never mints a fresh trace)."""
        with self._lock:
            self.stages += 1
        with metrics.span("mesh.stage", stage=label):
            return self._run_stage(label, stage)

    def _run_stage(self, label: str, stage: Callable[[object], object]):
        while True:
            with self._lock:
                mesh = self.mesh
            try:
                return run_collective(label, lambda: stage(mesh))
            except (faults.Cancelled, faults.DeadlineExceeded,
                    faults.Degraded):
                raise
            except Exception as e:
                if faults.classify(e) is not faults.TransientDeviceError:
                    raise
                # retries exhausted at this mesh size: walk the ladder
                self._degrade(label, mesh, e)
                with self._lock:
                    self.replays += 1
                if flight.enabled():
                    flight.record("I", "mesh.replay", label)

    def _degrade(self, label: str, failed_mesh, cause) -> None:
        """Remesh to the surviving device count (or raise Degraded)."""
        n = int(failed_mesh.shape[self.axis])
        while n > self.min_devices:
            n = max(n // 2, self.min_devices)
            try:
                candidate = make_mesh(n, self.axis)
            except (faults.FaultError, ValueError) as e:
                faults.note_error_class(e, "mesh.remesh")
                continue  # this rung is dead too; keep walking down
            if not self.health.probe(candidate, self.axis):
                continue
            with self._lock:
                # another thread may have degraded further already;
                # never grow the mesh back mid-incident
                if int(self.mesh.shape[self.axis]) > n:
                    self.mesh = candidate
                self.degraded = True
                self.degradations += 1
            metrics.counter_add("mesh.degraded")
            metrics.gauge_set("mesh.devices", n)
            if flight.enabled():
                flight.record("I", "mesh.degraded", f"{label}:{n}")
            log.log(
                "WARN", "faults", "mesh_degraded", stage=label,
                devices=n, was=int(failed_mesh.shape[self.axis]),
                cause=f"{type(cause).__name__}: {str(cause)[:200]}",
            )
            return
        metrics.counter_add("mesh.exhausted")
        if flight.enabled():
            flight.record("I", "mesh.exhausted", label)
        raise faults.Degraded(
            f"mesh stage {label!r}: collective failures persist down "
            f"to the {self.min_devices}-device floor; degrade to the "
            "single-device exact path"
        ) from cause

    def to_doc(self) -> dict:
        with self._lock:
            return {
                "axis": self.axis,
                "requested_devices": self.requested,
                "devices": int(self.mesh.shape[self.axis]),
                "min_devices": self.min_devices,
                "degraded": self.degraded,
                "stages": self.stages,
                "replays": self.replays,
                "degradations": self.degradations,
            }
