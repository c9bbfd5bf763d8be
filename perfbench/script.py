"""The one general generator: a traffic file's request script, served.

A traffic file names the tables of its configuration it uses (resident
on the device or sent with each request, fixed or one per variant) and
the steps of one request: ``plan`` over resident tables, ``download``,
``upload``, ``stream`` of host batches, ``free``. The same steps drive
the program here and the plain reference in ``reference.py``. Each step
runs under a ``client.<step>`` annotation, so a device trace can say
what the client was waiting for in an idle gap, and client-side serde
is clocked apart (``serde_s``).
"""

from __future__ import annotations

import time

import jax.profiler
import numpy as np

from . import datagen
from .wirefmt import table_rows, unwire, wire


class Data:
    """The cell's host tables, made from the seed: ``env(v)`` gives the
    tables of variant ``v`` by the names the script uses."""

    def __init__(self, config: dict, traffic: dict, seed: int, rehearse: bool):
        self.variants = int(traffic["variants"])
        self.uses = traffic["tables"]
        sizes = config.get("rehearse_rows", {}) if rehearse else {}
        self._fixed, self._varied = {}, {}
        for k, (name, use) in enumerate(sorted(self.uses.items())):
            spec = config["tables"][use["table"]]
            rows = int(sizes.get(use["table"], spec["rows"]))
            n = self.variants if use.get("vary") else 1
            made = [
                datagen.make_table(spec, rows, np.random.default_rng([seed, k, v]))
                for v in range(n)
            ]
            if use.get("vary"):
                self._varied[name] = made
            else:
                self._fixed[name] = made[0]

    def env(self, variant: int) -> dict:
        out = dict(self._fixed)
        out.update({k: v[variant] for k, v in self._varied.items()})
        return out


class Session:
    """One client connection and what it keeps resident on the device."""

    def __init__(self, client, data: Data, steps):
        self.client = client
        self.data = data
        self.steps = steps
        self.serde_s = 0.0
        self._resident = {}

    def upload_resident(self) -> None:
        for name, use in sorted(self.data.uses.items()):
            if not use.get("resident"):
                continue
            n = self.data.variants if use.get("vary") else 1
            self._resident[name] = [
                self.client.upload(wire(self.data.env(v)[name])) for v in range(n)
            ]

    def _clocked(self, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        self.serde_s += time.perf_counter() - t0
        return out

    def request(self, variant: int):
        """One request -> ({answer name: host table}, input rows)."""
        c = self.client
        host = self.data.env(variant)
        ids = {k: v[variant % len(v)] for k, v in self._resident.items()}
        answers = {}
        for s in self.steps:
            do = s["do"]
            with jax.profiler.TraceAnnotation("client." + do):
                if do == "plan":
                    ids[s["out"]] = c.plan(s["plan"], [ids[t] for t in s["tables"]])
                elif do == "download":
                    host[s["out"]] = self._clocked(unwire, c.download(ids[s["table"]]))
                elif do == "upload":
                    ids[s["out"]] = c.upload(self._clocked(wire, host[s["batch"]]))
                elif do == "stream":
                    batches = [self._clocked(wire, host[b]) for b in s["batches"]]
                    for o, r in zip(s["out"], c.stream(s["plan"], batches)):
                        host[o] = self._clocked(unwire, r)
                elif do == "free":
                    c.free(ids.pop(s["table"]))
                else:
                    raise ValueError(f"traffic: no step {do!r}")
            if s.get("answer"):
                for o in ([s["out"]] if isinstance(s["out"], str) else s["out"]):
                    answers[o] = host[o]
        return answers


def rows_in(traffic: dict, data: Data) -> int:
    """Input rows of one request: the rows of the table the traffic names."""
    return table_rows(data.env(0)[traffic["rows_in"]])
