"""The plain reference: the same request script, in pandas and numpy.

Interprets a traffic file's ``request`` steps over host tables with
straightforward implementations of the plan ops, importing nothing of
the program and taking nothing it made. ``lowprec=True`` is the control
of the check: every FLOAT64 input is carried as float32 and float64 sums
are accumulated in float32 — the step a later PR would be tempted by on
a chip with no native float64. Plan cells have no nulls; an op that
meets one says so and does not guess.
"""

from __future__ import annotations

import numpy as np

from . import plugins, rowformat
from .wirefmt import TYPE_NAMES, Col, Table

SPARK_SEED = 42


def _no_nulls(table: Table, op: str) -> None:
    if any(c.valid is not None and not c.valid.all() for c in table):
        raise ValueError(f"reference {op}: nulls are not defined here")


def _take(table: Table, idx) -> Table:
    return [Col(c.type, c.scale, c.values[idx], None) for c in table]


def _filter(op, tables, lowprec):
    (t,) = tables
    _no_nulls(t, "filter")
    k = op["mask"]
    keep = np.flatnonzero(t[k].values != 0)
    return _take([c for i, c in enumerate(t) if i != k], keep)


def _join(op, tables, lowprec):
    import pandas as pd

    left, right = tables
    _no_nulls(left, "join")
    _no_nulls(right, "join")
    on = list(op["on"])
    ldf = pd.DataFrame({f"l{i}": c.values for i, c in enumerate(left)})
    rdf = pd.DataFrame({
        (f"l{i}" if i in on else f"r{i}"): c.values
        for i, c in enumerate(right)
    })
    df = ldf.merge(rdf, on=[f"l{i}" for i in on], how="inner")
    cols = list(left) + [c for i, c in enumerate(right) if i not in on]
    return [
        Col(c.type, c.scale, df[name].to_numpy(), None)
        for c, name in zip(cols, df.columns)
    ]


def _groupby(op, tables, lowprec):
    import pandas as pd

    (t,) = tables
    _no_nulls(t, "groupby")
    by = list(op["by"])
    df = pd.DataFrame({f"c{i}": c.values for i, c in enumerate(t)})
    keys = [f"c{i}" for i in by]
    g = df.groupby(keys, sort=True)
    out = g.size().reset_index()
    res = [Col(t[i].type, t[i].scale, out[f"c{i}"].to_numpy(), None) for i in by]
    for a in op["aggs"]:
        src = t[a["column"]]
        name = f"c{a['column']}"
        if a["agg"] == "count":
            res.append(Col("INT64", 0, g[name].count().to_numpy().astype(np.int64)))
        elif a["agg"] == "sum" and src.type == "FLOAT64" and lowprec:
            # float32 all the way: rows in key order, summed group by group
            order = np.lexsort([df[k].to_numpy() for k in reversed(keys)])
            starts = np.concatenate([[0], np.cumsum(out[0].to_numpy())[:-1]])
            v32 = src.values[order].astype(np.float32)
            s32 = np.add.reduceat(v32, starts, dtype=np.float32)
            res.append(Col("FLOAT64", 0, s32.astype(np.float64)))
        elif a["agg"] == "sum":
            res.append(Col(src.type, src.scale, g[name].sum().to_numpy()))
        else:
            raise ValueError(f"reference groupby: no aggregation {a['agg']!r}")
    return res


def _sort_by(op, tables, lowprec):
    (t,) = tables
    _no_nulls(t, "sort_by")
    keys = []
    for k in reversed(op["keys"]):
        v = t[k["column"]].values
        if not k.get("ascending", True):
            if not np.issubdtype(v.dtype, np.integer):
                raise ValueError("reference sort_by: descending needs integers")
            v = -v
        keys.append(v)
    return _take(t, np.lexsort(keys))


def murmur3_long(values: np.ndarray, seed: int = SPARK_SEED) -> np.ndarray:
    """Spark's ``Murmur3_x86_32.hashLong`` over int64 values -> int32."""
    with np.errstate(over="ignore"):
        u = values.astype(np.int64).view(np.uint64)
        h = np.full(u.shape, seed, np.uint32)
        for half in ((u & np.uint64(0xFFFFFFFF)), (u >> np.uint64(32))):
            k = half.astype(np.uint32) * np.uint32(0xCC9E2D51)
            k = (k << np.uint32(15)) | (k >> np.uint32(17))
            k = k * np.uint32(0x1B873593)
            h = h ^ k
            h = (h << np.uint32(13)) | (h >> np.uint32(19))
            h = h * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ np.uint32(8)
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h.view(np.int32)


def _partition(op, tables, lowprec):
    (t,) = tables
    _no_nulls(t, "partition")
    if op.get("kind") != "hash" or len(op["keys"]) != 1:
        raise ValueError("reference partition: one hashed INT64 key only")
    key = t[op["keys"][0]]
    if key.type != "INT64":
        raise ValueError("reference partition: the key must be INT64")
    num = int(op["num"])
    pid = np.mod(np.mod(murmur3_long(key.values).astype(np.int64), num) + num, num)
    return _take(t, np.argsort(pid, kind="stable"))


def _to_rows(op, tables, lowprec):
    (t,) = tables
    return [Col("LIST", 5, rowformat.pack(t), None)]


def _from_rows(op, tables, lowprec):
    (t,) = tables
    types = [TYPE_NAMES[int(x)] for x in op["type_ids"]]
    return rowformat.unpack(t[0].values, types, op["scales"])


OPS = {
    "filter": _filter, "join": _join, "groupby": _groupby,
    "sort_by": _sort_by, "partition": _partition, "to_rows": _to_rows,
    "from_rows": _from_rows,
}


def run_plan(ops, tables, lowprec: bool = False) -> Table:
    """One plan: the first table flows through, the rest feed joins."""
    head, rest = tables[0], list(tables[1:])
    for op in ops:
        apply = OPS.get(op["op"]) or plugins.find("refop", op["op"], "apply")
        args = [head] + ([rest.pop(0)] if op["op"] == "join" else [])
        head = apply(op, args, lowprec)
    return head


def _through_f32(table: Table) -> Table:
    return [
        Col(c.type, c.scale, c.values.astype(np.float32).astype(np.float64), c.valid)
        if c.type == "FLOAT64" else c
        for c in table
    ]


def run_request(steps, env: dict, lowprec: bool = False) -> dict:
    """The request script on host tables -> {answer name: table}."""
    env = {k: _through_f32(v) if lowprec else v for k, v in env.items()}
    answers = {}
    for s in steps:
        do = s["do"]
        if do == "plan":
            env[s["out"]] = run_plan(s["plan"], [env[t] for t in s["tables"]], lowprec)
        elif do == "stream":
            for b, o in zip(s["batches"], s["out"]):
                env[o] = run_plan(s["plan"], [env[b]], lowprec)
                if s.get("answer"):
                    answers[o] = env[o]
        elif do in ("download", "upload"):
            src = s["table"] if do == "download" else s["batch"]
            env[s["out"]] = env[src]
            if s.get("answer"):
                answers[s["out"]] = env[s["out"]]
        elif do != "free":
            raise ValueError(f"reference: no step {do!r}")
    return answers
