#!/usr/bin/env python3
"""srt-check — repo-invariant static analyzer for the TPU runtime.

Eleven PRs of CONTRIBUTING prose turned into machine-checked passes:
the invariants below used to live in reviewers' heads and each of them
has been violated (or nearly) by a landed PR. This is the repo's
``compute-sanitizer``/``cuda-memcheck`` CI lane analog (see the README
parity table) — the static half; the dynamic half is the lock-order
detector in ``spark_rapids_jni_tpu/utils/lockcheck.py``.

Passes (each emits ``file:line:col`` findings):

* **SRT001 env-outside-config** — ``SPARK_RAPIDS_TPU_*`` environment
  reads anywhere but ``utils/config.py``. Every knob rides the flag
  plane (loud-fail parsers, generation-counter cache invalidation); a
  raw read is invisible to ``set_flag`` and silently un-parsed.
* **SRT002 broad-except** — ``except Exception``/``BaseException``
  handlers that swallow or reclassify without routing through the
  ``faults`` taxonomy and without a bare re-``raise``. Retrying an
  unclassified failure is how retry storms start (PR 10). Justified
  sites carry ``# srt: allow-broad-except(<reason>)``.
* **SRT003 hot-env-read** — any ``os.environ``/``os.getenv`` access
  inside a function body in the package. Module-level one-time reads
  are fine; per-call reads are the ~6 µs/op mistake the cached-gate
  pattern (``config.generation()``) exists to prevent.
* **SRT004 wallclock-in-replay** — ``time.time``/``datetime.now``/
  stdlib ``random`` in the determinism-critical modules (fault
  injection, compile-cache keys, plan fusion): seeded chaos replay and
  cache-key stability both break the moment a wall clock leaks in.
* **SRT005 retry-on-donated** — ``run_with_retry`` wrapping a call
  site that passes ``donate=True``: a donated segment consumed its
  input buffers, so a replay reads deleted memory. Retry is at-most-
  once for donated work (PR 5's doomed-replay rule).
* **SRT006 metric-name** — metric/flight event name literals that
  don't match the dotted-name convention (``^[a-z0-9_]+(\\.[a-z0-9_]+
  )*$``) or whose first segment isn't a registered namespace. One
  typo'd namespace splits a counter across two dashboard rows forever.
* **SRT009 host-sync** — implicit device->host synchronizations in the
  hot dispatch modules (``plan.py``, ``bucketed.py``): ``bool()``/
  ``int()``/``float()`` over device values (``.data``/``.validity``/
  ``.lengths`` attributes, locals bound from device-producing calls),
  ``.item()``, and ``np.asarray`` on non-constants. Each sync stalls
  the launch pipeline; deliberate ones (the exact path's row-count
  reads) carry ``# srt: allow-host-sync(<reason>)``.
* **SRT010 stats-append** — append-mode ``open()`` on the plan-stats
  store anywhere but ``planstats._open_append``: the store's crash
  tolerance rests on every writer emitting CRC-framed records through
  the one helper (truncate-to-good self-heal, rotation, flush
  discipline). A raw ``open(..., "a")`` on a stats path bypasses the
  framing, and a torn write there corrupts history for every later
  reader. Justified sites carry ``# srt: allow-stats-append(<reason>)``.
* **SRT011 trace-context** — trace-plane discipline, both halves: a
  string-literal span name handed to ``tracing.span_begin`` /
  ``trace_range`` must follow the same dotted-name grammar and
  registered-namespace rule as SRT006 (span names land on the flight
  ring and merge into dashboards next to metric names — one typo
  splits a request's spans across two rows); and serving modules must
  not hand-roll trace ids (``uuid``/``os.urandom``/``secrets`` flowing
  into a trace-named binding): ``tracing.new_context()`` is the one
  mint, which is what keeps ids W3C-shaped and the ambient context the
  single source of truth. Justified sites carry
  ``# srt: allow-trace-context(<reason>)``.
* **SRT012 kernel-parity** — the kernel-tier registries
  (``kernels/registry.py``): the ``KERNEL_NAMES``
  literal, the ``_REGISTRY`` dict keys, and plancheck's
  ``_KERNEL_RULES`` table must hold exactly the same kernel names, the
  ``kernel`` metric namespace must be registered here, and every
  ``_REGISTRY`` entry must be a well-formed ``KernelSpec(...)`` whose
  name argument matches its key. A kernel added to one registry
  without the others would launch untagged (no static eligibility,
  unattributed counters) or tag ops the runtime cannot accelerate.
* **SRT000 bad-pragma** — a suppression pragma with a missing reason
  or an unknown pass name is itself a finding: silent suppression
  grows back the prose problem this tool replaces.

Pragma grammar (the finding line or the line directly above)::

    # srt: allow-<pass-slug>(<non-empty reason>)

Baseline workflow: ``tools/srt_check_baseline.json`` holds
fingerprints of grandfathered findings. New findings FAIL (exit 1);
baselined ones report and burn down (a fixed finding leaves a stale
baseline entry, listed so it can be pruned with ``--write-baseline``).
Fingerprints hash (pass, path, enclosing scope, normalized source
line) — not line numbers — so unrelated edits don't churn the file.

Usage::

    python tools/srt_check.py                  # scan repo, gate on new
    python tools/srt_check.py --json           # machine-readable
    python tools/srt_check.py --write-baseline # re-grandfather all
    python tools/srt_check.py path.py ...      # scan specific files
"""

from __future__ import annotations

import argparse
import ast
import hashlib
import io
import json
import os
import re
import sys
import tokenize
from typing import Dict, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "srt_check_baseline.json"
)

# scan roots relative to the repo root (tests are exempt: test code
# legitimately monkeypatches environs and provokes broad failures)
DEFAULT_ROOTS = ("spark_rapids_jni_tpu", "tools")

ENV_PREFIX = "SPARK_RAPIDS_TPU_"
CONFIG_MODULE = os.path.join("spark_rapids_jni_tpu", "utils", "config.py")

# SRT004 scope: the modules where wall-clock / unseeded randomness
# breaks seeded replay or cache-key stability
DETERMINISM_MODULES = (
    os.path.join("spark_rapids_jni_tpu", "utils", "faults.py"),
    os.path.join("spark_rapids_jni_tpu", "utils", "buckets.py"),
    os.path.join("spark_rapids_jni_tpu", "plan.py"),
)

# SRT009 scope: the hot dispatch modules where an implicit host sync
# stalls the launch pipeline (each one blocks until the device drains)
HOT_SYNC_MODULES = (
    os.path.join("spark_rapids_jni_tpu", "plan.py"),
    os.path.join("spark_rapids_jni_tpu", "bucketed.py"),
    # the distributed tier: syncs here stall every device on the mesh,
    # so the deliberate ones (two-phase sizing, overflow verdicts,
    # result gathers) carry allow-host-sync pragmas and anything new
    # gets flagged
    os.path.join("spark_rapids_jni_tpu", "parallel", "mesh.py"),
    os.path.join("spark_rapids_jni_tpu", "parallel", "shuffle.py"),
    os.path.join("spark_rapids_jni_tpu", "parallel", "distributed.py"),
    os.path.join("spark_rapids_jni_tpu", "parallel", "planmesh.py"),
)

# attribute names that denote DEVICE buffers on a Column/Table — an
# int()/bool()/float() over an expression touching one is a sync
DEVICE_ATTRS = frozenset({"data", "validity", "lengths", "offsets"})

# attribute reads that are HOST scalars even on device-holding objects
# (Table/Column bookkeeping) — reading one is not a sync
HOST_ATTRS = frozenset({
    "row_count", "logical_row_count", "logical_rows", "names",
    "dtype", "scale", "id", "shape", "ndim", "size",
})

# call names whose result is a HOST value: assigning a local from one
# of these does NOT mark it device (everything else conservatively
# does — in the hot modules most call results are jax arrays)
HOST_CALLS = frozenset({
    "int", "float", "bool", "str", "len", "range", "enumerate", "zip",
    "list", "tuple", "dict", "set", "sorted", "min", "max", "sum",
    "abs", "get", "isinstance", "getattr", "hasattr", "repr", "format",
    "join", "split", "append", "pop", "keys", "values", "items",
    "perf_counter", "monotonic", "bucket_for", "enabled", "get_flag",
    "generation", "segment_plan", "op_fusable", "op_bucketable",
    "table_bytes", "dumps", "loads",
})

# the faults-taxonomy vocabulary whose presence in a broad handler
# counts as "routed through the taxonomy" (SRT002)
FAULTS_NAMES = frozenset({
    "faults", "classify", "classify_text", "run_with_retry",
    "FaultError", "TransientDeviceError", "PermanentError",
    "ResourceExhausted", "Cancelled", "DeadlineExceeded", "Degraded",
    "DependencyFailed",
    # taxonomy entry points: feeding a breaker / the error-class
    # counters IS routing the failure through the fault plane
    "note_failure", "note_success", "note_error_class",
})

# SRT006: registered metric/flight namespace roots. A NEW subsystem
# registers its namespace here (one line, reviewed) — that is what
# makes the dotted names "registered" instead of folklore.
METRIC_NAMESPACES = frozenset({
    "op", "wire", "resident", "dispatch", "plan", "bucket",
    "compile_cache", "pipeline", "hbm", "span", "span_ms", "serving",
    "session", "retry", "faults", "breaker", "fault", "spill", "lock",
    "shuffle", "distributed", "io", "probe", "groupby",
    "join", "sort", "profile", "stream", "checkpoint", "restore",
    "mesh", "planstats", "drift", "partition", "client", "compile",
    "kernel", "project", "device", "jax", "frames",
})
METRIC_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)*$")

# metrics-registry entry points whose FIRST string arg is a metric
# name; flight.record's name is its SECOND arg
METRIC_FNS = frozenset({
    "counter_add", "bytes_add", "timer_record", "gauge_set",
    "hist_observe", "self_time_record", "span",
})

# SRT011: tracing entry points whose FIRST string arg is a span name
# (rides the SRT006 grammar: span names land on the flight ring next
# to metric names)
TRACE_SPAN_FNS = frozenset({"span_begin", "trace_range"})

# SRT011: calls that mint random identity. In serving modules a result
# of one of these flowing into a trace-named binding bypasses
# tracing.new_context(), the one sanctioned trace-id mint.
_MINT_CALLS = frozenset({
    "uuid1", "uuid4", "urandom", "token_hex", "token_bytes",
    "getrandbits",
})

# pass -> pragma slug; a suppression comment is "srt:" then
# "allow-" + slug + "(reason)" (see the module docstring)
PASS_PRAGMAS = {
    "SRT001": "env-read",
    "SRT002": "broad-except",
    "SRT003": "hot-env",
    "SRT004": "wallclock",
    "SRT005": "retry-donated",
    "SRT006": "metric-name",
    "SRT009": "host-sync",
    "SRT010": "stats-append",
    "SRT011": "trace-context",
    "SRT012": "kernel-parity",
}
PRAGMA_RE = re.compile(r"#\s*srt:\s*allow-([a-z0-9-]+)\(([^)]*)\)")
LOOSE_PRAGMA_RE = re.compile(r"#\s*srt:\s*allow-")
KNOWN_PRAGMAS = frozenset(PASS_PRAGMAS.values())


class Finding:
    __slots__ = ("pass_id", "path", "line", "col", "message",
                 "fingerprint", "baselined")

    def __init__(self, pass_id: str, path: str, line: int, col: int,
                 message: str):
        self.pass_id = pass_id
        self.path = path
        self.line = line
        self.col = col
        self.message = message
        self.fingerprint = ""
        self.baselined = False

    def to_doc(self) -> dict:
        return {
            "pass": self.pass_id,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "fingerprint": self.fingerprint,
            "baselined": self.baselined,
        }

    def render(self) -> str:
        tag = " [baselined]" if self.baselined else ""
        return (
            f"{self.path}:{self.line}:{self.col}: "
            f"{self.pass_id} {self.message}{tag}"
        )


# ---------------------------------------------------------------------------
# pragma handling
# ---------------------------------------------------------------------------


class _Pragmas:
    """Suppression pragmas of one file: line -> (slug, reason).

    Scans REAL comment tokens (via ``tokenize``), not raw line text —
    a docstring or string literal that happens to quote the pragma
    grammar (this file's own docs, error messages) is not a pragma.
    """

    def __init__(self, source: str, relpath: str):
        self.by_line: Dict[int, Tuple[str, str]] = {}
        self.bad: List[Finding] = []
        self.used: set = set()
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(source).readline
            ))
        except (tokenize.TokenError, IndentationError, SyntaxError):
            return  # scan_file already reports the syntax error
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            i, col = tok.start
            text = tok.string
            m = PRAGMA_RE.search(text)
            if not m:
                # a pragma-looking comment that doesn't parse (e.g. no
                # parens, a typo'd slug shape) is a silent no-op — flag
                if LOOSE_PRAGMA_RE.search(text):
                    self.bad.append(Finding(
                        "SRT000", relpath, i, col,
                        "malformed srt pragma: expected "
                        "'# srt: allow-<pass>(<reason>)'",
                    ))
                continue
            slug, reason = m.group(1), m.group(2).strip()
            if slug not in KNOWN_PRAGMAS:
                self.bad.append(Finding(
                    "SRT000", relpath, i, col,
                    f"unknown srt pragma 'allow-{slug}' (known: "
                    + ", ".join(
                        f"allow-{s}" for s in sorted(KNOWN_PRAGMAS)
                    ) + ")",
                ))
                continue
            if not reason:
                self.bad.append(Finding(
                    "SRT000", relpath, i, col,
                    f"srt pragma 'allow-{slug}' requires a non-empty "
                    "reason: the justification IS the point",
                ))
                continue
            self.by_line[i] = (slug, reason)

    def suppresses(self, pass_id: str, line: int) -> bool:
        slug = PASS_PRAGMAS[pass_id]
        for ln in (line, line - 1):
            got = self.by_line.get(ln)
            if got is not None and got[0] == slug:
                self.used.add(ln)
                return True
        return False


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------


def _is_environ(node: ast.AST) -> bool:
    """True for the expression ``os.environ``."""
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "environ"
        and isinstance(node.value, ast.Name)
        and node.value.id == "os"
    )


def _env_read_key(node: ast.AST) -> Optional[Tuple[ast.AST, Optional[str]]]:
    """If ``node`` reads an environment variable, return (node, key or
    None-when-dynamic); else None. Writes (``os.environ[k] = v``) pass."""
    if isinstance(node, ast.Call):
        f = node.func
        # os.environ.get(...) / os.environ.setdefault(...)
        if (
            isinstance(f, ast.Attribute)
            and f.attr in ("get", "setdefault")
            and _is_environ(f.value)
        ) or (
            # os.getenv(...)
            isinstance(f, ast.Attribute)
            and f.attr == "getenv"
            and isinstance(f.value, ast.Name)
            and f.value.id == "os"
        ):
            key = None
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                key = node.args[0].value
            return node, key
    if isinstance(node, ast.Subscript) and _is_environ(node.value):
        if isinstance(node.ctx, ast.Load):
            key = None
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                key = sl.value
            return node, key
    if isinstance(node, ast.Compare) and any(
        isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
    ):
        for cand in node.comparators:
            if _is_environ(cand):
                key = None
                if isinstance(node.left, ast.Constant) and isinstance(
                    node.left.value, str
                ):
                    key = node.left.value
                return node, key
    return None


def _call_name(node: ast.Call) -> str:
    """Trailing name of the called function (``a.b.c()`` -> ``c``)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _names_in(tree: ast.AST):
    for sub in ast.walk(tree):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr
            if isinstance(sub.value, ast.Name):
                yield sub.value.id


def _mints_id(node: ast.AST) -> bool:
    """True when the subtree calls a random-identity mint
    (``uuid.uuid4()``, ``os.urandom()``, ``secrets.token_hex()``...)."""
    return any(
        isinstance(sub, ast.Call) and _call_name(sub) in _MINT_CALLS
        for sub in ast.walk(node)
    )


def _trace_named(node: ast.AST) -> bool:
    """True when a binding target / dict key names trace identity
    (``trace_id = ...``, ``header["traceparent"] = ...``)."""
    if isinstance(node, ast.Name):
        return "trace" in node.id
    if isinstance(node, ast.Attribute):
        return "trace" in node.attr
    if isinstance(node, ast.Subscript):
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return "trace" in sl.value
        return _trace_named(node.value)
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return "trace" in node.value
    return False


# ---------------------------------------------------------------------------
# per-file analysis
# ---------------------------------------------------------------------------


class _FileChecker(ast.NodeVisitor):
    def __init__(self, relpath: str, source: str, pragmas: _Pragmas):
        self.relpath = relpath
        self.pragmas = pragmas
        self.findings: List[Finding] = []
        self.scope: List[str] = []
        self.func_depth = 0
        norm = relpath.replace("/", os.sep)
        self.in_package = norm.startswith("spark_rapids_jni_tpu" + os.sep)
        self.is_config = norm == CONFIG_MODULE
        self.determinism = norm in DETERMINISM_MODULES
        self.hot_sync = norm in HOT_SYNC_MODULES
        # SRT011 mint-check scope: the serving tier (tracing.py itself
        # owns the os.urandom mint and lives in utils/)
        self.in_serving = norm.startswith(
            os.path.join("spark_rapids_jni_tpu", "serving") + os.sep
        )
        # SRT009: per-function sets of local names bound from
        # device-producing calls (conservative: any call not in
        # HOST_CALLS and not itself flagged as a sync)
        self._device_locals: List[set] = []

    # -- bookkeeping ------------------------------------------------------
    def _emit(self, pass_id: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        if self.pragmas.suppresses(pass_id, line):
            return
        self.findings.append(
            Finding(pass_id, self.relpath, line, col, message)
        )

    def _scoped(self, name: str, node, is_func: bool):
        self.scope.append(name)
        if is_func:
            self.func_depth += 1
        self.generic_visit(node)
        if is_func:
            self.func_depth -= 1
        self.scope.pop()

    def visit_FunctionDef(self, node):
        self._device_locals.append(set())
        self._scoped(node.name, node, True)
        self._device_locals.pop()

    def visit_AsyncFunctionDef(self, node):
        self._device_locals.append(set())
        self._scoped(node.name, node, True)
        self._device_locals.pop()

    def visit_Lambda(self, node):
        self.func_depth += 1
        self.generic_visit(node)
        self.func_depth -= 1

    def visit_ClassDef(self, node):
        self._scoped(node.name, node, False)

    # -- SRT001 / SRT003: env reads ---------------------------------------
    def _check_env(self, node) -> None:
        got = _env_read_key(node)
        if got is None:
            return
        _, key = got
        if key is not None and key.startswith(ENV_PREFIX) \
                and not self.is_config:
            self._emit(
                "SRT001", node,
                f"{key} read outside utils/config.py — declare a Flag "
                "and use config.get_flag (loud-fail parse + generation-"
                "cached gates)",
            )
            return  # one finding per site; SRT003 would double-report
        if self.in_package and not self.is_config and self.func_depth > 0:
            self._emit(
                "SRT003", node,
                "environ read inside a function body — per-call env "
                "reads cost ~6us each; cache on config.generation() "
                "(the metrics-gate pattern) or read once at module "
                "scope",
            )

    def visit_Subscript(self, node):
        self._check_env(node)
        self.generic_visit(node)

    def visit_Compare(self, node):
        self._check_env(node)
        self.generic_visit(node)

    # -- SRT002: broad excepts --------------------------------------------
    def _broad_types(self, node: ast.ExceptHandler) -> List[str]:
        out = []
        t = node.type
        cands = t.elts if isinstance(t, ast.Tuple) else [t]
        for c in cands:
            if isinstance(c, ast.Name) and c.id in (
                "Exception", "BaseException"
            ):
                out.append(c.id)
        return out

    def visit_ExceptHandler(self, node):
        # SRT002 applies to the runtime package, where the faults
        # taxonomy lives; tools are offline drivers whose broad
        # excepts are best-effort harness resilience by design
        broad = (
            self._broad_types(node)
            if node.type is not None and self.in_package else []
        )
        if broad:
            body_names = set()
            reraises = False
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Raise) and sub.exc is None:
                        reraises = True
                body_names.update(
                    n for stmt2 in [stmt] for n in _names_in(stmt2)
                )
            if not reraises and not (body_names & FAULTS_NAMES):
                self._emit(
                    "SRT002", node,
                    f"broad 'except {'/'.join(broad)}' neither "
                    "re-raises nor routes through the faults taxonomy "
                    "(classify / typed FaultError) — add "
                    "'# srt: allow-broad-except(<reason>)' if the "
                    "swallow is deliberate",
                )
        self.generic_visit(node)

    # -- SRT009: implicit host syncs in the hot dispatch modules ----------
    def _is_device_expr(self, expr: ast.AST) -> bool:
        """Could ``expr`` hold a device value? Attribute reads of device
        buffers, locals bound from device-producing calls, and direct
        jnp/jax calls count; host-scalar attribute reads (row counts,
        dtypes) and HOST_CALLS results don't."""
        locals_ = self._device_locals[-1] if self._device_locals else set()

        def dev(n: ast.AST) -> bool:
            if isinstance(n, ast.Attribute):
                if n.attr in DEVICE_ATTRS:
                    return True
                if n.attr in HOST_ATTRS:
                    return False  # host bookkeeping on a device object
                return dev(n.value)
            if isinstance(n, ast.Name):
                return n.id in locals_
            if isinstance(n, ast.Call):
                root = n.func
                while isinstance(root, ast.Attribute):
                    root = root.value
                if isinstance(root, ast.Name) and root.id in (
                    "jnp", "jax", "lax"
                ):
                    return True
                if _call_name(n) in HOST_CALLS:
                    return False  # host-valued helper
                return any(dev(a) for a in n.args)
            return any(dev(c) for c in ast.iter_child_nodes(n))

        return dev(expr)

    def _classify_assign(self, node: ast.Assign) -> None:
        if not (self.hot_sync and self._device_locals):
            return
        v = node.value
        is_device = False
        if isinstance(v, ast.Call):
            root = v.func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in (
                "jnp", "jax", "lax"
            ):
                # jnp.sum/jnp.max/... produce device arrays even though
                # the bare names shadow HOST_CALLS entries
                is_device = True
            else:
                is_device = _call_name(v) not in HOST_CALLS
        elif isinstance(v, (ast.Name, ast.Attribute, ast.Subscript,
                            ast.IfExp, ast.BinOp)):
            is_device = self._is_device_expr(v)
        targets: List[str] = []
        for t in node.targets:
            if isinstance(t, ast.Name):
                targets.append(t.id)
            elif isinstance(t, ast.Tuple):
                targets.extend(
                    e.id for e in t.elts if isinstance(e, ast.Name)
                )
        locals_ = self._device_locals[-1]
        for name in targets:
            if is_device:
                locals_.add(name)
            else:
                locals_.discard(name)

    def visit_Assign(self, node):
        self._classify_assign(node)
        if self.in_serving and any(
            _trace_named(t) for t in node.targets
        ) and _mints_id(node.value):
            self._emit(
                "SRT011", node,
                "hand-rolled trace id in a serving module — "
                "tracing.new_context() / tracing.ensure_context() is "
                "the one mint (W3C-shaped ids, ambient context as the "
                "single source of truth)",
            )
        self.generic_visit(node)

    def visit_Dict(self, node):
        if self.in_serving:
            for k, v in zip(node.keys, node.values):
                if k is not None and _trace_named(k) and _mints_id(v):
                    self._emit(
                        "SRT011", v,
                        "hand-rolled trace id under a trace-named key "
                        "in a serving module — mint through "
                        "tracing.new_context() / ensure_context()",
                    )
        self.generic_visit(node)

    def _check_host_sync(self, node: ast.Call, name: str) -> None:
        if not self.hot_sync or self.func_depth == 0:
            return
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "item" \
                and not node.args:
            self._emit(
                "SRT009", node,
                ".item() is an implicit device->host sync (blocks until "
                "the device drains) — keep the value on device or mark "
                "a deliberate sync with '# srt: allow-host-sync(<why>)'",
            )
            return
        if (
            name == "asarray"
            and isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id == "np"
            and node.args
            and not isinstance(node.args[0], ast.Constant)
        ):
            self._emit(
                "SRT009", node,
                "np.asarray on a (potentially device) value is an "
                "implicit transfer+sync in a hot dispatch module — use "
                "jnp ops, or mark with '# srt: allow-host-sync(<why>)'",
            )
            return
        if (
            isinstance(f, ast.Name)
            and f.id in ("bool", "int", "float")
            and node.args
            and self._is_device_expr(node.args[0])
        ):
            self._emit(
                "SRT009", node,
                f"{f.id}() over a device value is an implicit "
                "device->host sync (stalls the launch pipeline) — "
                "deliberate syncs carry "
                "'# srt: allow-host-sync(<why>)'",
            )

    # -- SRT004/005/006: calls --------------------------------------------
    def visit_Call(self, node):
        self._check_env(node)
        name = _call_name(node)
        self._check_host_sync(node, name)

        if self.determinism:
            f = node.func
            if isinstance(f, ast.Attribute) and isinstance(
                f.value, ast.Name
            ):
                mod, attr = f.value.id, f.attr
                if (mod == "time" and attr in ("time", "time_ns")) or (
                    mod == "random"
                ) or (
                    mod in ("datetime", "date") and attr in (
                        "now", "utcnow", "today"
                    )
                ):
                    self._emit(
                        "SRT004", node,
                        f"{mod}.{attr}() in a determinism-critical "
                        "module (cache keys / fault-injection "
                        "decisions): wall clocks and unseeded "
                        "randomness break seeded chaos replay — hash "
                        "the (seed, site, index) tuple or use "
                        "time.monotonic/perf_counter for intervals",
                    )

        if name == "run_with_retry":
            for sub in ast.walk(node):
                if isinstance(sub, ast.keyword) and sub.arg in (
                    "donate", "donate_input", "donate_args"
                ):
                    v = sub.value
                    if not (
                        isinstance(v, ast.Constant)
                        and v.value in (False, None)
                    ):
                        self._emit(
                            "SRT005", node,
                            "run_with_retry wraps a donated call site "
                            f"({sub.arg}=...): donated segments consume "
                            "their input buffers, so a replay reads "
                            "deleted memory — retry must stay at-most-"
                            "once (gate on the consumed-input check "
                            "BEFORE the retry loop)",
                        )
                        break

        metric_arg = None
        if name in METRIC_FNS and node.args:
            metric_arg = node.args[0]
        elif name == "record" and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "flight" and len(node.args) >= 2:
            metric_arg = node.args[1]
        if (
            metric_arg is not None
            and isinstance(metric_arg, ast.Constant)
            and isinstance(metric_arg.value, str)
        ):
            mname = metric_arg.value
            if not METRIC_NAME_RE.match(mname):
                self._emit(
                    "SRT006", node,
                    f"metric/flight name {mname!r} is not "
                    "dotted-lowercase ([a-z0-9_] segments joined "
                    "by '.')",
                )
            elif mname.split(".", 1)[0] not in METRIC_NAMESPACES:
                self._emit(
                    "SRT006", node,
                    f"metric/flight name {mname!r} uses unregistered "
                    f"namespace {mname.split('.', 1)[0]!r} — register "
                    "it in tools/srt_check.py METRIC_NAMESPACES (one "
                    "reviewed line) or reuse an existing namespace",
                )

        if name in TRACE_SPAN_FNS and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                sname = a.value
                if not METRIC_NAME_RE.match(sname):
                    self._emit(
                        "SRT011", node,
                        f"span name {sname!r} is not dotted-lowercase "
                        "([a-z0-9_] segments joined by '.') — span "
                        "names land on the flight ring next to metric "
                        "names and follow the same grammar",
                    )
                elif sname.split(".", 1)[0] not in METRIC_NAMESPACES:
                    self._emit(
                        "SRT011", node,
                        f"span name {sname!r} uses unregistered "
                        f"namespace {sname.split('.', 1)[0]!r} — "
                        "register it in tools/srt_check.py "
                        "METRIC_NAMESPACES (one reviewed line) or "
                        "reuse an existing namespace",
                    )
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# SRT012: kernel-tier registry parity
# ---------------------------------------------------------------------------


def _str_set_literal(node: ast.AST) -> Optional[set]:
    """``{'a', 'b'}`` / ``frozenset({'a', 'b'})`` / list / tuple of str
    constants -> the set of strings; None when not a pure literal."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id == "frozenset" and len(node.args) == 1 \
            and not node.keywords:
        node = node.args[0]
    if isinstance(node, (ast.Set, ast.List, ast.Tuple)):
        out = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
            else:
                return None
        return out
    return None


def check_kernel_parity(relpath: str, tree: ast.Module,
                        pragmas: _Pragmas,
                        src_dir: str) -> List[Finding]:
    """Runs when the scanned module IS the kernel registry (it defines
    both ``KERNEL_NAMES`` and ``_REGISTRY``): the kernel-tier parity
    pass. The KERNEL_NAMES
    literal, the _REGISTRY dict keys, and the sibling plancheck.py's
    _KERNEL_RULES table must hold exactly the same names; every
    _REGISTRY entry must be a ``KernelSpec(...)`` whose name argument
    matches its key; and the ``kernel`` metric namespace must be
    registered so the tier's counters/spans pass SRT006."""
    names_assign: Optional[ast.Assign] = None
    declared: Optional[set] = None
    reg_assign: Optional[ast.Assign] = None
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            if node.targets[0].id == "KERNEL_NAMES":
                names_assign = node
                declared = _str_set_literal(node.value)
            elif node.targets[0].id == "_REGISTRY":
                reg_assign = node
    if names_assign is None or reg_assign is None:
        return []  # not the kernel-registry module
    findings: List[Finding] = []

    def emit(node, msg):
        line = getattr(node, "lineno", 1)
        if not pragmas.suppresses("SRT012", line):
            findings.append(Finding(
                "SRT012", relpath, line,
                getattr(node, "col_offset", 0), msg,
            ))

    if declared is None:
        emit(
            names_assign,
            "KERNEL_NAMES must be a pure string-literal frozenset — "
            "the kernel-parity pass reads it statically",
        )
        return findings
    if not isinstance(reg_assign.value, ast.Dict):
        emit(
            reg_assign,
            "_REGISTRY must be a literal dict keyed by kernel-name "
            "strings — the kernel-parity pass reads it statically",
        )
        return findings

    registered: set = set()
    for k, v in zip(reg_assign.value.keys, reg_assign.value.values):
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            emit(k or reg_assign,
                 "_REGISTRY keys must be kernel-name string literals")
            continue
        registered.add(k.value)
        # malformed-entry check: a KernelSpec(...) whose first/name
        # argument is the key itself
        spec_name = None
        if isinstance(v, ast.Call) and isinstance(v.func, ast.Name) \
                and v.func.id == "KernelSpec":
            if v.args and isinstance(v.args[0], ast.Constant):
                spec_name = v.args[0].value
            for kw in v.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    spec_name = kw.value.value
        else:
            emit(v, f"_REGISTRY[{k.value!r}] is not a KernelSpec(...) "
                    "literal")
            continue
        if spec_name != k.value:
            emit(v, f"_REGISTRY[{k.value!r}] names its KernelSpec "
                    f"{spec_name!r} — key and spec name must match")

    for kn in sorted(registered - declared):
        emit(names_assign,
             f"_REGISTRY entry {kn!r} missing from KERNEL_NAMES")
    for kn in sorted(declared - registered):
        emit(names_assign,
             f"KERNEL_NAMES entry {kn!r} has no _REGISTRY spec — "
             "orphan name?")

    # the metric namespace the tier's counters/spans live under
    if "kernel" not in METRIC_NAMESPACES:
        emit(
            names_assign,
            "the 'kernel' metric namespace is not registered in "
            "tools/srt_check.py METRIC_NAMESPACES — kernel.launches/"
            "declines/fallbacks would fail SRT006",
        )

    # the analyzer side: plancheck._KERNEL_RULES one directory up
    pc_path = os.path.join(os.path.dirname(src_dir), "plancheck.py")
    if not os.path.exists(pc_path):
        emit(
            names_assign,
            "no plancheck.py above the kernel registry — every kernel "
            "needs a static eligibility rule (_KERNEL_RULES)",
        )
        return findings
    try:
        with open(pc_path, "r", encoding="utf-8") as f:
            pc_tree = ast.parse(f.read(), filename=pc_path)
    except SyntaxError:
        return findings  # plancheck.py's own scan reports the error
    rules: Optional[set] = None
    rules_line = 1
    for node in pc_tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "_KERNEL_RULES" \
                and isinstance(node.value, ast.Dict):
            rules_line = node.lineno
            rules = set()
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(
                    k.value, str
                ):
                    rules.add(k.value)
    if rules is None:
        emit(
            names_assign,
            "plancheck.py has no literal _KERNEL_RULES table — the "
            "kernel-parity pass (and the static kernel tag) need one "
            "rule per registered kernel",
        )
        return findings
    for kn in sorted(declared - rules):
        emit(
            names_assign,
            f"kernel {kn!r} has no plancheck eligibility rule "
            f"(plancheck.py _KERNEL_RULES, line {rules_line}) — the "
            "static report would never tag its ops",
        )
    for kn in sorted(rules - declared):
        emit(
            names_assign,
            f"plancheck kernel rule {kn!r} has no registry spec — the "
            "analyzer would tag ops no kernel accelerates",
        )
    return findings


# ---------------------------------------------------------------------------
# SRT010: plan-stats store writes go through the CRC-framed helper
# ---------------------------------------------------------------------------

# the one sanctioned raw-append site (crc framing + self-heal live there)
STATS_APPEND_HELPER = "_open_append"
_STATS_PATH_HINTS = ("planstats", "stats_dir", "stats_path")


def _open_mode_literal(call: ast.Call) -> Optional[str]:
    """The string mode of an ``open()`` call, or None when dynamic."""
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant) \
            and isinstance(call.args[1].value, str):
        return call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def _mentions_stats_path(call: ast.Call) -> bool:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for node in ast.walk(arg):
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ) and "planstats" in node.value:
                return True
            if isinstance(node, ast.Name) and any(
                h in node.id for h in _STATS_PATH_HINTS
            ):
                return True
            if isinstance(node, ast.Attribute) and any(
                h in node.attr for h in _STATS_PATH_HINTS
            ):
                return True
    return False


def check_stats_append(relpath: str, tree: ast.Module,
                       pragmas: _Pragmas) -> List[Finding]:
    """Append-mode ``open()`` on the stats store outside the framed
    helper. Inside ``utils/planstats.py`` every append-mode open must
    live in ``_open_append``; elsewhere, an append-mode open whose
    arguments reference a stats path is a bypass of the framing."""
    in_planstats = relpath.replace(os.sep, "/").endswith(
        "spark_rapids_jni_tpu/utils/planstats.py"
    )
    findings: List[Finding] = []

    class _V(ast.NodeVisitor):
        def __init__(self):
            self.fn_stack: List[str] = []

        def visit_FunctionDef(self, node):
            self.fn_stack.append(node.name)
            self.generic_visit(node)
            self.fn_stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            if isinstance(node.func, ast.Name) and node.func.id == "open":
                mode = _open_mode_literal(node)
                if mode is not None and "a" in mode:
                    if in_planstats:
                        if STATS_APPEND_HELPER not in self.fn_stack:
                            self._emit(
                                node,
                                "append-mode open() in planstats "
                                "outside _open_append — every store "
                                "write must go through the CRC-framed "
                                "helper (torn-tail self-heal, "
                                "rotation, flush discipline)",
                            )
                    elif _mentions_stats_path(node):
                        self._emit(
                            node,
                            "raw append-mode open() on a plan-stats "
                            "path — append via planstats' framed "
                            "writer instead; unframed bytes corrupt "
                            "the store for every later reader",
                        )
            self.generic_visit(node)

        def _emit(self, node, msg):
            if not pragmas.suppresses("SRT010", node.lineno):
                findings.append(Finding(
                    "SRT010", relpath, node.lineno,
                    node.col_offset, msg,
                ))

    _V().visit(tree)
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def scan_file(path: str, repo_root: str = REPO_ROOT) -> List[Finding]:
    relpath = os.path.relpath(os.path.abspath(path), repo_root)
    with open(path, "r", encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(
            "SRT000", relpath, e.lineno or 1, e.offset or 0,
            f"syntax error: {e.msg}",
        )]
    lines = source.splitlines()
    pragmas = _Pragmas(source, relpath)
    checker = _FileChecker(relpath, source, pragmas)
    checker.visit(tree)
    findings = checker.findings
    findings.extend(check_stats_append(relpath, tree, pragmas))
    findings.extend(check_kernel_parity(
        relpath, tree, pragmas,
        os.path.dirname(os.path.abspath(path)),
    ))
    findings.extend(pragmas.bad)
    # fingerprints: (pass, path, scope-less normalized line, occurrence)
    seen: Dict[str, int] = {}
    for fd in findings:
        text = lines[fd.line - 1].strip() if fd.line - 1 < len(lines) else ""
        base = f"{fd.pass_id}|{fd.path}|{text}"
        n = seen.get(base, 0)
        seen[base] = n + 1
        fd.fingerprint = hashlib.sha1(
            f"{base}|{n}".encode()
        ).hexdigest()[:16]
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.pass_id))
    return findings


def iter_sources(roots: Sequence[str], repo_root: str = REPO_ROOT):
    for root in roots:
        full = os.path.join(repo_root, root)
        if os.path.isfile(full):
            yield full
        else:
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [
                    d for d in dirnames
                    if d not in ("__pycache__", ".git")
                ]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def scan_repo(roots: Sequence[str] = DEFAULT_ROOTS,
              repo_root: str = REPO_ROOT) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_sources(roots, repo_root):
        findings.extend(scan_file(path, repo_root))
    return findings


def load_baseline(path: str) -> Dict[str, dict]:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "fingerprints" not in doc:
        raise ValueError(
            f"baseline {path!r} is not a srt-check baseline "
            "(missing 'fingerprints')"
        )
    return dict(doc["fingerprints"])


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    doc = {
        "version": 1,
        "tool": "srt-check",
        "note": (
            "grandfathered findings: new violations fail CI while "
            "these burn down. Regenerate with --write-baseline; an "
            "EMPTY table is the goal state."
        ),
        "fingerprints": {
            f.fingerprint: {
                "pass": f.pass_id,
                "path": f.path,
                "line": f.line,
                "message": f.message,
            }
            for f in findings
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def prune_baseline(path: str, live_fps) -> int:
    """Drop baseline fingerprints that no longer match any finding;
    returns how many were removed. The doc is rewritten in place with
    everything else (version, note) preserved."""
    if not os.path.exists(path):
        return 0
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    fps = doc.get("fingerprints", {})
    stale = [fp for fp in fps if fp not in live_fps]
    if not stale:
        return 0
    for fp in stale:
        del fps[fp]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return len(stale)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="srt-check", description=__doc__.splitlines()[0]
    )
    ap.add_argument("paths", nargs="*", help="files/dirs to scan "
                    "(default: the repo's standard roots)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: %(default)s)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: every finding fails")
    ap.add_argument("--write-baseline", action="store_true",
                    help="re-grandfather every current finding and exit")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="drop stale fingerprints from the baseline in "
                    "place (keeps grandfathered entries that still "
                    "match) and continue the normal gate")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root for relative paths")
    args = ap.parse_args(argv)

    if args.paths:
        findings: List[Finding] = []
        for p in args.paths:
            full = p if os.path.isabs(p) else os.path.join(args.root, p)
            findings.extend(scan_repo([os.path.relpath(full, args.root)],
                                      args.root)
                            if os.path.isdir(full)
                            else scan_file(full, args.root))
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.pass_id))
    else:
        findings = scan_repo(repo_root=args.root)

    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(
            f"srt-check: baseline written to {args.baseline} "
            f"({len(findings)} findings grandfathered)"
        )
        return 0

    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    new = 0
    for f in findings:
        if f.fingerprint in baseline:
            f.baselined = True
        else:
            new += 1
    live_fps = {f.fingerprint for f in findings}
    stale = [fp for fp in baseline if fp not in live_fps]
    if args.prune_baseline and stale:
        removed = prune_baseline(args.baseline, live_fps)
        print(
            f"srt-check: pruned {removed} stale baseline entr(y/ies) "
            f"from {args.baseline}"
        )
        stale = []

    files_scanned = len({f.path for f in findings}) if findings else 0
    summary = (
        f"srt-check: {len(findings)} finding(s) ({new} new, "
        f"{len(findings) - new} baselined, {len(stale)} stale baseline "
        "entr(y/ies))"
    )
    if args.json:
        print(json.dumps({
            "version": 1,
            "findings": [f.to_doc() for f in findings],
            "counts": {
                "total": len(findings),
                "new": new,
                "baselined": len(findings) - new,
                "stale_baseline": len(stale),
                "files_with_findings": files_scanned,
            },
            "stale_baseline": stale,
            "summary": summary,
        }, indent=1, sort_keys=True))
    else:
        for f in findings:
            print(f.render())
        if stale:
            print(
                f"srt-check: {len(stale)} baseline entr(y/ies) no "
                "longer match (fixed or moved) — prune with "
                "--prune-baseline"
            )
        print(summary)
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
