#!/usr/bin/env python3
"""Run the plan-time analyzer over every plan LITERAL in the repo's
drivers — the CI gate that keeps the smoke scripts inside
the dispatch plane's statically-supported surface.

Scans the given files for plan literals — a list literal whose elements
are all dicts with an ``"op"`` key, or a lone op dict (treated as a
1-op plan) — resolves the small constant vocabulary those literals use
(``int(dt.TypeId.X)``, ``dt.TypeId.X``, and module-level names assigned
from either), and runs ``plancheck.analyze`` structurally (no input
schema: the drivers feed many shapes). Any plan that fails the
structural walk — unknown op, malformed spec, bad join how — fails the
gate with the op index and reason.

Shell scripts are scanned too: python heredocs (``<<'PY'`` ... ``PY``)
are extracted and parsed as modules, which is how the smoke scripts
embed their plans.

Usage::

    python tools/plancheck_literals.py chip_smoke.py ci/smoke-chaos.sh ...
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HEREDOC_RE = re.compile(
    r"<<\s*['\"]?(PY|PYTHON|EOF_PY)['\"]?\n(.*?)\n\1\s*$",
    re.DOTALL | re.MULTILINE,
)


class _Unresolved(Exception):
    pass


def _typeid_value(node: ast.AST) -> Optional[int]:
    """``dt.TypeId.X`` / ``TypeId.X`` -> the numeric id, else None."""
    from spark_rapids_jni_tpu import dtype as dt

    if isinstance(node, ast.Attribute):
        v = node.value
        is_typeid = (
            isinstance(v, ast.Attribute) and v.attr == "TypeId"
        ) or (isinstance(v, ast.Name) and v.id == "TypeId")
        if is_typeid and node.attr in dt.TypeId.__members__:
            return int(dt.TypeId[node.attr])
    return None


def _resolve(node: ast.AST, env: Dict[str, object]):
    """Literal evaluator for the plan-constant vocabulary."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Dict):
        out = {}
        for k, v in zip(node.keys, node.values):
            if k is None:
                raise _Unresolved("dict splat")
            out[_resolve(k, env)] = _resolve(v, env)
        return out
    if isinstance(node, (ast.List, ast.Tuple)):
        return [_resolve(e, env) for e in node.elts]
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _Unresolved(f"name {node.id!r}")
    tid = _typeid_value(node)
    if tid is not None:
        return tid
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id == "int" and len(node.args) == 1:
        return int(_resolve(node.args[0], env))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_resolve(node.operand, env)
    raise _Unresolved(ast.dump(node)[:60])


def _is_op_dict(node: ast.AST) -> bool:
    return isinstance(node, ast.Dict) and any(
        isinstance(k, ast.Constant) and k.value == "op"
        for k in node.keys
    )


def _collect_plans(tree: ast.Module) -> List[Tuple[int, list]]:
    """(line, plan) for every plan literal in the module. A constant
    environment of module/function-level ``NAME = <resolvable>``
    assignments feeds the evaluator."""
    env: Dict[str, object] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            try:
                env[node.targets[0].id] = _resolve(node.value, env)
            except _Unresolved:
                pass

    plans: List[Tuple[int, list]] = []
    in_list: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.List) and node.elts and all(
            _is_op_dict(e) for e in node.elts
        ):
            try:
                plans.append((node.lineno, _resolve(node, env)))
            except _Unresolved as e:
                print(
                    f"  note: line {node.lineno}: plan literal uses "
                    f"unresolvable value ({e}) — skipped"
                )
            in_list.update(id(e) for e in node.elts)
    for node in ast.walk(tree):
        if _is_op_dict(node) and id(node) not in in_list:
            try:
                plans.append((node.lineno, [_resolve(node, env)]))
            except _Unresolved as e:
                print(
                    f"  note: line {node.lineno}: op literal uses "
                    f"unresolvable value ({e}) — skipped"
                )
    plans.sort(key=lambda p: p[0])
    return plans


def _modules_in(path: str) -> List[Tuple[str, ast.Module]]:
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    if path.endswith(".py"):
        return [(path, ast.parse(text, filename=path))]
    out = []
    for m in _HEREDOC_RE.finditer(text):
        body = m.group(2)
        line0 = text[: m.start(2)].count("\n")
        try:
            tree = ast.parse(body)
        except SyntaxError:
            continue  # not a python heredoc after all
        ast.increment_lineno(tree, line0)
        out.append((path, tree))
    return out


def main(argv=None) -> int:
    from spark_rapids_jni_tpu import plancheck

    paths = (argv if argv is not None else sys.argv[1:]) or ["chip_smoke.py"]
    total = 0
    bad = 0
    for path in paths:
        for src, tree in _modules_in(path):
            for line, plan in _collect_plans(tree):
                total += 1
                # generic unknown-schema extra tables: the drivers feed
                # multi-table ops (join/concat) their build sides at
                # runtime, which a structural walk cannot see — without
                # these, every join-bearing driver plan would be
                # rejected for missing inputs it does in fact have
                report = plancheck.analyze(
                    plan, rest=[(None, None)] * 8
                )
                if report["ok"]:
                    continue
                bad += 1
                first = next(
                    e for e in report["ops"]
                    if e["tier"] == "unsupported"
                )
                print(
                    f"{src}:{line}: plan literal REJECTED — "
                    f"op[{first['index']}] {first['op']!r}: "
                    f"{first['reason']}"
                )
    label = "clean" if not bad else f"{bad} REJECTED"
    print(
        f"plancheck-literals: {total} plan literal(s) across "
        f"{len(paths)} file(s): {label}"
    )
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
