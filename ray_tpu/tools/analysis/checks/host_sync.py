"""host-sync-in-hot-path: device->host round trips inside registered hot paths.

The single biggest perf bug in this repo's history — a host round trip per
decode step that capped engine decode until PR 12 — was a host sync
on the scheduler hot path that no review caught. Hot functions are now
registered explicitly with `@hot_path` (ray_tpu/util/hot_path.py, a runtime
no-op), and this check walks them PLUS their one-level same-file callees for
constructs that force the host to wait on the device:

- ``.item()`` / ``.tolist()`` on anything;
- ``block_until_ready`` (call or attribute);
- ``np.asarray(...)`` / ``numpy.asarray(...)`` / ``jax.device_get(...)``;
- ``float(x)`` / ``int(x)`` / ``bool(x)`` where ``x`` is a bare
  name/attribute/subscript (scalarizing an array implicitly calls
  ``__float__``/``__index__`` — a blocking transfer when x lives on device).

The designed sync points (the engine's one fetch per K-step burst) carry an
inline allow with the reason spelling out why the sync is intentional.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from ..base import Check, Project, SourceFile, Violation, call_name, decorator_names

SYNC_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get"}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}
SCALARIZERS = {"float", "int", "bool"}


def _hot_roots(tree: ast.AST) -> List[ast.AST]:
    """Functions decorated @hot_path (bare or called form)."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in decorator_names(node):
                if dec == "hot_path" or dec.endswith(".hot_path"):
                    out.append(node)
    return out


def _local_defs(tree: ast.AST) -> Dict[str, ast.AST]:
    """name -> def for module-level functions and every method (methods keyed
    as 'ClassName.method' AND bare 'method' for self-call resolution)."""
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defs[f"{node.name}.{item.name}"] = item
                    defs.setdefault(item.name, item)
    for node in ast.iter_child_nodes(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node
    return defs


def _callees(fn: ast.AST) -> Set[str]:
    """Names this function calls that can resolve in-file: `self.m()` -> 'm',
    bare `helper()` -> 'helper'."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Name):
            out.add(func.id)
        elif (isinstance(func, ast.Attribute)
              and isinstance(func.value, ast.Name)
              and func.value.id in ("self", "cls")):
            out.add(func.attr)
    return out


def _sync_sites(fn: ast.AST) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = call_name(node.func)
            if name in SYNC_CALLS:
                yield node.lineno, f"{name}() copies device memory to host"
                continue
            last = name.rsplit(".", 1)[-1]
            if last in SYNC_METHODS and "." in name:
                yield node.lineno, (f".{last}() blocks on the device "
                                    "round trip")
                continue
            if (name in SCALARIZERS and len(node.args) == 1
                    and not node.keywords
                    and isinstance(node.args[0],
                                   (ast.Name, ast.Attribute, ast.Subscript))):
                yield node.lineno, (f"{name}() on a name scalarizes (implicit "
                                    "__float__/__index__ host sync if the "
                                    "value is a device array)")


class HostSyncInHotPath(Check):
    name = "host-sync-in-hot-path"

    def run(self, f: SourceFile, project: Project) -> Iterable[Violation]:
        roots = _hot_roots(f.tree)
        if not roots:
            return
        defs = _local_defs(f.tree)
        seen: Set[int] = set()
        for root in roots:
            targets = [(root, root.name)]
            for callee in sorted(_callees(root)):
                fn = defs.get(callee)
                if fn is not None and fn not in roots:
                    targets.append((fn, f"{root.name} -> {callee}"))
            for fn, label in targets:
                if id(fn) in seen:
                    continue
                seen.add(id(fn))
                for line, why in _sync_sites(fn):
                    yield Violation(
                        self.name, f.path, line,
                        f"host sync on hot path {label}: {why}")
