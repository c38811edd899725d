"""batcher-bypass: direct mesh reducer dispatch outside parallel/.

Device dispatch must flow through the dispatch batcher
(docs/batching.md): a direct call of the per-stage launcher bypasses
cross-query fusion, the queued-deadline drop-out, and the dispatch
stats.  Only ``parallel/`` touches the executables; everything else
goes through ``executor.batcher.reduce`` (or its explicit disabled-mode
fallback).

Replaces the check.sh grep with a receiver-aware pass: besides literal
``mesh.reduce_async(...)`` shapes it tracks simple local aliases
(``m = self.executor.mesh; m.reduce_async(...)`` and
``m = MeshExecutor(...)``), which the grep could never see.
"""

from __future__ import annotations

import ast

from ..astlint import rule

# MeshExecutor's one per-stage launcher (parallel/mesh_exec.py)
REDUCERS = {"reduce_async"}


def _chain_names(node) -> list[str]:
    """Attribute chain as name parts: self.executor.mesh -> [self,
    executor, mesh]."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    parts.reverse()
    return parts


def _is_mesh_expr(node, aliases: set[str]) -> bool:
    if isinstance(node, ast.Call):  # m = MeshExecutor(...)
        inner = _chain_names(node.func)
        return bool(inner) and inner[-1] == "MeshExecutor"
    parts = _chain_names(node)
    if not parts:
        return False
    if parts[0] in aliases:
        return True
    return any("mesh" in p for p in parts)


@rule("batcher-bypass", scope="src")
def check(mod):
    """Mesh reducer call outside parallel/ (route through the batcher)."""
    rel = mod.rel.replace("\\", "/")
    if rel.startswith(("pilosa_tpu/parallel/", "pilosa_tpu/analysis/")):
        return
    # one linear pass per function body keeps alias tracking simple:
    # a Name assigned from a mesh-looking expression taints that name
    # for the rest of the module (over-approximate, which is the safe
    # direction for a bypass check)
    aliases: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_mesh_expr(node.value, aliases):
            aliases.add(node.targets[0].id)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr not in REDUCERS:
            continue
        if _is_mesh_expr(node.func.value, aliases):
            yield node.lineno, (
                f"direct mesh dispatch '{node.func.attr}' outside "
                f"parallel/ — route through executor.batcher "
                f"(parallel/batcher.py) so fusion, deadline drop-out, "
                f"and dispatch stats apply")
