"""PALLAS01 — lazy-Pallas discipline.

PR 5's measurement-honesty invariant, made structural: on a CPU host,
``--flash auto`` must resolve to XLA *without Pallas ever entering
``sys.modules``* (``__graft_entry__`` dryrun mode 10 proves it at runtime
by inspecting ``sys.modules``). That only holds if no
module outside ``tpudist/ops/pallas/`` imports Pallas — or anything from
the ``tpudist.ops.pallas`` package — at module level. Kernel access from
dispatch clients, models, and benches is function-local by convention
(``from tpudist.ops.pallas import …`` inside the branch that already
decided to use it); this rule turns the convention into a gate.

``if TYPE_CHECKING:`` imports are exempt (never executed); files under
``tpudist/ops/pallas/`` are the kernel package itself and exempt.
"""

from __future__ import annotations

import ast

from tpudist.analysis import astutil
from tpudist.analysis.core import Module, finding

_EXEMPT_PREFIX = "tpudist/ops/pallas/"


def _resolve_from(node: ast.ImportFrom, relpath: str) -> str:
    """Absolute dotted module path of an ImportFrom, resolving relative
    levels against the importing file's own package — ``from .pallas
    import x`` in tpudist/ops/ must read as tpudist.ops.pallas, or the
    natural relative refactor of a dispatch client evades the gate."""
    if not node.level:
        return node.module or ""
    pkg = relpath.split("/")[:-1]                 # the file's package dirs
    base = pkg[:len(pkg) - (node.level - 1)] if node.level > 1 else pkg
    return ".".join(base + ([node.module] if node.module else []))


def _pallas_target(node: ast.stmt, relpath: str) -> str | None:
    """The offending import path when this statement imports Pallas."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            if alias.name.startswith("jax.experimental.pallas") \
                    or alias.name.startswith("tpudist.ops.pallas"):
                return alias.name
    elif isinstance(node, ast.ImportFrom):
        m = _resolve_from(node, relpath)
        if m.startswith("jax.experimental.pallas") \
                or m.startswith("tpudist.ops.pallas"):
            return m
        if m in ("jax.experimental", "tpudist.ops"):
            for alias in node.names:
                if alias.name == "pallas":
                    return f"{m}.pallas"
    return None


def check(ctx: dict, mod: Module) -> list:
    if mod.relpath.startswith(_EXEMPT_PREFIX):
        return []
    out = []
    parents = astutil.parent_map(mod.tree)
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        target = _pallas_target(node, mod.relpath)
        if target is None:
            continue
        if not astutil.at_module_level(node, parents):
            continue                      # lazy function-local import: fine
        if astutil.under_type_checking(node, parents):
            continue
        out.append(finding(
            mod, "PALLAS01", node.lineno, node.col_offset,
            f"module-level import of '{target}' outside tpudist/ops/pallas/ "
            f"— breaks the 'CPU auto never imports Pallas' honesty "
            f"invariant (dryrun mode 10); move the import inside the "
            f"function that already decided to use the kernel"))
    return out
