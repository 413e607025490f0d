"""compat-boundary: gated JAX symbols live only in repro.compat.

The mesh-context and Pallas compiler-params symbols of the installed JAX
(ROADMAP, "Supported environment") are the API surface most likely to move
between releases, so they may only be touched from ``src/repro/compat/``
(``meshenv``, ``pallascompat``).  The original guard was a token grep;
this checker is import/attribute-aware, so it

* catches ``from jax.sharding import get_abstract_mesh``,
  ``jax.sharding.set_mesh``, aliased module imports, bare uses of a gated
  name, and the ``axis_types=`` keyword — wherever they appear in real
  code;
* does NOT fire on docstrings or comments that merely *mention* a gated
  symbol (the grep's false-positive class, which forced whole-file
  allowlists).
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from repro.analysis.framework import Checker, Finding, RepoIndex, register

# mesh-context and Pallas compiler-params symbols of the installed JAX
GATED_SYMBOLS = frozenset({
    "get_abstract_mesh", "AxisType", "set_mesh", "CompilerParams",
})
# call keywords with the same version-gating problem
GATED_KWARGS = frozenset({"axis_types"})

# the compat package IS the sanctioned home, and its tests exercise it
ALLOWED_PREFIXES = ("src/repro/compat/",)
ALLOWED_FILES = ("tests/test_compat.py",)

_HINT = "route through repro.compat (meshenv / pallascompat) instead"


def _allowed(rel: str) -> bool:
    return rel in ALLOWED_FILES or any(rel.startswith(p)
                                       for p in ALLOWED_PREFIXES)


@register
class CompatBoundaryChecker(Checker):
    rule_id = "compat-boundary"
    description = ("version-gated jax.sharding/Pallas symbols are "
                   "resolvable only from repro.compat")

    def run(self, repo: RepoIndex) -> Iterable[Finding]:
        for rel in repo.py_files():
            if _allowed(rel):
                continue
            tree = repo.tree(rel)
            if tree is None:
                continue
            yield from self._check_module(rel, tree)

    def _check_module(self, rel: str, tree: ast.Module) -> List[Finding]:
        out: List[Finding] = []

        def hit(node: ast.AST, name: str, how: str) -> None:
            out.append(Finding(
                self.rule_id, rel, node.lineno,
                f"version-gated symbol '{name}' {how}; {_HINT}"))

        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.split(".")[0] == "jax":
                for alias in node.names:
                    if alias.name in GATED_SYMBOLS:
                        hit(node, alias.name,
                            f"imported from {node.module}")
                    elif alias.name == "*":
                        hit(node, "*",
                            f"star-imported from {node.module} "
                            f"(unanalyzable; gated symbols may leak)")
            elif isinstance(node, ast.Attribute) \
                    and node.attr in GATED_SYMBOLS:
                hit(node, node.attr, "accessed as an attribute")
            elif isinstance(node, ast.Name) and node.id in GATED_SYMBOLS \
                    and isinstance(node.ctx, ast.Load):
                hit(node, node.id, "referenced by name")
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in GATED_KWARGS:
                        hit(node, f"{kw.arg}=", "passed as a call keyword")
        return out
