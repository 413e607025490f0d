"""layering: the import DAG, the Executor contract, and state boundaries.

Decentralized serving lives or dies by enforceable node-side contracts
(DESIGN.md §7): the Executor layer is the only sanctioned backend
extension point, and the packages below it must stay importable without
dragging the serving stack in.  Four sub-rules:

* ``layering/import-dag`` — each ``repro.*`` subpackage declares the
  subpackages it may import (``ALLOWED_IMPORTS``); any other ``repro``
  import is a violation, and a *new* subpackage must add itself to the
  table (unknown packages are flagged, so layering stays a conscious
  decision).  In particular: ``core`` must not import ``serving`` or
  ``models``; ``sim`` must not import ``serving`` (the sim twins are the
  spec the engines are tested against, so the dependency points at them).
* ``layering/executor-contract`` — every ``Executor`` subclass under
  ``src/`` implements the full contract surface (DESIGN.md §6.1):
  ``admit``, ``load``, ``estimate``, ``n_active`` — defined locally or
  inherited from another repo class (the abstract root itself does not
  count as an implementation).
* ``layering/service-time`` — only the executor layer may call the
  analytic ``BackendProfile.service_time`` (frozen-share scheduling must
  not creep back; DESIGN.md §6.1).
* ``layering/private-state`` — the paged engine's page-pool bookkeeping
  (``_free_pages``, ``_block_tables``, ...) is private to
  ``repro.serving.engine``; everything else reads
  ``Engine.load_snapshot()`` / ``Executor.load()``.
* ``layering/digest-construction`` — gossip ``LoadDigest`` payloads
  (DESIGN.md §6.2-gossip) are constructed only in the executor layer
  (``repro.sim.executor``); everything else — gossip, routing, benches,
  tests — obtains them via ``Executor.digest()`` / ``make_load_digest``,
  so a digest always reflects a real ``ExecutorLoad`` projection rather
  than hand-rolled fields drifting from the load snapshot.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set, Tuple

from repro.analysis.astutil import imported_modules
from repro.analysis.framework import Checker, Finding, RepoIndex, register

# subpackage -> repro subpackages it may import (itself always allowed).
# Order is the layering: compat/data at the bottom, launch on top.
ALLOWED_IMPORTS: Dict[str, Tuple[str, ...]] = {
    "analysis": (),                       # stdlib-only analyzer
    "compat": (),
    "data": (),
    "obs": (),                            # stdlib-only trace/metrics sink
    "sim": ("compat", "obs"),
    "core": ("compat", "obs", "sim"),
    # the paged decode step dispatches its attention through kernels.ops
    # (imported where it is called: kernels import models' attention
    # helpers, whose package import would otherwise come round again)
    "models": ("compat", "kernels"),
    "kernels": ("compat", "models"),      # ref oracles live in models
    "configs": ("compat", "models"),
    "training": ("compat", "models", "data"),
    "serving": ("compat", "obs", "sim", "models", "kernels"),
    "launch": ("compat", "obs", "sim", "core", "models", "kernels",
               "serving", "configs", "training", "data"),
}

# the Executor contract surface (DESIGN.md §6.1); bind() has a concrete
# default on the ABC so it is not part of the required surface
EXECUTOR_ROOT = "Executor"
EXECUTOR_REQUIRED = ("admit", "load", "estimate", "n_active")

# BackendProfile.service_time callers (frozen-share guard)
SERVICE_TIME_ALLOWED = ("src/repro/sim/executor.py",
                        "src/repro/sim/servicemodel.py",
                        "tests/test_executor.py")

# paged-engine page-pool privates and their one sanctioned home
PRIVATE_STATE = frozenset({"_free_pages", "_row_pages", "_block_tables",
                           "_num_pages", "_pools", "_slot_seq",
                           # prefix-cache internals (DESIGN.md §6.1-prefix):
                           # chain/refcount/cold-LRU/pin state is engine-
                           # private; other layers read load_snapshot()'s
                           # cached_pages / prefix_hit_rate /
                           # resident_prefixes or call prefix_pin()
                           "_chain", "_page_hash", "_page_ref", "_cold",
                           "_head_lru", "_pinned"})
PRIVATE_STATE_HOME = "src/repro/serving/engine.py"

# gossip LoadDigest construction and its one sanctioned home (DESIGN.md
# §6.2-gossip); everyone else calls Executor.digest() / make_load_digest
DIGEST_CTOR = "LoadDigest"
DIGEST_HOME = "src/repro/sim/executor.py"


def _subpackage(module: str) -> str:
    """'repro.sim.executor' -> 'sim'; bare 'repro' -> ''. """
    parts = module.split(".")
    return parts[1] if len(parts) > 1 and parts[0] == "repro" else ""


@register
class LayeringChecker(Checker):
    rule_id = "layering"
    description = ("import-DAG contract, Executor contract surface, "
                   "service_time and page-pool state boundaries")

    def run(self, repo: RepoIndex) -> Iterable[Finding]:
        yield from self._import_dag(repo)
        yield from self._executor_contract(repo)
        yield from self._restricted_access(repo)

    # ---------------------------------------------------------- import DAG
    def _import_dag(self, repo: RepoIndex) -> Iterable[Finding]:
        for rel in repo.py_files():
            if not rel.startswith("src/repro/"):
                continue          # tests/benchmarks may import any layer
            mod = repo.module_name(rel) or ""
            sub = _subpackage(mod)
            if not sub:
                continue
            tree = repo.tree(rel)
            if tree is None:
                continue
            if sub not in ALLOWED_IMPORTS:
                yield Finding(
                    "layering/import-dag", rel, 1,
                    f"subpackage 'repro.{sub}' has no layering entry; add "
                    f"it to repro.analysis.layering.ALLOWED_IMPORTS to "
                    f"declare its place in the import DAG")
                continue
            allowed = set(ALLOWED_IMPORTS[sub]) | {sub}
            seen: Set[Tuple[str, int]] = set()
            for imported, line in imported_modules(tree):
                tgt = _subpackage(imported)
                if not imported.startswith("repro") or not tgt:
                    continue
                if tgt not in allowed and (tgt, line) not in seen:
                    seen.add((tgt, line))
                    yield Finding(
                        "layering/import-dag", rel, line,
                        f"'repro.{sub}' must not import 'repro.{tgt}' "
                        f"(allowed: "
                        f"{', '.join(sorted(allowed - {sub})) or 'none'})")

    # -------------------------------------------------- Executor contract
    def _executor_contract(self, repo: RepoIndex) -> Iterable[Finding]:
        # class name -> (rel, lineno, base names, method names); names are
        # unique in this codebase, later definitions win deterministically
        index: Dict[str, Tuple[str, int, List[str], Set[str]]] = {}
        for rel in repo.py_files():
            if not rel.startswith("src/"):
                continue          # test fakes may be deliberately partial
            tree = repo.tree(rel)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                bases = []
                for b in node.bases:
                    if isinstance(b, ast.Name):
                        bases.append(b.id)
                    elif isinstance(b, ast.Attribute):
                        bases.append(b.attr)
                methods = {m.name for m in node.body
                           if isinstance(m, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))}
                index[node.name] = (rel, node.lineno, bases, methods)

        def is_executor(name: str, seen: Set[str]) -> bool:
            if name == EXECUTOR_ROOT:
                return True
            if name in seen or name not in index:
                return False
            seen.add(name)
            return any(is_executor(b, seen) for b in index[name][2])

        def inherited(name: str, seen: Set[str]) -> Set[str]:
            """Methods implemented by ``name`` or its repo ancestors,
            excluding the abstract root."""
            if name == EXECUTOR_ROOT or name in seen or name not in index:
                return set()
            seen.add(name)
            out = set(index[name][3])
            for b in index[name][2]:
                out |= inherited(b, seen)
            return out

        for name, (rel, line, bases, _methods) in sorted(index.items()):
            if name == EXECUTOR_ROOT or not is_executor(name, set()):
                continue
            have = inherited(name, set())
            missing = [m for m in EXECUTOR_REQUIRED if m not in have]
            if missing:
                yield Finding(
                    "layering/executor-contract", rel, line,
                    f"Executor subclass '{name}' is missing the contract "
                    f"surface: {', '.join(missing)} (DESIGN.md §6.1)")

    # ------------------------------------------------- restricted access
    def _restricted_access(self, repo: RepoIndex) -> Iterable[Finding]:
        for rel in repo.py_files():
            tree = repo.tree(rel)
            if tree is None:
                continue
            check_service = rel not in SERVICE_TIME_ALLOWED
            check_private = rel != PRIVATE_STATE_HOME
            check_digest = rel != DIGEST_HOME
            if not (check_service or check_private or check_digest):
                continue
            for node in ast.walk(tree):
                if check_service and isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "service_time":
                    yield Finding(
                        "layering/service-time", rel, node.lineno,
                        "direct BackendProfile.service_time call outside "
                        "the executor layer (route through Executor."
                        "admit/load/estimate; DESIGN.md §6.1)")
                elif check_private and isinstance(node, ast.Attribute) \
                        and node.attr in PRIVATE_STATE:
                    yield Finding(
                        "layering/private-state", rel, node.lineno,
                        f"page-pool private '{node.attr}' accessed outside "
                        f"the paged engine (read Engine.load_snapshot() / "
                        f"Executor.load() instead)")
                elif check_digest and isinstance(node, ast.Call) \
                        and ((isinstance(node.func, ast.Name)
                              and node.func.id == DIGEST_CTOR)
                             or (isinstance(node.func, ast.Attribute)
                                 and node.func.attr == DIGEST_CTOR)):
                    yield Finding(
                        "layering/digest-construction", rel, node.lineno,
                        "LoadDigest constructed outside the executor layer "
                        "(build digests via Executor.digest() / "
                        "make_load_digest; DESIGN.md §6.2-gossip)")
