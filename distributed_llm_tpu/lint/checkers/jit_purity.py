"""JIT-purity lint: host-side impurity inside traced computations.

A function is a JIT ROOT when it is decorated with ``jax.jit`` /
``pjit`` / ``shard_map`` (directly or through ``partial``), or passed
to one of those as the function argument (``jax.jit(run)``,
``shard_map(step, mesh=...)``, ``jax.jit(partial(init, cfg))``,
``jax.jit(lambda: ...)``).  ``pl.pallas_call`` counts as a wrapper too:
a Pallas KERNEL body is traced exactly like a jitted function (and a
blocking host call inside one wedges the whole device program), so the
kernels in ops/pallas_attention.py and ops/rows_attention.py are
roots — including the repo idiom ``kernel = partial(_kernel, ...)``
followed by ``pl.pallas_call(kernel, ...)``, resolved through the
module-local assignment.  The checker walks roots plus every
module-local function they transitively call (cross-module callees are
out of static reach and skipped — keep traced helpers in the module
that jits them, or lint them where they live).

Rules:

- ``jit-host-impurity``: ``time.*``, ``print``, Python/NumPy RNG
  (``random.*`` / ``np.random.*`` — host randomness baked in at trace
  time; use ``jax.random`` with explicit keys), ``open(...)`` and
  ``.block_until_ready()`` (a host sync point has no meaning inside a
  traced function) anywhere in a jit-reachable body.  ``jax.debug.*``
  and ``jax.random.*`` are exempt by construction (matched by module
  root).
- ``jit-traced-concretization``: on the root function itself,
  ``bool()`` / ``int()`` / ``float()`` / ``len()`` over an expression
  mentioning a traced parameter, or ``.item()`` / ``.tolist()`` on one
  — Python branching/iteration on traced values, the
  compile-time-explosion / ConcretizationError class (HybridGen's
  mixed host/accelerator pitfall: the bug hides until compile).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..core import Checker, Finding, Project
from ..symbols import (JIT_WRAPPERS, attr_chain, call_name, jit_roots_for,
                       symbols_for, unwrap_partial as _unwrap_partial,
                       wrapper_leaf as _wrapper_leaf)

CONCRETIZERS = {"bool", "int", "float", "len"}
CONCRETIZE_METHODS = {"item", "tolist"}


class _ImportMap(ast.NodeVisitor):
    """name -> source module for top-level imports, to tell stdlib
    ``random`` apart from ``jax.random`` and ``np`` from anything
    else."""

    def __init__(self, tree: ast.Module):
        self.modules: Dict[str, str] = {}
        self.visit(tree)

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self.modules[alias.asname or alias.name.split(".")[0]] = \
                alias.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        for alias in node.names:
            base = node.module or ""
            self.modules[alias.asname or alias.name] = \
                f"{base}.{alias.name}".lstrip(".")


class JitPurityChecker(Checker):
    name = "jit_purity"
    rules = ("jit-host-impurity", "jit-traced-concretization")
    scope = ("distributed_llm_tpu",)

    def check(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for mod in project.in_dirs(self.scope):
            syms = symbols_for(mod)
            if syms is None:
                continue
            findings.extend(self._check_module(mod, syms))
        return findings

    def _check_module(self, mod, syms) -> List[Finding]:
        imports = _ImportMap(mod.tree)
        # Root discovery (decorator forms, call-site forms including the
        # ``kernel = partial(_f, ...)`` then ``pl.pallas_call(kernel,
        # ...)`` idiom, scoped variable resolution) lives in
        # symbols.jit_roots_for — one cached pass shared with the
        # retrace checker's traced-reachability analysis.
        roots, lambda_roots = jit_roots_for(mod, syms)

        if not roots and not lambda_roots:
            return []

        reachable = syms.local_closure(roots)
        findings: List[Finding] = []
        for qual in sorted(reachable):
            info = syms.functions[qual]
            findings.extend(self._scan_body(
                mod, imports, info.node, is_root=(qual in roots)))
        for lam in lambda_roots:
            # A lambda passed to jit IS a root: its params are traced,
            # so the concretization rules apply to it too.
            findings.extend(self._scan_body(mod, imports, lam,
                                            is_root=True))
        return findings

    # -- body scanning -----------------------------------------------------

    def _scan_body(self, mod, imports: _ImportMap, func_node,
                   is_root: bool) -> List[Finding]:
        findings: List[Finding] = []
        params: Set[str] = set()
        if is_root and hasattr(func_node, "args"):
            a = func_node.args
            params = {p.arg for p in
                      list(a.posonlyargs) + list(a.args)
                      + list(a.kwonlyargs)}

        body = (func_node.body if isinstance(func_node.body, list)
                else [func_node.body])
        # Skip nested def/lambda subtrees: they are their own entries in
        # the reachable set when actually called from traced code.  The
        # exception is Pallas's ``@pl.when(...)`` idiom — the decorator
        # RUNS the nested body at trace time right where it is defined,
        # so its statements belong to the enclosing kernel's scan.
        stack = list(body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(isinstance(deco, ast.Call)
                       and (attr_chain(deco.func) or "").rsplit(
                           ".", 1)[-1] == "when"
                       for deco in node.decorator_list):
                    stack.extend(node.body)
                continue
            if isinstance(node, ast.Lambda):
                continue
            if isinstance(node, ast.Call):
                findings.extend(self._check_call(mod, imports, node,
                                                 params))
            stack.extend(ast.iter_child_nodes(node))
        return findings

    def _check_call(self, mod, imports: _ImportMap, node: ast.Call,
                    params: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        chain = attr_chain(node.func) or ""
        root = chain.split(".", 1)[0]
        root_module = imports.modules.get(root, "")
        name = call_name(node)

        def flag(rule: str, msg: str) -> None:
            out.append(Finding(rule, mod.relpath, node.lineno, msg))

        # time.* inside a traced function.
        if root_module == "time" or chain.startswith("time."):
            flag("jit-host-impurity",
                 f"`{chain}(...)` inside a jit-traced function runs at "
                 f"TRACE time only — the compiled program never sees it")
        # print() (jax.debug.print is an Attribute call, unaffected).
        elif isinstance(node.func, ast.Name) and name == "print":
            flag("jit-host-impurity",
                 "`print(...)` inside a jit-traced function fires at "
                 "trace time only — use jax.debug.print for runtime "
                 "values")
        # Host RNG: stdlib random (but not `from jax import random`)
        # and numpy.random under any alias.
        elif (chain.startswith("random.")
              and imports.modules.get("random", "random") == "random"):
            flag("jit-host-impurity",
                 f"host RNG `{chain}(...)` is baked in at trace time — "
                 f"use jax.random with an explicit key")
        elif (".random." in f"{chain}." and root_module == "numpy"):
            flag("jit-host-impurity",
                 f"host RNG `{chain}(...)` is baked in at trace "
                 f"time — use jax.random with an explicit key")
        # File I/O.
        elif isinstance(node.func, ast.Name) and name == "open":
            flag("jit-host-impurity",
                 "`open(...)` inside a jit-traced function is host I/O "
                 "at trace time")
        # Device sync inside traced code.
        elif name == "block_until_ready":
            flag("jit-host-impurity",
                 "`.block_until_ready()` has no meaning inside a traced "
                 "function — sync on the host after the jitted call")

        # Concretization of traced parameters (root functions only:
        # only there do we know which names are traced).
        if params:
            mentions = {n.id for n in ast.walk(node)
                        if isinstance(n, ast.Name)} & params
            if mentions:
                if (isinstance(node.func, ast.Name)
                        and name in CONCRETIZERS):
                    flag("jit-traced-concretization",
                         f"`{name}(...)` over traced parameter(s) "
                         f"{sorted(mentions)} forces concretization at "
                         f"trace time (Python branching on traced "
                         f"values)")
                elif (name in CONCRETIZE_METHODS
                      and isinstance(node.func, ast.Attribute)):
                    flag("jit-traced-concretization",
                         f"`.{name}()` on traced parameter(s) "
                         f"{sorted(mentions)} pulls the value to host "
                         f"at trace time")
        return out
