"""jit-hygiene pass: device programs must be module-level and
argument-driven.

The PR 3 bug class: a ``jax.jit`` / ``counted_jit`` / ``shard_map``
wrapped program minted inside a function gets a fresh Python identity
per call, so jax's trace cache can never hit — every execution
re-traces — and any value it closes over is frozen at trace time, so a
cache hit (via an outer memo) can silently read a STALE closure.  Both
failure modes disappear when the program lives at module level and
every query-specific value arrives as an argument.

Rule: any wrapper application at function scope is a violation; the
message names the outer variables the wrapped function captures (the
retrace/staleness surface).  The sanctioned escape for legitimately
dynamic programs is ``utils.jitcache.cached_jit`` / a signature-keyed
cache, with a line suppression explaining the key discipline.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tidb_tpu.analysis.core import Pass, Project, SourceFile, Violation

__all__ = ["JitHygienePass"]

# modules whose exported callables are jit-family wrappers
_WRAPPER_IMPORTS = {
    ("jax", "jit"), ("jax", "shard_map"),
    ("tidb_tpu.utils.dispatch", "counted_jit"),
}


def _bound_names(fn: ast.AST) -> Set[str]:
    """Names bound inside a function scope (params + any assignment
    target + comprehension/for/with/except targets + local defs),
    NOT descending into nested function scopes (their bindings are
    their own)."""
    out: Set[str] = set()
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            out.add(arg.arg)

    def walk(node: ast.AST) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(node.name)
            return  # its body is a new scope
        if isinstance(node, ast.Lambda):
            return
        if isinstance(node, ast.ClassDef):
            out.add(node.name)
            return
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                out.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, ast.ExceptHandler) and node.name:
            out.add(node.name)
        for child in ast.iter_child_nodes(node):
            walk(child)

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        walk(stmt)
    return out


def _loaded_names(fn: ast.AST) -> Set[str]:
    """Names read inside a function INCLUDING nested scopes (a nested
    lambda reading an outer name still captures it)."""
    out: Set[str] = set()
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                out.add(node.id)
    return out


class JitHygienePass(Pass):
    id = "jit-hygiene"
    doc = ("jit/counted_jit/shard_map wraps must be module-level; "
           "query-specific values arrive as arguments, never closures")

    def run(self, project: Project) -> List[Violation]:
        out: List[Violation] = []
        for sf in project.files():
            out.extend(self._check_module(sf))
        # one violation per wrap site even when wrappers nest
        # (jax.jit(jax.shard_map(...)) is one device program)
        seen = set()
        uniq = []
        for v in out:
            key = (v.path, v.line)
            if key not in seen:
                seen.add(key)
                uniq.append(v)
        return uniq

    # ------------------------------------------------------------------

    def _check_module(self, sf: SourceFile) -> List[Violation]:
        wrappers = self._wrapper_names(sf.tree)
        out: List[Violation] = []

        def visit(node: ast.AST, fn_stack: List[ast.AST]) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_wrapper(dec, wrappers) and fn_stack:
                        out.append(self._violation(
                            sf, dec, node, fn_stack,
                            f"`{node.name}` is jit-wrapped at function "
                            f"scope (decorator)"))
                fn_stack = fn_stack + [node]
            elif isinstance(node, ast.Lambda):
                fn_stack = fn_stack + [node]
            elif isinstance(node, ast.Call) and self._is_wrapper(
                    node.func, wrappers):
                if fn_stack:
                    target = self._wrapped_target(node, fn_stack[-1])
                    out.append(self._violation(
                        sf, node, target, fn_stack,
                        "device program wrapped at function scope"))
            for child in ast.iter_child_nodes(node):
                visit(child, fn_stack)

        visit(sf.tree, [])
        return out

    def _violation(self, sf: SourceFile, site: ast.AST,
                   target: Optional[ast.AST], fn_stack: List[ast.AST],
                   what: str) -> Violation:
        captured: List[str] = []
        if target is not None:
            enclosing_bound: Set[str] = set()
            for fn in fn_stack:
                enclosing_bound |= _bound_names(fn)
            free = _loaded_names(target) - _bound_names(target)
            captured = sorted(free & enclosing_bound)
        msg = (f"{what}: fresh jit identity per call (retrace) and any "
               "captured value goes stale on cache hits")
        if captured:
            msg += f"; closes over {', '.join(captured)}"
        msg += (". Hoist to module level with the dynamic values as "
                "arguments, or route through a signature-keyed cache "
                "(utils.jitcache.cached_jit) and suppress with the key "
                "discipline as the reason.")
        return Violation(self.id, sf.rel, site.lineno, msg)

    @staticmethod
    def _wrapped_target(call: ast.Call,
                        scope: ast.AST) -> Optional[ast.AST]:
        """The function object being wrapped: a lambda argument, or the
        local def a Name argument points at."""
        if not call.args:
            return None
        arg = call.args[0]
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            body = scope.body if isinstance(scope.body, list) else []
            for stmt in body:
                for node in ast.walk(stmt):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node.name == arg.id:
                        return node
        return None

    @staticmethod
    def _wrapper_names(tree: ast.Module) -> Set[str]:
        """Bare names that are jit-family wrappers in this module."""
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if (node.module, alias.name) in _WRAPPER_IMPORTS:
                        names.add(alias.asname or alias.name)
        return names

    @staticmethod
    def _is_wrapper(node: ast.AST, wrappers: Set[str]) -> bool:
        # jax.jit / jax.shard_map / dispatch.counted_jit attribute form
        if isinstance(node, ast.Attribute):
            if node.attr in ("jit", "shard_map"):
                root = node.value
                if isinstance(root, ast.Name) and root.id == "jax":
                    return True
            if node.attr == "counted_jit":
                return True
        if isinstance(node, ast.Name) and node.id in wrappers:
            return True
        # functools.partial(jax.jit, ...) — the decorator idiom
        if isinstance(node, ast.Call):
            f = node.func
            is_partial = (isinstance(f, ast.Attribute)
                          and f.attr == "partial") or \
                         (isinstance(f, ast.Name) and f.id == "partial")
            if is_partial and node.args:
                return JitHygienePass._is_wrapper(node.args[0], wrappers)
        return False
