"""Trace-safety rules (DESIGN.md §6/§13): the solver hot path compiles
ONCE per (shape, config); everything that silently retraces or runs device
work at import is a measured regression class (PR 2)."""
from __future__ import annotations

import ast

from ..registry import RawFinding, Rule, RuleMeta, register
from ._common import (is_device_work_call, jit_decorated, loop_bodies,
                      param_names)


@register
class ImportTimeDeviceWork(Rule):
    """TRC001: `jnp.*` (and device_put) calls evaluated at module import.

    Import-time device work allocates buffers / compiles before anyone
    chose a device or config, breaks JAX_PLATFORMS-late selection, and
    slows every CLI/test import. Flags module-level statements, class
    bodies, and function default arguments; `if __name__ == "__main__"`
    and `if TYPE_CHECKING` blocks stay exempt.
    """

    meta = RuleMeta(
        id="TRC001", name="import-time-jnp",
        summary="no jax.numpy/device work at module import time",
        default_include=("src",))

    def check(self, ctx):
        for node in self._import_time_nodes(ctx.tree):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    name = ctx.resolve(sub.func)
                    if name and is_device_work_call(name):
                        yield RawFinding(
                            sub.lineno, sub.col_offset,
                            f"`{name}` runs device work at import time — "
                            "build arrays lazily inside the function that "
                            "uses them")

    def _import_time_nodes(self, tree):
        """Statements executed at import: module body (minus guarded ifs
        and def/class *bodies*), class bodies, and default-arg expressions."""
        for stmt in self._module_stmts(tree.body):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from stmt.args.defaults
                yield from (d for d in stmt.args.kw_defaults if d is not None)
            elif isinstance(stmt, ast.ClassDef):
                for sub in stmt.body:
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        yield from sub.args.defaults
                        yield from (d for d in sub.args.kw_defaults
                                    if d is not None)
                    else:
                        yield sub
            else:
                yield stmt

    def _module_stmts(self, body):
        for stmt in body:
            if isinstance(stmt, ast.If):
                if not self._guarded(stmt):
                    yield from self._module_stmts(stmt.body + stmt.orelse)
            elif isinstance(stmt, (ast.Try, ast.With)):
                inner = list(getattr(stmt, "body", []))
                for h in getattr(stmt, "handlers", []):
                    inner.extend(h.body)
                inner.extend(getattr(stmt, "orelse", []))
                inner.extend(getattr(stmt, "finalbody", []))
                yield from self._module_stmts(inner)
            else:
                yield stmt

    def _guarded(self, stmt: ast.If) -> bool:
        src = ast.dump(stmt.test)
        return "__main__" in src or "TYPE_CHECKING" in src


@register
class PythonBranchOnTraced(Rule):
    """TRC002: Python control flow / scalar coercion on traced values.

    Inside traced code — jit-decorated functions and `while_loop` /
    `fori_loop` / `scan` bodies — `bool()`, `float()`, `int()`, `.item()`
    and `if`/`while` on operands force a device sync at trace time (or a
    TracerBoolConversionError). Branching on *static* jit args is legal
    and recognized via `static_argnames`/`static_argnums`.
    """

    meta = RuleMeta(
        id="TRC002", name="traced-python-branch",
        summary="no Python bool/if or scalar coercion on traced values in "
                "solver bodies",
        default_include=("src/repro/core",))

    _COERCERS = ("bool", "float", "int")

    def check(self, ctx):
        for fn, statics, _dec in jit_decorated(ctx):
            yield from self._scan(ctx, fn, set(param_names(fn)) - statics)
        for body, _call, loop in loop_bodies(ctx):
            yield from self._scan(ctx, body, set(param_names(body)),
                                  where=f"{loop.rsplit('.', 1)[-1]} body")

    def _scan(self, ctx, fn, traced_params, where="jit-compiled function"):
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in (n for stmt in body for n in ast.walk(stmt)):
            if isinstance(node, (ast.If, ast.While)):
                if not self._structure_check(node.test) and \
                        self._touches_traced(ctx, node.test, traced_params):
                    yield RawFinding(
                        node.lineno, node.col_offset,
                        f"Python `{'if' if isinstance(node, ast.If) else 'while'}`"
                        f" on a traced value inside a {where} — use lax.cond/"
                        "jnp.where, or mark the argument static")
            elif isinstance(node, ast.Call):
                fname = ctx.resolve(node.func)
                if fname in self._COERCERS and node.args and \
                        self._touches_traced(ctx, node.args[0], traced_params):
                    yield RawFinding(
                        node.lineno, node.col_offset,
                        f"`{fname}()` concretizes a traced value inside a "
                        f"{where} — keep it on-device (trace-once discipline, "
                        "DESIGN.md §6)")
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args and \
                        self._touches_traced(ctx, node.func.value, traced_params):
                    yield RawFinding(
                        node.lineno, node.col_offset,
                        f"`.item()` concretizes a traced value inside a {where}")

    def _structure_check(self, expr) -> bool:
        """`x is None` / `x is not None` (and not/and/or combinations)
        branch on pytree STRUCTURE, which is part of the jit key — legal
        Python control flow even on traced-argument names."""
        if isinstance(expr, ast.Compare):
            return all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops)
        if isinstance(expr, ast.BoolOp):
            return all(self._structure_check(v) for v in expr.values)
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
            return self._structure_check(expr.operand)
        return False

    def _touches_traced(self, ctx, expr, traced_params) -> bool:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Name) and sub.id in traced_params:
                return True
            if isinstance(sub, ast.Call):
                name = ctx.resolve(sub.func)
                if name and name.startswith("jax.numpy."):
                    return True
        return False


@register
class JitStaticConfig(Rule):
    """TRC003: jit boundaries must mark config-like params static.

    Passing an (unhashable, equality-keyed) config object as a traced arg
    either crashes at the boundary or — worse — retraces per call when the
    object is hashable but fresh each time. The repo convention since PR 2:
    `config` / `mesh` / `axes` style params are `static_argnames` at every
    jit boundary.
    """

    meta = RuleMeta(
        id="TRC003", name="jit-static-config",
        summary="jit-decorated functions mark config/mesh params static",
        default_include=("src",))

    _CONFIGY = ("config", "cfg", "mesh", "axes")

    def check(self, ctx):
        for fn, statics, dec in jit_decorated(ctx):
            missing = [p for p in param_names(fn)
                       if (p in self._CONFIGY or p.endswith("_config"))
                       and p not in statics]
            if missing:
                yield RawFinding(
                    dec.lineno, dec.col_offset,
                    f"jit boundary `{fn.name}` takes {missing} without "
                    "static treatment — add static_argnames (trace-once "
                    "discipline, DESIGN.md §6)")
