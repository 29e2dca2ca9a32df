"""jit-purity — traced functions must be pure.

Invariant: everything under ``jax.jit`` executes at TRACE time once
and is then replayed as a compiled graph.  Side effects (print, time,
random) silently freeze into constants; host syncs (``.item()``,
``np.asarray`` on traced values) either crash or force a device
round-trip per call; ``global``/``nonlocal`` writes disappear on the
second call.  The ops/ kernels (cuckoo, rolling_hash, sha256,
similarity) are the dedup fingerprint path — an impure kernel
corrupts dedup ratios in ways parity tests can't always see (cf. CDC
drift, PAPERS.md).
"""

from __future__ import annotations

import ast

from ..core import Rule
from ._util import call_name

_IMPURE_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.")
_IMPURE_CALLS = {
    "print": "trace-time only: runs once at trace, never on device "
             "(use jax.debug.print)",
    "input": "blocks tracing",
    "open": "host IO cannot be traced",
    "jax.device_get": "forces a host sync per call",
}
_SYNC_METHODS = {"item": "host-syncs the device (traced values crash)",
                 "block_until_ready": "host-syncs the device"}
_ASARRAY = ("np.asarray", "numpy.asarray", "np.array", "numpy.array")


def _is_jit_expr(node: ast.AST) -> bool:
    """jax.jit / jit / functools.partial(jax.jit, ...) as an expression."""
    if isinstance(node, ast.Name) and node.id == "jit":
        return True
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    if isinstance(node, ast.Call):
        fname = call_name(node)
        if fname in ("functools.partial", "partial") and node.args:
            return _is_jit_expr(node.args[0])
    return False


class JitPurity(Rule):
    name = "jit-purity"
    invariant = ("functions decorated/wrapped with jax.jit may not call "
                 "time/random/print/IO, host-sync, or mutate outer scope")

    def begin_file(self, ctx):
        if "jit" not in ctx.source:
            return False
        by_name: dict[str, list[ast.AST]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                by_name.setdefault(node.name, []).append(node)
        self._jitted: set[int] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jit_expr(d) for d in node.decorator_list):
                    self._jitted.add(id(node))
            # wrapped form: jax.jit(fn, ...) anywhere in the module marks
            # every same-named def (names are unique in practice)
            if isinstance(node, ast.Call) and _is_jit_expr(node.func):
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        for fn in by_name.get(arg.id, ()):
                            self._jitted.add(id(fn))
        return bool(self._jitted)

    def _in_jit(self, ctx) -> bool:
        return any(id(f) in self._jitted for f in ctx.func_stack)

    def visit_Call(self, ctx, node: ast.Call) -> None:
        if not self._in_jit(ctx):
            return
        name = call_name(node)
        if name in _IMPURE_CALLS:
            ctx.report(self, node,
                       f"`{name}` inside a jitted function: "
                       f"{_IMPURE_CALLS[name]}")
            return
        if name and name.startswith(_IMPURE_PREFIXES):
            ctx.report(self, node,
                       f"`{name}` inside a jitted function freezes into a "
                       "trace-time constant (use jax.random / pass values "
                       "as arguments)")
            return
        if name in _ASARRAY:
            ctx.report(self, node,
                       f"`{name}` inside a jitted function: crashes on "
                       "traced values, silently constant-folds on static "
                       "ones (use jnp.asarray)")
            return
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SYNC_METHODS and not node.args:
            ctx.report(self, node,
                       f"`.{node.func.attr}()` inside a jitted function: "
                       f"{_SYNC_METHODS[node.func.attr]}")

    def visit_Global(self, ctx, node: ast.Global) -> None:
        if self._in_jit(ctx):
            ctx.report(self, node,
                       "`global` write inside a jitted function is applied "
                       "once at trace time, then never again")

    def visit_Nonlocal(self, ctx, node: ast.Nonlocal) -> None:
        if self._in_jit(ctx):
            ctx.report(self, node,
                       "`nonlocal` write inside a jitted function is "
                       "applied once at trace time, then never again")
