"""Shared AST helpers for the lint layer: dotted-name resolution and the
traced-function index (which functions in a module execute under JAX
tracing).

The traced index is deliberately a *syntactic* approximation — no
imports are executed. A function counts as traced when it is:

1. decorated with ``jax.jit`` / ``jax.vmap`` / ``jax.pmap`` /
   ``jax.grad`` / ``jax.value_and_grad`` / ``jax.remat`` /
   ``jax.checkpoint`` / ``shard_map`` — directly or through
   ``functools.partial(jax.jit, ...)``;
2. passed by name into one of those wrappers, or into
   ``jax.lax.scan`` / ``lax.fori_loop`` / ``lax.while_loop`` /
   ``lax.cond`` / ``lax.switch`` / ``shard_map`` / ``pallas_call``;
3. defined INSIDE a traced function (nested defs run during trace);
4. calling an in-trace-only primitive (``lax.psum`` / ``pmean`` /
   ``ppermute`` / ``all_gather`` / ``axis_index``) — such a body can
   only ever execute under tracing; or
5. called by name from another traced function in the same module
   (a fixpoint over module-level defs — the "code path" closure).

Cross-module calls are NOT followed; the per-module fixpoint plus rule
(4) covers the repo's real traced paths without import-time execution.
"""
from __future__ import annotations

import ast
from typing import Dict, Optional, Set

# names (match by dotted suffix) that trace their function argument
TRACE_WRAPPERS = {
    "jax.jit", "jit",
    "jax.vmap", "vmap",
    "jax.pmap", "pmap",
    "jax.grad", "grad",
    "jax.value_and_grad", "value_and_grad",
    "jax.remat", "remat",
    "jax.checkpoint", "checkpoint",
    "shard_map",
}
TRACE_HOFS = {           # higher-order control flow: fn is the 1st arg
    "lax.scan", "jax.lax.scan", "scan",
    "lax.fori_loop", "jax.lax.fori_loop",
    "lax.while_loop", "jax.lax.while_loop",
    "lax.cond", "jax.lax.cond",
    "lax.switch", "jax.lax.switch",
    "pallas_call", "pl.pallas_call",
}
TRACE_ONLY_PRIMS = {     # callable only under tracing with a named axis
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather",
    "all_to_all", "axis_index", "psum_scatter",
}
PARTIAL_NAMES = {"functools.partial", "partial"}
SUB_F32 = {"bfloat16", "float16", "bf16", "f16"}


def dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _suffix_in(name: Optional[str], names: Set[str]) -> bool:
    if name is None:
        return False
    return name in names or any(name.endswith("." + n) for n in names)


def is_partial_of(call: ast.AST, names: Set[str]) -> bool:
    """``functools.partial(jax.jit, ...)``-style expression?"""
    return (isinstance(call, ast.Call)
            and dotted(call.func) in PARTIAL_NAMES
            and call.args
            and _suffix_in(dotted(call.args[0]), names))


def is_trace_wrapper_expr(node: ast.AST) -> bool:
    """Does ``node`` evaluate to something that traces its argument —
    ``jax.jit``, ``functools.partial(jax.jit, ...)``, a ``shard_map``
    call missing only the function, ..."""
    name = dotted(node)
    if name is not None and _suffix_in(name, TRACE_WRAPPERS):
        return True
    if isinstance(node, ast.Call):
        if _suffix_in(dotted(node.func), TRACE_WRAPPERS):
            return True
        if is_partial_of(node, TRACE_WRAPPERS):
            return True
    return False


def is_sub_f32(node: ast.AST) -> bool:
    """``jnp.bfloat16`` / ``np.float16`` / ``"bfloat16"`` / ... — a
    dtype expression below f32 precision."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value in SUB_F32
    name = dotted(node)
    return name is not None and name.split(".")[-1] in SUB_F32


class TracedIndex:
    """The set of function nodes in one module that run under tracing."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self._defs: Dict[str, ast.AST] = {}
        self._parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # last def wins on shadowing — matches runtime binding
                self._defs[node.name] = node
        self.traced: Set[ast.AST] = set()
        self._seed_traced()
        self._fixpoint()

    # -- seeding ----------------------------------------------------------

    def _seed_traced(self):
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(is_trace_wrapper_expr(d) for d in node.decorator_list):
                    self.traced.add(node)
                elif self._calls_trace_only_prim(node):
                    self.traced.add(node)
            elif isinstance(node, ast.Call):
                self._seed_from_call(node)

    def _seed_from_call(self, call: ast.Call):
        fname = dotted(call.func)
        args = call.args
        # jax.jit(f) / vmap(f) / partial(jax.jit, ...)(f) / shard_map(f,...)
        if (_suffix_in(fname, TRACE_WRAPPERS)
                or is_partial_of(call, TRACE_WRAPPERS)
                or (fname is None and is_trace_wrapper_expr(call.func))):
            for a in args[:1]:
                self._mark_name(a)
        # lax.scan(f, ...) and friends: any function NAME handed to a
        # control-flow HOF is traced, whatever its position (cond takes
        # two branches, fori_loop's body is the 3rd arg, ...)
        if _suffix_in(fname, TRACE_HOFS):
            for a in args:
                self._mark_name(a)

    def _mark_name(self, node: ast.AST):
        name = dotted(node)
        if name is not None and name in self._defs:
            self.traced.add(self._defs[name])

    def _calls_trace_only_prim(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted(node.func)
                if d is not None and d.split(".")[-1] in TRACE_ONLY_PRIMS:
                    return True
        return False

    # -- closure ----------------------------------------------------------

    def _fixpoint(self):
        changed = True
        while changed:
            changed = False
            for fn in list(self.traced):
                for node in ast.walk(fn):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and node is not fn and node not in self.traced:
                        self.traced.add(node)        # nested defs trace too
                        changed = True
                    if isinstance(node, ast.Call):
                        name = dotted(node.func)
                        if name in self._defs \
                                and self._defs[name] not in self.traced:
                            self.traced.add(self._defs[name])
                            changed = True

    # -- queries -----------------------------------------------------------

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self._parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self._parents.get(cur)
        return None

    def in_traced(self, node: ast.AST) -> bool:
        fn = self.enclosing_function(node)
        while fn is not None:
            if fn in self.traced:
                return True
            fn = self.enclosing_function(fn)
        return False

    def traced_functions(self):
        return iter(self.traced)
