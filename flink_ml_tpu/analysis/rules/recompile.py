"""Rule JL102 ``recompile-hazard``: jit churn and unhashable statics.

``jax.jit`` called inside a loop body builds a fresh ``PjitFunction``
per iteration, so the compile cache is keyed on a new object and every
iteration pays a retrace and a full compile. Passing an unhashable value (list/
dict/set/ndarray) for a declared static argument raises at call time —
after a possibly long trace. Both are invisible until the hot loop runs.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, Set, Tuple

from flink_ml_tpu.analysis.core import (
    FileContext,
    Finding,
    Rule,
    call_name,
    register,
)
from flink_ml_tpu.analysis.rules._shared import (
    _is_jit_callee,
    _literal_statics,
)

#: expression forms that are unhashable at runtime
_UNHASHABLE_NODES = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)
_UNHASHABLE_CALLS = {"list", "dict", "set", "bytearray",
                     "np.array", "np.asarray", "np.zeros", "np.ones",
                     "np.arange", "numpy.array", "numpy.asarray",
                     "numpy.zeros", "numpy.ones", "numpy.arange"}


def _is_unhashable(node: ast.AST) -> bool:
    if isinstance(node, _UNHASHABLE_NODES):
        return True
    return isinstance(node, ast.Call) and call_name(node) in _UNHASHABLE_CALLS


@register
class RecompileHazardRule(Rule):
    name = "recompile-hazard"
    code = "JL102"
    rationale = (
        "jax.jit inside a loop body recompiles every iteration (fresh "
        "cache key per PjitFunction); an unhashable static_argnums/"
        "static_argnames value dies at call time after the trace")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        #: name -> (static_argnums, static_argnames) of jitted callables
        jitted: Dict[str, Tuple[Set[int], Set[str]]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not _is_jit_callee(
                    node.func):
                continue
            if ctx.enclosing_loop(node) is not None:
                yield self.finding(
                    ctx, node,
                    "jit/shard_map wrapped inside a loop body: a fresh "
                    "traced callable per iteration defeats the compile "
                    "cache — hoist it (module level or "
                    "functools.lru_cache)")
            argnums, argnames = _literal_statics(node.keywords)
            if not argnums and not argnames:
                continue
            parent = ctx.parents.get(node)
            if isinstance(parent, ast.Assign):
                for tgt in parent.targets:
                    if isinstance(tgt, ast.Name):
                        jitted[tgt.id] = (argnums, argnames)
            elif isinstance(parent, ast.Call) and parent.func is node:
                # immediate call: jax.jit(f, static_argnums=0)(...)
                yield from self._check_call(ctx, parent, argnums, argnames)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Name) and node.func.id in jitted:
                argnums, argnames = jitted[node.func.id]
                yield from self._check_call(ctx, node, argnums, argnames)

    def _check_call(self, ctx, call: ast.Call, argnums: Set[int],
                    argnames: Set[str]) -> Iterator[Finding]:
        for i, arg in enumerate(call.args):
            if i in argnums and _is_unhashable(arg):
                yield self.finding(
                    ctx, arg,
                    f"unhashable value for static argument {i}: jit "
                    "statics are cache keys and must be hashable (pass "
                    "a tuple, or drop the static declaration)")
        for kw in call.keywords:
            if kw.arg in argnames and _is_unhashable(kw.value):
                yield self.finding(
                    ctx, kw.value,
                    f"unhashable value for static argument "
                    f"{kw.arg!r}: jit statics are cache keys and must "
                    "be hashable")
