"""Rule JL104 ``host-sync``: device→host syncs inside iteration loops.

The iteration runtime's loop bodies are the measured hot path: an
``np.asarray``/``.item()``/``print`` on a device array there blocks on
the device queue every round, silently serializing the async dispatch pipeline the runtime
exists to keep full. Static analysis cannot see residency, so the rule
is scoped by PATH (modules whose path mentions ``iteration``) and by
POSITION (inside a For/While body, same function scope) — exactly where
a sync is a per-round cost; deliberate syncs get a justified
suppression.
"""

from __future__ import annotations

import ast
from typing import Iterator

from flink_ml_tpu.analysis.core import (
    FileContext,
    Finding,
    Rule,
    call_name,
    register,
)

#: path fragments that mark hot-loop modules (the iteration runtime and
#: its streaming driver)
PATH_MARKERS = ("iteration",)

_SYNC_CALLS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}


@register
class HostSyncRule(Rule):
    name = "host-sync"
    code = "JL104"
    rationale = (
        "np.asarray/.item()/print inside an iteration-runtime loop body "
        "blocks on the device queue every round, serializing async "
        "dispatch")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        path = ctx.path.replace("\\", "/")
        if not any(m in path for m in PATH_MARKERS):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if ctx.enclosing_loop(node) is None:
                continue
            name = call_name(node)
            if name in _SYNC_CALLS:
                yield self.finding(
                    ctx, node,
                    f"`{name}` in an iteration loop body synchronously "
                    "pulls the array to host every round (hoist it out "
                    "of the loop, or keep the value on device)")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield self.finding(
                    ctx, node,
                    "`.item()` in an iteration loop body is a blocking "
                    "device sync every round (batch the readback, or "
                    "carry the scalar on device)")
            elif name == "print":
                yield self.finding(
                    ctx, node,
                    "`print` in an iteration loop body forces "
                    "device-to-host materialization of its arguments "
                    "every round (log outside the loop or use "
                    "jax.debug.print)")
