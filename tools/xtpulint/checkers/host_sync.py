"""host-sync: device->host pulls inside per-round / per-level loops.

Each ``.item()`` / ``int(jnp...)`` / ``np.asarray(device_value)`` inside a
hot loop blocks the host on the device stream, serializing work that
async dispatch would otherwise overlap. Scope is the training hot paths
(``tree/``, ``ops/``, ``core.py`` by default) — cold paths pull freely.

Flagged, when lexically inside a ``for``/``while`` in scope:

- ``x.item()`` on any receiver;
- ``int(...)`` / ``float(...)`` / ``bool(...)`` whose argument mentions
  ``jnp.`` / ``jax.`` (a device value is being coerced to a Python
  scalar);
- ``np.asarray(...)`` / ``np.array(...)`` whose argument mentions
  ``jnp.`` / ``jax.``;
- ``jax.device_get(...)`` and ``.block_until_ready()``.
"""

from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, RepoIndex, dotted, enclosing_loop

HINT = ("keep the value on device (lax.cond / jnp.where / carried state), "
        "batch the pull once per level instead of per node, or hoist it "
        "out of the loop; if the sync is intentional and measured, "
        "baseline it with the measurement in the justification")

_COERCERS = {"int", "float", "bool"}
_NP_PULLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
             "jax.device_get", "device_get"}


def _mentions_device(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        d = dotted(sub)
        if d and (d.startswith("jnp.") or d.startswith("jax.")
                  or d == "jnp" or d == "jax"):
            return True
    return False


def check_host_sync(index: RepoIndex) -> List[Finding]:
    scope = index.config.host_sync_scope
    out: List[Finding] = []
    for mod in index.modules.values():
        if not index.in_scope(mod.relpath, scope):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            loop = enclosing_loop(node, mod.parents)
            if loop is None:
                continue
            msg = None
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                msg = (".item() inside a loop forces a device->host sync "
                       "every iteration")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                msg = (".block_until_ready() inside a loop serializes the "
                       "host on the device stream every iteration")
            else:
                d = dotted(node.func)
                if d in _COERCERS and node.args \
                        and _mentions_device(node.args[0]):
                    msg = (f"{d}() coerces a device value to a Python "
                           "scalar inside a loop — one blocking sync per "
                           "iteration")
                elif d in _NP_PULLS and node.args \
                        and (_mentions_device(node.args[0])
                             or d.endswith("device_get")):
                    msg = (f"{d}() materializes a device value on host "
                           "inside a loop — one blocking transfer per "
                           "iteration")
            if msg:
                out.append(mod.finding("host-sync", node, msg, HINT))
    return out
