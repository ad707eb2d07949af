"""async-timer: host timers bracketing un-synced device dispatches.

jax dispatch is asynchronous: after ``f = jax.jit(g)``, the bracket

    t0 = time.perf_counter()
    out = f(x)
    dt = time.perf_counter() - t0

times the DISPATCH (microseconds) rather than the computation — the
classic source of too-good-to-be-true kernel numbers, and the reason
``bench.py`` pulls a scalar off every result it times. Flagged: a
``perf_counter()`` / ``time()`` / ``monotonic()`` delta whose bracket
contains a call to a name visibly bound to ``jax.jit`` (assignment,
``functools.partial(jax.jit, ...)``, or decorator) with NO
synchronization between the LAST jitted call and the timer stop.
Recognized syncs: ``block_until_ready`` / ``jax.device_get`` /
``np.asarray``/``np.array`` / ``float``/``int``/``bool`` coercion /
``.item()`` / the repo's ``fetch_struct``/``fetch_packed`` helpers.

Only names *visibly* jit-bound in the same module are considered, so
timers around opaque callables (kernels stashed in caches or passed in
as arguments) don't produce noise — the checker trades recall for a
zero-false-positive repo run, like host-sync does.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from ..engine import Finding, RepoIndex, dotted

HINT = ("block on the result before stopping the clock — "
        "jax.block_until_ready(out) (or pull a scalar: "
        "float(np.asarray(out[0]))); for Monitor sections use "
        "Monitor(sync=True) + sec.sync_on(out) so the section blocks "
        "on a sentinel before it stops (docs/observability.md)")

_TIME_FNS = {"time.perf_counter", "time.monotonic", "time.time",
             "perf_counter", "monotonic"}
_SYNC_CALLS = {"jax.block_until_ready", "block_until_ready",
               "jax.device_get", "device_get",
               "np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "float", "int", "bool",
               "fetch_struct", "fetch_packed"}
_SYNC_ATTRS = {"item", "block_until_ready", "sync_on"}
_PARTIALS = {"functools.partial", "partial"}


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit(...)`` or ``partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    d = dotted(node.func)
    if d in ("jax.jit", "jit"):
        return True
    if (d in _PARTIALS and node.args
            and dotted(node.args[0]) in ("jax.jit", "jit")):
        return True
    # partial(jax.jit, ...)(g) / jax.jit(g) applied immediately
    return _is_jit_expr(node.func)


def _jit_bound_names(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(bare names, attribute names) visibly bound to a jitted callable
    anywhere in the module."""
    names: Set[str] = set()
    attrs: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_jit_expr(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
                elif isinstance(tgt, ast.Attribute):
                    attrs.add(tgt.attr)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if dotted(dec) in ("jax.jit", "jit") or _is_jit_expr(dec):
                    names.add(node.name)
    return names, attrs


def _is_jit_call(node: ast.Call, names: Set[str],
                 attrs: Set[str]) -> bool:
    f = node.func
    if isinstance(f, ast.Name) and f.id in names:
        return True
    if isinstance(f, ast.Attribute) and f.attr in attrs:
        return True
    return _is_jit_expr(f)  # immediate jax.jit(g)(x)


def _is_sync(node: ast.Call) -> bool:
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _SYNC_ATTRS:
        return True
    return dotted(node.func) in _SYNC_CALLS


def check_async_timer(index: RepoIndex) -> List[Finding]:
    out: List[Finding] = []
    for mod in index.modules.values():
        jit_names, jit_attrs = _jit_bound_names(mod.tree)
        # group events by lexical function so a timer in one def never
        # brackets a dispatch in another
        starts: Dict[str, Dict[str, int]] = {}
        stops: List[Tuple[str, str, int, ast.AST]] = []
        jit_calls: Dict[str, List[int]] = {}
        syncs: Dict[str, List[int]] = {}
        for node in ast.walk(mod.tree):
            sym = mod.symbol_of(node)
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and dotted(node.value.func) in _TIME_FNS \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                starts.setdefault(sym, {})[node.targets[0].id] = \
                    node.lineno
            elif isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.Sub) \
                    and isinstance(node.right, ast.Name) \
                    and isinstance(node.left, ast.Call) \
                    and dotted(node.left.func) in _TIME_FNS:
                stops.append((sym, node.right.id, node.lineno, node))
            elif isinstance(node, ast.Call):
                if _is_sync(node):
                    syncs.setdefault(sym, []).append(node.lineno)
                elif _is_jit_call(node, jit_names, jit_attrs):
                    jit_calls.setdefault(sym, []).append(node.lineno)
        for sym, tname, stop_ln, stop_node in stops:
            start_ln = starts.get(sym, {}).get(tname)
            if start_ln is None or start_ln >= stop_ln:
                continue
            bracketed = [ln for ln in jit_calls.get(sym, [])
                         if start_ln < ln < stop_ln]
            if not bracketed:
                continue
            last_jit = max(bracketed)
            if any(last_jit <= ln <= stop_ln
                   for ln in syncs.get(sym, [])):
                continue
            out.append(mod.finding(
                "async-timer", stop_node,
                f"timer delta over '{tname}' brackets an async jitted "
                "dispatch with no device sync before the stop — this "
                "times the dispatch, not the computation", HINT))
    return out
