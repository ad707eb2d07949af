"""xtpulint core: repo model, call graph, traced-region inference, findings.

The analyzer is deliberately domain-specific: it knows this codebase's
failure modes (trace-time env capture, host syncs in round loops, donated
buffers, lock discipline, rank-asymmetric collectives) rather than trying
to be a general Python linter. Everything is plain ``ast`` — no imports of
the analyzed code, so a broken module can still be linted and fixtures
never execute.

Key concepts:

- :class:`RepoIndex` parses every file once and exposes per-module ASTs,
  a function table (qualified names, nesting, owning class) and resolved
  import aliases.
- *Traced regions* are function/lambda nodes that jax traces: decorated
  with ``jax.jit`` (bare or through ``partial``), passed to a tracing
  wrapper (``jit``/``shard_map``/``pallas_call``/``lax.scan``/...), or
  reachable from one through the call graph.
- The *call graph* is name-based (class-hierarchy-agnostic): a call edge
  ``f -> g`` exists when ``f``'s body calls a name or attribute that
  resolves to ``g``. Attribute calls resolve by method name across the
  repo, capped by :data:`MAX_NAME_FANOUT` so hub names (``get``, ``sum``)
  don't connect everything to everything.
- A :class:`Finding` carries a stable fingerprint (checker + path +
  enclosing symbol + whitespace-normalized line text) so baseline entries
  survive unrelated line drift.
"""

from __future__ import annotations

import ast
import hashlib
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# ----------------------------------------------------------------- constants

# Call targets that trace their function argument(s). Matched against the
# dotted source text of the call's func (exact or final-attribute match).
TRACE_WRAPPERS = {
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap", "jax.vmap", "vmap",
    "jax.grad", "jax.value_and_grad", "jax.remat", "jax.checkpoint",
    "jax.shard_map", "shard_map",
    "pl.pallas_call", "pallas_call",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.cond", "lax.cond", "jax.lax.switch", "lax.switch",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.map", "lax.map",
}

# jit-like wrappers that create a compile cache (used by the recompile and
# donation checkers; scan/cond trace but don't own a cache or donation).
JIT_WRAPPERS = {"jax.jit", "jit", "pjit"}

PARTIAL_NAMES = {"partial", "functools.partial", "_functools.partial"}

# Attribute-call names never resolved through the name-based call graph:
# they are ubiquitous library verbs, and an edge through them would connect
# unrelated code.
ATTR_RESOLVE_SKIP = {
    "get", "items", "keys", "values", "update", "copy", "pop", "append",
    "extend", "add", "sum", "mean", "max", "min", "all", "any", "astype",
    "reshape", "join", "split", "strip", "lower", "upper", "format",
    "encode", "decode", "read", "write", "close", "flush", "result",
    "setdefault", "sort", "count", "index", "insert", "remove", "clear",
    "shape", "item", "tolist", "replace", "startswith", "endswith", "t",
}

# A method name defined more than this many times repo-wide is too generic
# to resolve by name alone.
MAX_NAME_FANOUT = 6

SUPPRESS_TOKEN = "xtpulint: disable="


# ------------------------------------------------------------------ findings

@dataclass
class Finding:
    checker: str          # slug, e.g. "trace-capture"
    path: str             # repo-relative posix path
    line: int
    symbol: str           # enclosing qualname ("module" when top-level)
    message: str
    hint: str = ""
    line_text: str = ""   # stripped source of the flagged line
    occurrence: int = 0   # disambiguates identical lines in one symbol

    @property
    def fingerprint(self) -> str:
        norm = "".join(self.line_text.split())
        key = f"{self.checker}|{self.path}|{self.symbol}|{norm}"
        if self.occurrence:
            key += f"#{self.occurrence}"
        return hashlib.sha1(key.encode()).hexdigest()[:12]

    def to_dict(self) -> Dict[str, object]:
        return {
            "checker": self.checker, "path": self.path, "line": self.line,
            "symbol": self.symbol, "message": self.message,
            "hint": self.hint, "fingerprint": self.fingerprint,
        }

    def render(self) -> str:
        out = (f"{self.path}:{self.line}: [{self.checker}] "
               f"({self.symbol}) {self.message}")
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


def finalize_findings(findings: List[Finding]) -> List[Finding]:
    """Sort and assign occurrence indices so identical-line findings in one
    symbol get distinct fingerprints."""
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.message))
    seen: Dict[Tuple[str, str, str, str], int] = {}
    for f in findings:
        key = (f.checker, f.path, f.symbol, "".join(f.line_text.split()))
        f.occurrence = seen.get(key, 0)
        seen[key] = f.occurrence + 1
    return findings


# ----------------------------------------------------------------- ast utils

def dotted(node: ast.AST) -> Optional[str]:
    """Source-dotted name of a Name/Attribute chain; None otherwise."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def matches(name: Optional[str], candidates: Set[str]) -> bool:
    """True when the dotted name equals a candidate or ends with one of the
    dotted candidates' final two components (``a.b.jit`` matches
    ``jax.jit``)."""
    if not name:
        return False
    if name in candidates:
        return True
    tail = name.rsplit(".", 1)[-1]
    for c in candidates:
        if "." in c and (name.endswith("." + c) or c.endswith("." + tail)
                         and name.endswith("." + c.rsplit(".", 1)[-1])
                         and tail == c.rsplit(".", 1)[-1]):
            return True
    return False


def is_env_read(node: ast.AST) -> Optional[Tuple[ast.AST, Optional[str],
                                                 Optional[str]]]:
    """Detect ``os.environ.get(k[, d])`` / ``os.environ[k]`` /
    ``os.getenv(k[, d])``. Returns (node, var_name, default_repr) or None.
    """
    def const_str(n: ast.AST) -> Optional[str]:
        return n.value if isinstance(n, ast.Constant) \
            and isinstance(n.value, str) else None

    def const_repr(n: Optional[ast.AST]) -> Optional[str]:
        if n is None:
            return None
        try:
            return ast.unparse(n)
        except Exception:  # pragma: no cover - unparse is total on 3.10
            return None

    if isinstance(node, ast.Call):
        d = dotted(node.func)
        if d and (d == "os.getenv" or d.endswith(".getenv")
                  or d == "getenv"):
            var = const_str(node.args[0]) if node.args else None
            default = const_repr(node.args[1]) if len(node.args) > 1 \
                else None
            return node, var, default
        if isinstance(node.func, ast.Attribute) and node.func.attr == "get":
            base = dotted(node.func.value)
            if base and (base == "os.environ" or base.endswith(".environ")
                         or base == "environ"):
                var = const_str(node.args[0]) if node.args else None
                default = const_repr(node.args[1]) if len(node.args) > 1 \
                    else None
                return node, var, default
    if isinstance(node, ast.Subscript):
        base = dotted(node.value)
        if base and (base == "os.environ" or base.endswith(".environ")
                     or base == "environ"):
            var = const_str(node.slice)
            return node, var, None
    return None


def enclosing_loop(node: ast.AST, parents: Dict[ast.AST, ast.AST],
                   stop_at_function: bool = True) -> Optional[ast.AST]:
    """Nearest For/While ancestor without crossing a def boundary."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.While)):
            return cur
        if stop_at_function and isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return None
        cur = parents.get(cur)
    return None


# -------------------------------------------------------------- module model

@dataclass
class FuncInfo:
    qualname: str                  # "pkg/mod.py::Class.method" style symbol
    name: str
    node: ast.AST                  # FunctionDef / AsyncFunctionDef / Lambda
    module: "ModuleInfo"
    class_name: Optional[str] = None
    traced: bool = False           # directly handed to a tracing wrapper
    call_names: Set[str] = field(default_factory=set)      # bare-name calls
    attr_calls: Set[str] = field(default_factory=set)      # x.m() names
    refs: Set[str] = field(default_factory=set)            # bare Name loads

    @property
    def symbol(self) -> str:
        return self.qualname.split("::", 1)[1]


@dataclass
class ModuleInfo:
    relpath: str                   # posix, repo-relative
    tree: ast.Module
    lines: List[str]
    functions: Dict[str, FuncInfo] = field(default_factory=dict)
    # simple alias map from imports: local name -> dotted origin
    imports: Dict[str, str] = field(default_factory=dict)
    parents: Dict[ast.AST, ast.AST] = field(default_factory=dict)
    # func node -> FuncInfo for fast symbol lookup of any ast node
    by_node: Dict[ast.AST, FuncInfo] = field(default_factory=dict)
    # pragma lines that suppressed at least one finding this run — the
    # stale-pragma checker flags the SUPPRESS_TOKEN lines missing here
    pragma_hits: Set[int] = field(default_factory=set)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def symbol_of(self, node: ast.AST) -> str:
        cur: Optional[ast.AST] = node
        while cur is not None:
            info = self.by_node.get(cur)
            if info is not None:
                return info.symbol
            cur = self.parents.get(cur)
        return "<module>"

    def suppressed(self, lineno: int, checker: str) -> bool:
        for ln in (lineno, lineno - 1):
            text = self.line_text(ln)
            if SUPPRESS_TOKEN in text:
                ids = text.split(SUPPRESS_TOKEN, 1)[1].split()[0]
                names = {s.strip() for s in ids.split(",")}
                # "all" never covers the meta-checker: a blanket pragma
                # must not be able to hide its own staleness
                if checker in names or \
                        ("all" in names and checker != "stale-pragma"):
                    self.pragma_hits.add(ln)
                    return True
        return False

    def finding(self, checker: str, node: ast.AST, message: str,
                hint: str = "") -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(checker=checker, path=self.relpath, line=line,
                       symbol=self.symbol_of(node), message=message,
                       hint=hint, line_text=self.line_text(line))


class _FuncCollector(ast.NodeVisitor):
    """Populate ModuleInfo.functions with nesting-aware qualnames."""

    def __init__(self, mod: ModuleInfo) -> None:
        self.mod = mod
        self.stack: List[str] = []
        self.class_stack: List[str] = []

    def _add(self, node: ast.AST, name: str) -> FuncInfo:
        qual = ".".join(self.stack + [name])
        info = FuncInfo(
            qualname=f"{self.mod.relpath}::{qual}", name=name, node=node,
            module=self.mod,
            class_name=self.class_stack[-1] if self.class_stack else None)
        self.mod.functions[info.qualname] = info
        self.mod.by_node[node] = info
        return info

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()
        self.stack.pop()

    def _visit_func(self, node) -> None:
        self._add(node, node.name)
        self.stack.append(node.name)
        # class context does not extend into nested defs' own lookups,
        # but keeping class_stack is right: a nested def still belongs to
        # the method's class for lock-context purposes.
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._add(node, f"<lambda:{node.lineno}>")
        self.generic_visit(node)


def _collect_parents(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent
    return parents


def _collect_imports(mod: ModuleInfo) -> None:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                mod.imports[alias.asname or alias.name.split(".")[0]] = \
                    alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            for alias in node.names:
                mod.imports[alias.asname or alias.name] = \
                    f"{base}.{alias.name}" if base else alias.name


def _collect_calls(mod: ModuleInfo) -> None:
    """Record, per function, the names it calls / references (call-graph
    edges are resolved later at the repo level)."""
    for info in mod.functions.values():
        for node in ast.walk(info.node):
            # nodes inside nested defs belong to the nested FuncInfo
            if mod.symbol_of(node) != info.symbol:
                continue
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name):
                    info.call_names.add(node.func.id)
                elif isinstance(node.func, ast.Attribute):
                    info.attr_calls.add(node.func.attr)
            elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load):
                info.refs.add(node.id)


# ----------------------------------------------------------------- the index

@dataclass
class LintConfig:
    root: str
    paths: Tuple[str, ...] = ("xgboost_tpu",)
    # path-prefix scopes for the location-sensitive checkers
    host_sync_scope: Tuple[str, ...] = (
        "xgboost_tpu/tree/", "xgboost_tpu/ops/", "xgboost_tpu/core.py")
    lock_scope: Tuple[str, ...] = (
        "xgboost_tpu/serve/", "xgboost_tpu/pipeline/",
        "xgboost_tpu/utils/checkpoint.py", "xgboost_tpu/data/binned.py",
        "xgboost_tpu/parallel/")
    select: Optional[Tuple[str, ...]] = None   # checker slugs to run


class RepoIndex:
    """Parsed view of every scanned module plus the repo-level call graph."""

    def __init__(self, config: LintConfig) -> None:
        self.config = config
        self.modules: Dict[str, ModuleInfo] = {}
        self.errors: List[str] = []
        self._load()
        # name -> [FuncInfo] across the repo (functions and methods)
        self.defs_by_name: Dict[str, List[FuncInfo]] = {}
        for mod in self.modules.values():
            for info in mod.functions.values():
                self.defs_by_name.setdefault(info.name, []).append(info)
        self._mark_traced_entries()
        self.traced_reachable = self._reach_from_traced()

    # ------------------------------------------------------------- loading
    def _load(self) -> None:
        root = os.path.abspath(self.config.root)
        files: List[str] = []
        for p in self.config.paths:
            full = os.path.join(root, p)
            if os.path.isfile(full):
                files.append(full)
                continue
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__",)]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        files.append(os.path.join(dirpath, fn))
        for path in sorted(files):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    src = fh.read()
                tree = ast.parse(src, filename=rel)
            except (SyntaxError, UnicodeDecodeError, OSError) as e:
                self.errors.append(f"{rel}: {e}")
                continue
            mod = ModuleInfo(relpath=rel, tree=tree,
                             lines=src.splitlines())
            mod.parents = _collect_parents(tree)
            _FuncCollector(mod).visit(tree)
            _collect_imports(mod)
            _collect_calls(mod)
            self.modules[rel] = mod

    # ---------------------------------------------------- traced detection
    def _mark_traced_entries(self) -> None:
        for mod in self.modules.values():
            # decorators
            for info in mod.functions.values():
                node = info.node
                if isinstance(node, ast.Lambda):
                    continue
                for dec in node.decorator_list:
                    if self._is_trace_wrapper_expr(dec):
                        info.traced = True
            # f passed to a wrapper call anywhere in the module
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                if not self._is_trace_wrapper_call(node):
                    continue
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    self._mark_traced_arg(mod, node, arg)

    def _is_trace_wrapper_expr(self, dec: ast.AST) -> bool:
        d = dotted(dec)
        if matches(d, TRACE_WRAPPERS):
            return True
        if isinstance(dec, ast.Call):
            return self._is_trace_wrapper_call(dec)
        return False

    def _is_trace_wrapper_call(self, call: ast.Call) -> bool:
        d = dotted(call.func)
        if matches(d, TRACE_WRAPPERS):
            return True
        # partial(jax.jit, ...) / functools.partial(jit, ...)
        if matches(d, PARTIAL_NAMES) and call.args:
            return matches(dotted(call.args[0]), TRACE_WRAPPERS)
        return False

    def _mark_traced_arg(self, mod: ModuleInfo, call: ast.Call,
                         arg: ast.AST) -> None:
        if isinstance(arg, ast.Lambda):
            info = mod.by_node.get(arg)
            if info is not None:
                info.traced = True
        elif isinstance(arg, ast.Name):
            target = self._resolve_local_name(mod, call, arg.id)
            if target is not None:
                target.traced = True

    def _resolve_local_name(self, mod: ModuleInfo, at: ast.AST,
                            name: str) -> Optional[FuncInfo]:
        """Resolve a bare name to a def: innermost enclosing scope first,
        then module level, then unique repo-wide."""
        sym = mod.symbol_of(at)
        # candidate quals from innermost scope outwards
        parts = sym.split(".") if sym != "<module>" else []
        for depth in range(len(parts), -1, -1):
            qual = ".".join(parts[:depth] + [name])
            info = mod.functions.get(f"{mod.relpath}::{qual}")
            if info is not None:
                return info
        # imported from a sibling module?
        origin = mod.imports.get(name)
        if origin:
            leaf = origin.rsplit(".", 1)[-1]
            cands = [d for d in self.defs_by_name.get(leaf, [])
                     if d.class_name is None]
            if len(cands) == 1:
                return cands[0]
        cands = [d for d in self.defs_by_name.get(name, [])
                 if d.class_name is None]
        if len(cands) == 1:
            return cands[0]
        return None

    # ----------------------------------------------------------- call graph
    def _callees(self, info: FuncInfo) -> Set[str]:
        out: Set[str] = set()
        mod = info.module
        for name in info.call_names | (info.refs if info.traced else set()):
            target = self._resolve_local_name(mod, info.node, name)
            if target is not None:
                out.add(target.qualname)
        for attr in info.attr_calls:
            if attr in ATTR_RESOLVE_SKIP or attr.startswith("__"):
                continue
            cands = self.defs_by_name.get(attr, [])
            if 0 < len(cands) <= MAX_NAME_FANOUT:
                out.update(c.qualname for c in cands)
        return out

    def _reach_from_traced(self) -> Set[str]:
        """Qualnames of every function reachable from a traced region."""
        edges: Dict[str, Set[str]] = {}
        roots: List[str] = []
        for mod in self.modules.values():
            for info in mod.functions.values():
                edges[info.qualname] = self._callees(info)
                if info.traced:
                    roots.append(info.qualname)
                    # nested defs of a traced fn run under the trace too
                    prefix = info.qualname + "."
                    roots.extend(q for q in mod.functions if
                                 q.startswith(prefix))
        seen: Set[str] = set()
        stack = list(roots)
        while stack:
            q = stack.pop()
            if q in seen:
                continue
            seen.add(q)
            stack.extend(edges.get(q, ()))
        return seen

    def func_of(self, qualname: str) -> Optional[FuncInfo]:
        rel = qualname.split("::", 1)[0]
        mod = self.modules.get(rel)
        return mod.functions.get(qualname) if mod else None

    def in_scope(self, relpath: str, scope: Sequence[str]) -> bool:
        return any(relpath == s or relpath.startswith(s) for s in scope)


# ------------------------------------------------------------------- running

def run_checkers(index: RepoIndex) -> List[Finding]:
    from .checkers import CHECKERS

    select = index.config.select
    findings: List[Finding] = []
    for slug, fn in CHECKERS.items():
        if select and slug not in select:
            continue
        for f in fn(index):
            mod = index.modules.get(f.path)
            if mod is not None and mod.suppressed(f.line, f.checker):
                continue
            findings.append(f)
    return finalize_findings(findings)
