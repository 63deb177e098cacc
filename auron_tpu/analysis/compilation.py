"""Static compilation-hygiene lint: the compile-time half of jitcheck.

The dynamic checker (runtime/jitcheck.py) sees only the traces a run
actually performs; this pass sees every lexical path.  It scans
`auron_tpu/` source (AST, no execution of scanned code) and

1. errors on RAW ``jax.jit`` constructions (direct calls,
   ``functools.partial(jax.jit, ...)``, ``@jax.jit`` decorators) that
   bypass the named jit-site registry — the registry is what makes
   compile counts exhaustive rather than advisory;
2. resolves every registered JIT BODY (the function a site wraps: the
   ``cached_jit`` builder's returned inner function, the ``site().jit``
   operand, the ``jax.shard_map`` program) and walks its bounded call
   closure (the PR 8 resolution rules) for HOST-MATERIALIZATION calls —
   ``.item()``, ``bool()/int()/float()`` on traced values,
   ``np.asarray``, ``.block_until_ready()``, ``jax.device_get``,
   ``host_sync`` — which inside a traced body either crash at trace
   time or, worse, silently constant-fold host state into the compiled
   program.  Deliberate sites carry a ``# jitcheck: waive`` comment;
3. flags jit bodies whose free names resolve to MUTABLE module state
   (a module global rebound more than once, or the target of a
   ``global`` statement): the closure bakes the value at trace time
   and never sees updates — the stale-compile bug class;
4. cross-checks every literal ``conf.get/set/unset``/``conf.scoped``
   key against the registered option set and CONFIG.md — unknown keys
   (literal typos fail at runtime, on the path that reads them),
   undocumented registered knobs (stale CONFIG.md) and documented-but-
   unregistered knobs (dead doc rows) are all diagnostics.

The committed golden is the COMPILE MANIFEST
(tests/golden_plans/compile_manifest.txt): per-site (distinct
signatures, compiles) from a canonical q01+q03 run, regen via
``python -m auron_tpu.analysis --compilation --regen-golden`` — an
accidental new recompile path fails CI by site name instead of by
latency.
"""

from __future__ import annotations

import ast
import difflib
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from auron_tpu.analysis.diagnostics import AnalysisResult, DiagnosticSink
# the PR 8 resolution stoplist: generic bare names must not resolve by
# package-unique fallback (a `run`/`build` hit fabricates closure paths)
from auron_tpu.analysis.concurrency import GENERIC_NAMES

PASS_ID = "compilation"

# files allowed to construct raw jax.jit (the checker's own factory)
RAW_JIT_ALLOWLIST = ("runtime/jitcheck.py",)

WAIVE_COMMENT = "jitcheck: waive"

MAX_CLOSURE_DEPTH = 8

# numpy module aliases for the asarray/array materialization check
_NUMPY_ALIASES = ("np", "numpy")


@dataclass
class JitBody:
    """One resolved jit root: the Python function a site traces."""
    site: str                 # registry site name ('' when unresolvable)
    module: str               # repo-relative path of the JIT SITE
    line: int                 # construction-site line
    node: ast.AST             # FunctionDef / Lambda of the traced body
    kind: str                 # cached_jit | site-jit | decorator
    owner: Any = None         # _ModuleScan DEFINING the body (fixed up
    #                           post-scan: imported builders live in
    #                           another module than their jit site)


@dataclass
class CompilationReport:
    jit_sites: List[JitBody] = field(default_factory=list)
    raw_jits: List[Tuple[str, int]] = field(default_factory=list)
    conf_keys_checked: int = 0
    result: AnalysisResult = field(default_factory=AnalysisResult)


def _line_has_waiver(src_lines: List[str], lineno: int) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(src_lines) and WAIVE_COMMENT in src_lines[ln - 1]:
            return True
    return False


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax")


def _is_site_jit_attr(node: ast.AST) -> bool:
    """`<expr>.jit` where <expr> is a jitcheck.site(...) call or a name
    bound to one (the bench_site pattern)."""
    if not (isinstance(node, ast.Attribute) and node.attr == "jit"):
        return False
    v = node.value
    if isinstance(v, ast.Call):
        f = v.func
        if isinstance(f, ast.Attribute) and f.attr == "site":
            return True
        if isinstance(f, ast.Name) and f.id == "site":
            return True
    return isinstance(v, ast.Name)   # resolved against site-bound names


# ---------------------------------------------------------------------------
# per-module scan: jit constructions, conf keys, lexical function scopes
# ---------------------------------------------------------------------------

class _ModuleScan:
    def __init__(self, rel: str, tree: ast.Module, src_lines: List[str]):
        self.rel = rel
        self.tree = tree
        self.src_lines = src_lines
        # package-wide module-level defs {bare name: [def nodes]} —
        # assigned before scan() so imported builders resolve
        self.package_defs: Dict[str, List[ast.AST]] = {}
        self.raw_jits: List[Tuple[int, bool]] = []        # (line, waived)
        self.jit_bodies: List[JitBody] = []
        self.conf_key_sites: List[Tuple[str, int]] = []   # (key, line)
        self.site_vars: Set[str] = set()      # names bound to site(...)
        self.module_assign_counts: Dict[str, int] = {}
        self.global_decls: Set[str] = set()

    # -- module-level mutability --------------------------------------------

    def _scan_module_state(self) -> None:
        for stmt in self.tree.body:
            targets: List[ast.AST] = []
            if isinstance(stmt, ast.Assign):
                targets = stmt.targets
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)) and \
                    stmt.value is not None:
                targets = [stmt.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    self.module_assign_counts[t.id] = \
                        self.module_assign_counts.get(t.id, 0) + 1
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Global):
                self.global_decls.update(node.names)

    # -- the walk ------------------------------------------------------------

    def scan(self) -> None:
        self._scan_module_state()
        self._walk(self.tree, scopes=())

    def _walk(self, node: ast.AST, scopes: Tuple[ast.AST, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_decorators(child, scopes)
                self._walk(child, scopes + (child,))
            elif isinstance(child, ast.Assign) and \
                    self._is_site_call(child.value):
                for t in child.targets:
                    if isinstance(t, ast.Name):
                        self.site_vars.add(t.id)
                self._walk(child, scopes)
            else:
                if isinstance(child, ast.Call):
                    self._scan_call(child, scopes)
                self._walk(child, scopes)

    @staticmethod
    def _is_site_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        return (isinstance(f, ast.Attribute) and f.attr == "site") or \
            (isinstance(f, ast.Name) and f.id == "site")

    def _scan_decorators(self, fn: ast.FunctionDef,
                         scopes: Tuple[ast.AST, ...]) -> None:
        for dec in fn.decorator_list:
            if _is_jax_jit(dec):
                self._note_raw_jit(dec.lineno)
            elif isinstance(dec, ast.Call):
                if _is_jax_jit(dec.func):
                    self._note_raw_jit(dec.lineno)
                # functools.partial(<factory>, ...) decorator form
                elif isinstance(dec.func, ast.Attribute) and \
                        dec.func.attr == "partial" and dec.args:
                    head = dec.args[0]
                    if _is_jax_jit(head):
                        self._note_raw_jit(dec.lineno)
                    elif isinstance(head, ast.Attribute) and \
                            _is_site_jit_attr(head):
                        self.jit_bodies.append(JitBody(
                            site=self._site_name_of(head), module=self.rel,
                            line=dec.lineno, node=fn, kind="decorator"))

    def _note_raw_jit(self, line: int) -> None:
        waived = any(self.rel.endswith(p) for p in RAW_JIT_ALLOWLIST) or \
            _line_has_waiver(self.src_lines, line)
        self.raw_jits.append((line, waived))

    @staticmethod
    def _site_name_of(jit_attr: ast.Attribute) -> str:
        v = jit_attr.value
        if isinstance(v, ast.Call) and v.args:
            name = _const_str(v.args[0])
            if name:
                return name
        return "?"

    def _scan_call(self, node: ast.Call,
                   scopes: Tuple[ast.AST, ...]) -> None:
        f = node.func
        # raw jax.jit(...) / functools.partial(jax.jit, ...)
        if _is_jax_jit(f):
            self._note_raw_jit(node.lineno)
        if isinstance(f, ast.Attribute) and f.attr == "partial" and \
                node.args and _is_jax_jit(node.args[0]):
            self._note_raw_jit(node.lineno)
        # <site>.jit(fn, ...)
        if isinstance(f, ast.Attribute) and f.attr == "jit" and \
                _is_site_jit_attr(f):
            base = f.value
            named = isinstance(base, ast.Call) or (
                isinstance(base, ast.Name) and base.id in self.site_vars)
            if named and node.args:
                body = self._resolve_fn_expr(node.args[0], scopes)
                if body is not None:
                    self.jit_bodies.append(JitBody(
                        site=self._site_name_of(f), module=self.rel,
                        line=node.lineno, node=body, kind="site-jit"))
        # cached_jit(key, builder, ...)
        if ((isinstance(f, ast.Name) and f.id == "cached_jit") or
                (isinstance(f, ast.Attribute) and f.attr == "cached_jit")) \
                and len(node.args) >= 2:
            key_expr, builder = node.args[0], node.args[1]
            fam = _const_str(key_expr)
            if fam is None and isinstance(key_expr, ast.Tuple) and \
                    key_expr.elts:
                fam = _const_str(key_expr.elts[0])
            body = self._resolve_builder(builder, scopes)
            if body is not None:
                self.jit_bodies.append(JitBody(
                    site=fam or "?", module=self.rel, line=node.lineno,
                    node=body, kind="cached_jit"))
        # conf.<get|set|unset>("literal") / conf.scoped({...})
        if isinstance(f, ast.Attribute) and \
                f.attr in ("get", "set", "unset") and node.args:
            v = f.value
            is_conf = (isinstance(v, ast.Name) and v.id in
                       ("conf", "_conf")) or \
                (isinstance(v, ast.Attribute) and v.attr == "conf")
            if is_conf:
                key = _const_str(node.args[0])
                if key is not None and key.startswith("auron."):
                    self.conf_key_sites.append((key, node.lineno))
        if isinstance(f, ast.Attribute) and \
                f.attr in ("scoped", "query_scoped") and node.args:
            d = node.args[0]
            if isinstance(d, ast.Dict):
                for k in d.keys:
                    key = _const_str(k) if k is not None else None
                    if key is not None and key.startswith("auron."):
                        self.conf_key_sites.append((key, d.lineno))

    # -- lexical function resolution ----------------------------------------

    def _lookup_def(self, name: str, scopes: Tuple[ast.AST, ...]
                    ) -> Optional[ast.AST]:
        """Innermost-first lexical lookup of a FunctionDef named `name`
        (anywhere in the enclosing function bodies — defs nested under
        `if` arms included — then module level)."""
        for scope in tuple(reversed(scopes)) + (self.tree,):
            for stmt in ast.walk(scope):
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        stmt.name == name and stmt is not scope:
                    return stmt
        # imported builder: package-unique module-level def (stoplisted)
        if name not in GENERIC_NAMES:
            cands = self.package_defs.get(name, [])
            if len(cands) == 1:
                return cands[0]
        return None

    def _resolve_fn_expr(self, expr: ast.AST, scopes: Tuple[ast.AST, ...]
                         ) -> Optional[ast.AST]:
        """The traced-body node of a site.jit operand: a def, a lambda,
        or the program inside jax.shard_map(program, ...)."""
        if isinstance(expr, ast.Lambda):
            return expr
        if isinstance(expr, ast.Name):
            return self._lookup_def(expr.id, scopes)
        if isinstance(expr, ast.Call):
            f = expr.func
            if isinstance(f, ast.Attribute) and f.attr == "shard_map" \
                    and expr.args:
                return self._resolve_fn_expr(expr.args[0], scopes)
        return None

    def _resolve_builder(self, expr: ast.AST, scopes: Tuple[ast.AST, ...],
                         depth: int = 0) -> Optional[ast.AST]:
        """cached_jit builder -> the inner function it returns."""
        if depth > 4:
            return None
        if isinstance(expr, ast.Lambda):
            # `lambda: _build_x(...)` => the built function's body
            if isinstance(expr.body, ast.Call):
                return self._resolve_builder(expr.body.func, scopes,
                                             depth + 1)
            return None
        if isinstance(expr, ast.Name):
            d = self._lookup_def(expr.id, scopes)
            if d is None:
                return None
            return self._returned_fn(d, scopes, depth)
        if isinstance(expr, ast.Attribute):
            d = self._lookup_def(expr.attr, scopes)
            if d is not None:
                return self._returned_fn(d, scopes, depth)
        return None

    def _returned_fn(self, builder: ast.AST, scopes: Tuple[ast.AST, ...],
                     depth: int) -> Optional[ast.AST]:
        """The function object a builder def returns (its jit body)."""
        nested = {s.name: s for s in getattr(builder, "body", ())
                  if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for stmt in ast.walk(builder):
            if isinstance(stmt, ast.Return) and stmt.value is not None:
                v = stmt.value
                if isinstance(v, ast.Name) and v.id in nested:
                    return nested[v.id]
                if isinstance(v, ast.Lambda):
                    return v
                if isinstance(v, ast.Call):
                    return self._resolve_builder(v.func,
                                                 scopes + (builder,),
                                                 depth + 1)
        return None


# ---------------------------------------------------------------------------
# host-materialization + taint walks over jit bodies
# ---------------------------------------------------------------------------

def _materialization_kind(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "item":
            return "item()"
        if f.attr == "block_until_ready":
            return "block_until_ready()"
        if f.attr in ("asarray", "array") and \
                isinstance(f.value, ast.Name) and \
                f.value.id in _NUMPY_ALIASES:
            return f"np.{f.attr}"
        if f.attr == "device_get":
            return "jax.device_get"
        if f.attr == "host_sync":
            return "host_sync"
    if isinstance(f, ast.Name) and f.id == "host_sync":
        return "host_sync"
    return None


def _param_cast_hits(body: ast.AST) -> List[Tuple[str, int]]:
    """Direct bool()/int()/float() casts of the jit body's OWN
    parameters — the 'Python branch on a traced value' class.  Only
    depth-0 and only parameter names: casts of static closure ints
    deeper in the call chain are trace-safe shape math (and a cast of a
    genuinely traced value crashes loudly at trace time regardless —
    the static check exists to fail in CI before any run)."""
    args = getattr(body, "args", None)
    if args is None:
        return []
    params = {a.arg for a in
              (args.posonlyargs + args.args + args.kwonlyargs)}
    out: List[Tuple[str, int]] = []
    for node in ast.walk(body):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id in ("bool", "int", "float") and \
                len(node.args) == 1 and \
                isinstance(node.args[0], ast.Name) and \
                node.args[0].id in params:
            out.append((f"{node.func.id}({node.args[0].id})",
                        node.lineno))
    return out


def _local_names(fn: ast.AST) -> Set[str]:
    """Names bound inside a function body (params, assignments,
    comprehension targets, nested defs, imports)."""
    out: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            out.add(a.arg)
        if args.vararg:
            out.add(args.vararg.arg)
        if args.kwarg:
            out.add(args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and \
                isinstance(node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(node.name)
        elif isinstance(node, ast.comprehension):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


class _BodyAnalysis:
    """Bounded call-closure walks rooted at jit bodies, resolved with
    the same-module/lexical rules (a subset of PR 8's resolution: the
    jit bodies' helper calls are overwhelmingly same-module)."""

    def __init__(self, scans: List[_ModuleScan]):
        self.scans = scans
        self.by_module: Dict[str, _ModuleScan] = {s.rel: s for s in scans}
        # bare name -> [(scan, def node)] over module-level defs AND
        # class methods (`spec.merge_segments(...)` must resolve into
        # the AggSpec implementations or the taint walk goes blind)
        self.module_defs: Dict[str, List[Tuple[_ModuleScan, ast.AST]]] = {}
        for s in scans:
            for stmt in s.tree.body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    self.module_defs.setdefault(stmt.name, []).append(
                        (s, stmt))
                elif isinstance(stmt, ast.ClassDef):
                    for m in stmt.body:
                        if isinstance(m, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                            self.module_defs.setdefault(
                                m.name, []).append((s, m))

    def _resolve(self, scan: _ModuleScan, node: ast.Call
                 ) -> Optional[Tuple[_ModuleScan, ast.AST]]:
        f = node.func
        name = None
        if isinstance(f, ast.Name):
            name = f.id
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name):
            name = f.attr
        if name is None:
            return None
        # same-module first, then package-unique bare name (gated by
        # the GENERIC_NAMES stoplist so `x.get(...)`/`run(...)` never
        # fabricates a closure path into an unrelated module)
        for stmt in scan.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt.name == name:
                return (scan, stmt)
        if name in GENERIC_NAMES:
            return None
        cands = self.module_defs.get(name, [])
        if len(cands) == 1:
            return cands[0]
        return None

    def closure_hits(self, scan: _ModuleScan, root: ast.AST,
                     kind_of, depth: int = 0,
                     seen: Optional[Set[int]] = None
                     ) -> List[Tuple[str, str, int, bool]]:
        """(kind, module, line, waived) for matching calls reachable
        from `root` through the bounded closure."""
        if seen is None:
            seen = set()
        if depth > MAX_CLOSURE_DEPTH or id(root) in seen:
            return []
        seen.add(id(root))
        # a waive comment on the `def` line waives the whole helper
        # (the host-column fallback functions: lexically inside traced
        # bodies, dynamically dead on the all-device traced path)
        if isinstance(root, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and depth > 0 and \
                _line_has_waiver(scan.src_lines, root.lineno):
            return []
        out: List[Tuple[str, str, int, bool]] = []
        for node in ast.walk(root):
            if not isinstance(node, ast.Call):
                continue
            kind = kind_of(node)
            if kind is not None:
                out.append((kind, scan.rel, node.lineno,
                            _line_has_waiver(scan.src_lines,
                                             node.lineno)))
                continue
            hit = self._resolve(scan, node)
            if hit is not None:
                s2, d2 = hit
                out.extend(self.closure_hits(s2, d2, kind_of, depth + 1,
                                             seen))
        return out


# ---------------------------------------------------------------------------
# config-knob lint
# ---------------------------------------------------------------------------

def _registered_conf_keys() -> Set[str]:
    from auron_tpu.config import conf
    return set(conf._options.keys())


def _config_md_keys(repo_root: str) -> Optional[Set[str]]:
    path = os.path.join(repo_root, "CONFIG.md")
    if not os.path.exists(path):
        return None
    keys: Set[str] = set()
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line.startswith("| `"):
                end = line.find("`", 3)
                if end > 3:
                    keys.add(line[3:end])
    return keys


# ---------------------------------------------------------------------------
# whole-package analysis
# ---------------------------------------------------------------------------

def analyze_compilation(root: Optional[str] = None,
                        repo_root: Optional[str] = None
                        ) -> CompilationReport:
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root is None:
        repo_root = os.path.dirname(root)
    scans: List[_ModuleScan] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path) as fh:
                src = fh.read()
            try:
                tree = ast.parse(src, filename=rel)
            except SyntaxError:
                continue   # ruff's department
            scans.append(_ModuleScan(rel, tree, src.splitlines()))
    # two phases: the package-wide def index must exist before any
    # module resolves its jit bodies (builders are often imported —
    # joins/exec.py jits kernels defined in joins/kernel.py)
    package_defs: Dict[str, List[ast.AST]] = {}
    for scan in scans:
        for stmt in scan.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                package_defs.setdefault(stmt.name, []).append(stmt)
    for scan in scans:
        scan.package_defs = package_defs
        scan.scan()
    # a resolved body may live in ANOTHER module than its jit site
    # (imported builder): closure walks and waiver comments must use
    # the DEFINING module's scan
    node_owner: Dict[int, _ModuleScan] = {}
    for scan in scans:
        for node in ast.walk(scan.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                node_owner[id(node)] = scan
    for scan in scans:
        for body in scan.jit_bodies:
            body.owner = node_owner.get(id(body.node), scan)

    report = CompilationReport()
    sink = DiagnosticSink()
    bodies = _BodyAnalysis(scans)

    for scan in scans:
        # 1. raw jax.jit constructions
        for line, waived in scan.raw_jits:
            report.raw_jits.append((scan.rel, line))
            if not waived:
                sink.error(PASS_ID, f"{scan.rel}:{line}", None,
                           "raw jax.jit construction bypasses the "
                           "jit-site registry",
                           hint="route through jitcheck.site(name).jit "
                                "(or cached_jit for kernel families)")

        for body in scan.jit_bodies:
            report.jit_sites.append(body)
            bscan = body.owner or scan
            # 2. host materialization inside the traced body
            for kind, where, line, waived in bodies.closure_hits(
                    bscan, body.node, _materialization_kind):
                if waived:
                    continue
                sink.error(
                    PASS_ID, f"{where}:{line}", None,
                    f"host-materialization {kind} reachable inside "
                    f"jitted body of site {body.site!r} "
                    f"({body.module}:{body.line}) — traced values "
                    f"crash here, closure constants silently bake in",
                    hint="hoist the host work outside the traced "
                         "body, or annotate the line with "
                         "'# jitcheck: waive (<reason>)'")
            for kind, line in _param_cast_hits(body.node):
                if _line_has_waiver(bscan.src_lines, line):
                    continue
                sink.error(
                    PASS_ID, f"{bscan.rel}:{line}", None,
                    f"{kind} inside jitted body of site "
                    f"{body.site!r}: a Python cast of a traced "
                    f"parameter branches on its VALUE at trace time",
                    hint="use jnp.where / lax.cond on the traced "
                         "value, or annotate with '# jitcheck: waive "
                         "(<reason>)' if the parameter is static")
            # 3. mutable-module-state capture
            local = _local_names(body.node)
            for node in ast.walk(body.node):
                if not (isinstance(node, ast.Name) and
                        isinstance(node.ctx, ast.Load)):
                    continue
                if node.id in local:
                    continue
                mutable = bscan.module_assign_counts.get(node.id, 0) > 1 \
                    or node.id in bscan.global_decls
                if mutable and not _line_has_waiver(bscan.src_lines,
                                                    node.lineno):
                    sink.error(
                        PASS_ID, f"{bscan.rel}:{node.lineno}", None,
                        f"jitted body of site {body.site!r} captures "
                        f"mutable module state {node.id!r}: the value "
                        f"bakes in at trace time and updates are "
                        f"never seen",
                        hint="pass the value as an argument (part of "
                             "the signature) or into the cache key; "
                             "'# jitcheck: waive (<reason>)' if the "
                             "rebinding is init-only")

    # 4. config-knob lint
    registered = _registered_conf_keys()
    doc_keys = _config_md_keys(repo_root)
    for scan in scans:
        for key, line in scan.conf_key_sites:
            report.conf_keys_checked += 1
            if key not in registered:
                close = difflib.get_close_matches(key, registered, n=1)
                hint = f"did you mean {close[0]!r}?" if close else \
                    "register it with conf.define(...)"
                sink.error(PASS_ID, f"{scan.rel}:{line}", None,
                           f"unknown config key {key!r} (literal typo "
                           f"or unregistered option: this raises "
                           f"KeyError on the path that reads it)",
                           hint=hint)
    if doc_keys is not None:
        for key in sorted(registered - doc_keys):
            sink.error(PASS_ID, "CONFIG.md", None,
                       f"registered option {key!r} missing from "
                       f"CONFIG.md",
                       hint="regen: python -m auron_tpu.config > "
                            "CONFIG.md")
        for key in sorted(doc_keys - registered):
            sink.error(PASS_ID, "CONFIG.md", None,
                       f"documented knob {key!r} is not registered "
                       f"(dead doc row)",
                       hint="remove the row or restore the option; "
                            "regen: python -m auron_tpu.config > "
                            "CONFIG.md")

    report.result = AnalysisResult(diagnostics=sink.diagnostics)
    return report


# ---------------------------------------------------------------------------
# compile manifest golden (tests/golden_plans/compile_manifest.txt)
# ---------------------------------------------------------------------------

MANIFEST_HEADER = (
    "# Compile manifest over the canonical q01+q03 run (sf=0.002,\n"
    "# fact_chunks=3, CPU backend): per jit site, the DISTINCT abstract\n"
    "# signatures and total traces a cold run performs — q01+q03 on the\n"
    "# default single-device stage path (one spmd.stage program per\n"
    "# query), then q01 again with the stage compiler off so the serial\n"
    "# fragment/kernel families compile too.  An accidental new\n"
    "# recompile path fails CI here BY SITE NAME instead of by latency.\n"
    "# Regenerate: python -m auron_tpu.analysis --compilation\n"
    "# --regen-golden\n")

CANONICAL_QUERIES = ("q01", "q03")
CANONICAL_SERIAL_QUERIES = ("q01",)
CANONICAL_SF = 0.002


def manifest_path() -> str:
    env = os.environ.get("AURON_GOLDEN_PLANS")
    base = env or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "tests", "golden_plans")
    return os.path.join(base, "compile_manifest.txt")


def reset_compile_state() -> None:
    """Drop every process-level compile cache so a manifest run counts
    from zero: the jitcheck registry, the kernel cache, the SPMD
    program/slicer caches and jax's own trace caches."""
    import jax

    from auron_tpu.ops import kernel_cache
    from auron_tpu.parallel import stage
    from auron_tpu.runtime import jitcheck

    kernel_cache.clear()
    stage._PROGRAM_CACHE.clear()
    stage._SLICER_CACHE.clear()
    jax.clear_caches()
    jitcheck.reset_state()


def collect_compile_manifest(data_dir: Optional[str] = None
                             ) -> Dict[str, Tuple[int, int]]:
    """Run the canonical corpus queries cold and snapshot the jitcheck
    registry.  Requires jitcheck enabled (the CLI and the test suite
    both force it)."""
    import tempfile

    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries as Q
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    from auron_tpu.runtime import jitcheck

    from auron_tpu.config import conf

    if data_dir is None:
        data_dir = os.path.join(tempfile.gettempdir(),
                                "auron_tpcds_manifest")
    cat = generate(data_dir, sf=CANONICAL_SF, fact_chunks=3)
    reset_compile_state()
    for name in CANONICAL_QUERIES:
        plan = Q.build(name, cat)
        AuronSession(foreign_engine=PyArrowEngine()).execute(plan)
    # the serial per-batch walk is the stage path's fallback shape:
    # run it too so the fragment/kernel families are in the manifest
    with conf.scoped({"auron.spmd.singleDevice.enable": False}):
        for name in CANONICAL_SERIAL_QUERIES:
            plan = Q.build(name, cat)
            AuronSession(foreign_engine=PyArrowEngine()).execute(plan)
    return jitcheck.manifest_snapshot()


def render_manifest(snapshot: Dict[str, Tuple[int, int]]) -> str:
    lines = [MANIFEST_HEADER.rstrip()]
    total_sigs = total_compiles = 0
    for site in sorted(snapshot):
        sigs, compiles = snapshot[site]
        total_sigs += sigs
        total_compiles += compiles
        lines.append(f"site {site} signatures={sigs} compiles={compiles}")
    lines.append(f"total signatures={total_sigs} "
                 f"compiles={total_compiles}")
    return "\n".join(lines) + "\n"


def parse_manifest(text: str) -> Dict[str, Tuple[int, int]]:
    out: Dict[str, Tuple[int, int]] = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or line.startswith("total "):
            continue
        parts = line.split()
        if len(parts) == 4 and parts[0] == "site":
            kv = {}
            for p in parts[2:]:
                name, _, val = p.partition("=")
                kv[name] = val
            out[parts[1]] = (int(kv.get("signatures", 0)),
                             int(kv.get("compiles", 0)))
    return out


def check_manifest(snapshot: Dict[str, Tuple[int, int]],
                   path: Optional[str] = None) -> List[str]:
    """Mismatch descriptions ([] = clean), with a regen hint — exactly
    like the plan goldens and the lock-order graph."""
    path = path or manifest_path()
    if not os.path.exists(path):
        return [f"missing compile manifest {path} (regen: python -m "
                f"auron_tpu.analysis --compilation --regen-golden)"]
    with open(path) as fh:
        golden = parse_manifest(fh.read())
    problems: List[str] = []
    for s in sorted(set(snapshot) - set(golden)):
        problems.append(f"site {s!r} compiles now ({snapshot[s][1]} "
                        f"traces) but is not in the manifest — a new "
                        f"compile path")
    for s in sorted(set(golden) - set(snapshot)):
        problems.append(f"manifest site {s!r} no longer compiles")
    for s in sorted(set(golden) & set(snapshot)):
        if golden[s] != snapshot[s]:
            problems.append(
                f"site {s!r} drifted: manifest signatures="
                f"{golden[s][0]} compiles={golden[s][1]} vs run "
                f"signatures={snapshot[s][0]} compiles="
                f"{snapshot[s][1]}")
    if problems:
        problems.append("regen: python -m auron_tpu.analysis "
                        "--compilation --regen-golden")
    return problems
