"""Knob analyzers: trace-time binding lint + declaration/documentation lint.

**knob-binding** — the subtle bug class: an ``os.environ`` / ``IGG_*`` read
executed *inside* a ``jit``/``shard_map``/Pallas-traced function runs at
TRACE time, so its value is baked into the cached executable; flipping the
env var later silently does nothing because the jit cache key never sees
it.  The pass builds an approximate intra-package call graph from the AST,
marks *trace roots* (functions handed to ``shard_map``/``pallas_call``/
``jit``/control-flow combinators, or decorated with them), and flags every
call edge that crosses from trace-reachable code into an env-reading
function.  Call resolution is name- and import-alias-based (documented
approximation: method dispatch and higher-order callables are not
followed), which is exactly enough for this package's idiom of nested
``def body(...)`` closures handed to ``shard_map``.

**knob-decl** — the discoverability lint from ``scripts/check_knobs.py``
(PR 4): every ``IGG_*`` referenced in the package must be declared in
``utils/config.py`` and documented in ``docs/usage.md``.  The script is now
a thin CLI wrapper over this module.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

from .core import Context, Finding

#: Callees whose function-valued arguments are traced.  ``grad``/
#: ``make_jaxpr``/``eval_shape`` trace too — an env read under any of
#: these binds at trace time.
TRACE_CALLEES = frozenset(
    {
        "jit",
        "shard_map",
        "pallas_call",
        "stencil",
        "fori_loop",
        "while_loop",
        "scan",
        "cond",
        "switch",
        "checkpoint",
        "remat",
        "custom_vjp",
        "custom_jvp",
        "vmap",
        "pmap",
        "grad",
        "value_and_grad",
        "make_jaxpr",
        "eval_shape",
        # package-local combinators that call their arguments inside an
        # enclosing trace (the fused group schedules), and the binding of a
        # kernel family's hooks, which the fused cadence calls in its trace
        # (`models._fused.Family.bind`)
        "run_group_schedule",
        "run_pipelined_group_schedule",
        "bind",
    }
)

#: Decorators that make the decorated function a trace root.
TRACE_DECORATORS = frozenset({"jit", "stencil", "custom_vjp", "custom_jvp"})

_KNOB = re.compile(r"IGG_[A-Z0-9_]+")


@dataclass
class _Func:
    """One function definition in the package."""

    module: str                 # repo-relative path
    qualname: str
    lineno: int
    calls: list = field(default_factory=list)   # (target_key, lineno, name)
    env_reads: list = field(default_factory=list)  # (lineno, knob-or-"")
    is_root: bool = False

    @property
    def key(self):
        return (self.module, self.qualname)


def _last_attr(node) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call):
        return _last_attr(node.func)
    return ""


class _ModuleIndexer(ast.NodeVisitor):
    """Collect functions, call edges, env reads and trace roots of one
    module.  Call targets are recorded as unresolved ``("local", name)`` /
    ``("import", alias, attr)`` keys; `_CallGraph` resolves them."""

    def __init__(self, rel: str):
        self.rel = rel
        self.funcs: dict[str, _Func] = {}
        self.stack: list[str] = []
        # import maps: alias -> module path ("a.b.c"), and
        # from-imports: name -> (module path, original name)
        self.mod_alias: dict[str, str] = {}
        self.from_imports: dict[str, tuple[str, str]] = {}
        self.local_names: set[str] = set()

    # -- imports ---------------------------------------------------------

    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.mod_alias[a.asname or a.name.split(".")[0]] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom):
        # resolve relative imports against this module's package path
        pkg_parts = self.rel.replace("/", ".").rsplit(".py", 1)[0].split(".")
        if node.level:
            base = pkg_parts[: -node.level]
        else:
            base = []
        mod = ".".join(base + (node.module.split(".") if node.module else []))
        for a in node.names:
            self.from_imports[a.asname or a.name] = (mod, a.name)

    # -- functions -------------------------------------------------------

    def _qual(self, name: str) -> str:
        return ".".join(self.stack + [name])

    def _cur(self) -> _Func | None:
        if not self.stack:
            return None
        return self.funcs.get(".".join(self.stack))

    def _visit_funcdef(self, node):
        qual = self._qual(node.name)
        fn = _Func(module=self.rel, qualname=qual, lineno=node.lineno)
        self.funcs[qual] = fn
        self.local_names.add(node.name)
        for dec in node.decorator_list:
            if _last_attr(dec) in TRACE_DECORATORS:
                fn.is_root = True
            # functools.partial(jax.jit, ...) and friends
            if isinstance(dec, ast.Call) and any(
                _last_attr(a) in TRACE_DECORATORS for a in dec.args
            ):
                fn.is_root = True
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_funcdef
    visit_AsyncFunctionDef = _visit_funcdef

    # -- reads + calls ---------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute):
        # os.environ in any expression position is an env read
        if (
            node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os"
        ):
            cur = self._cur()
            if cur is not None:
                cur.env_reads.append((node.lineno, ""))
        self.generic_visit(node)

    def _environ_get(self, node: ast.Call) -> bool:
        """``os.environ.get("X")``: record the knob constant and skip the
        func subtree so `visit_Attribute` does not double-count the read."""
        f = node.func
        if not (
            isinstance(f, ast.Attribute)
            and f.attr == "get"
            and isinstance(f.value, ast.Attribute)
            and f.value.attr == "environ"
            and isinstance(f.value.value, ast.Name)
            and f.value.value.id == "os"
        ):
            return False
        cur = self._cur()
        if cur is not None:
            knob = ""
            if node.args and isinstance(node.args[0], ast.Constant):
                knob = str(node.args[0].value)
            cur.env_reads.append((node.lineno, knob))
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            self.visit(arg)
        return True

    def visit_Call(self, node: ast.Call):
        if self._environ_get(node):
            return
        cur = self._cur()
        name = _last_attr(node.func)
        if cur is not None:
            if name == "getenv":
                knob = ""
                if node.args and isinstance(node.args[0], ast.Constant):
                    knob = str(node.args[0].value)
                cur.env_reads.append((node.lineno, knob))
            else:
                target = self._call_target(node.func)
                if target is not None:
                    # constant first-arg knob names ride along so accessor
                    # calls like _int_env("IGG_DONATE") attribute the knob
                    knob = ""
                    if node.args and isinstance(node.args[0], ast.Constant):
                        if isinstance(node.args[0].value, str) and _KNOB.match(
                            node.args[0].value
                        ):
                            knob = node.args[0].value
                    cur.calls.append((target, node.lineno, name, knob))
        # any function handed to a tracing callee becomes a trace root
        if name in TRACE_CALLEES:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    self._mark_root(arg.id)
        self.generic_visit(node)

    def _call_target(self, func) -> tuple | None:
        if isinstance(func, ast.Name):
            if func.id in self.from_imports:
                mod, orig = self.from_imports[func.id]
                return ("import", mod, orig)
            return ("local", func.id)
        if isinstance(func, ast.Attribute) and isinstance(
            func.value, ast.Name
        ):
            base = func.value.id
            if base in self.from_imports:
                mod, orig = self.from_imports[base]
                return ("import", f"{mod}.{orig}", func.attr)
            if base in self.mod_alias:
                return ("import", self.mod_alias[base], func.attr)
        return None

    def _mark_root(self, name: str):
        # innermost function of that name visible from the current scope
        for depth in range(len(self.stack), -1, -1):
            qual = ".".join(self.stack[:depth] + [name])
            if qual in self.funcs:
                self.funcs[qual].is_root = True
                return


class _CallGraph:
    def __init__(self, ctx: Context):
        self.package_name = os.path.basename(ctx.package_root)
        self.modules: dict[str, _ModuleIndexer] = {}
        for rel, (_src, tree) in ctx.module_asts().items():
            idx = _ModuleIndexer(rel)
            idx.visit(tree)
            self.modules[rel] = idx
        # global indices
        self.funcs: dict[tuple, _Func] = {}
        self.by_module_and_name: dict[tuple, list[tuple]] = {}
        for rel, idx in self.modules.items():
            for qual, fn in idx.funcs.items():
                self.funcs[fn.key] = fn
                bare = qual.split(".")[-1]
                self.by_module_and_name.setdefault((rel, bare), []).append(
                    fn.key
                )

    def _module_rel(self, dotted: str) -> str | None:
        """``implicitglobalgrid_tpu.utils.config`` -> its repo-relative
        path, if the module is part of the scanned package."""
        parts = dotted.split(".")
        if not parts or parts[0] != self.package_name:
            # relative imports already resolved to full dotted paths that
            # start with the scanned package's directory name
            pass
        for cand in (
            "/".join(parts) + ".py",
            "/".join(parts) + "/__init__.py",
        ):
            if cand in self.modules:
                return cand
        return None

    def resolve(self, caller: _Func, target: tuple) -> list[tuple]:
        """Candidate callee keys for one recorded call target."""
        if target[0] == "local":
            name = target[1]
            idx = self.modules[caller.module]
            # innermost enclosing scope first, then module level
            parts = caller.qualname.split(".")
            for depth in range(len(parts), -1, -1):
                qual = ".".join(parts[:depth] + [name])
                if qual in idx.funcs:
                    return [(caller.module, qual)]
            return []
        _, mod, name = target
        rel = self._module_rel(mod)
        if rel is None:
            return []
        return self.by_module_and_name.get((rel, name), [])

    def trace_roots(self) -> list[tuple]:
        return [k for k, f in self.funcs.items() if f.is_root]


def _direct_readers(graph: _CallGraph) -> dict[tuple, list]:
    return {
        k: f.env_reads for k, f in graph.funcs.items() if f.env_reads
    }


def _transitive_readers(graph: _CallGraph) -> dict[tuple, set[str]]:
    """``func key -> set of knob names`` for every function that reads env
    directly or through calls.  Knob names come from constant reads and
    from constant first args passed into reader calls (the accessor idiom
    ``_int_env("IGG_DONATE")``)."""
    readers: dict[tuple, set[str]] = {
        k: {kn for _, kn in reads if kn} or {""}
        for k, reads in _direct_readers(graph).items()
    }
    changed = True
    while changed:
        changed = False
        for key, fn in graph.funcs.items():
            for target, _ln, _name, knob in fn.calls:
                for callee in graph.resolve(fn, target):
                    if callee in readers:
                        knobs = set(readers[callee])
                        if knob:
                            knobs = {knob} | (knobs - {""})
                        cur = readers.setdefault(key, set())
                        if not knobs <= cur:
                            cur |= knobs
                            changed = True
    return readers


def run_knob_binding(ctx: Context) -> list[Finding]:
    """One finding PER KNOB reachable from traced code.

    BFS from the trace roots over the call graph; every edge that crosses
    from non-reading code into the env-reading closure is a "crossing",
    attributed to the knob(s) it binds.  Findings aggregate all crossings
    of one knob (a knob read by five cadences is ONE decision to make:
    fix the binding or baseline the documented per-trace contract), with
    an example trace chain and the crossing count in the message.  The
    fingerprint hashes only the knob name, so a baseline entry survives
    any refactor of the paths that reach it.
    """
    graph = _CallGraph(ctx)
    readers = _transitive_readers(graph)
    roots = graph.trace_roots()

    hits: dict[str, dict] = {}  # knob -> evidence

    def record(knob: str, chain, crossing_fn: _Func, lineno: int):
        h = hits.setdefault(
            knob,
            {"chain": None, "crossings": set(), "fn": crossing_fn,
             "line": lineno},
        )
        h["crossings"].add((crossing_fn.module, crossing_fn.qualname))
        if h["chain"] is None or len(chain) < len(h["chain"]):
            h["chain"] = chain
            h["fn"] = crossing_fn
            h["line"] = lineno

    seen = set(roots)
    frontier = list(roots)
    chains = {k: [k] for k in roots}
    while frontier:
        key = frontier.pop()
        fn = graph.funcs[key]
        # Crossing attribution happens at the first reader edge along a
        # chain: once a chain has passed THROUGH a reader, everything
        # deeper is that reader's internals (config accessors, telemetry
        # registry) and is already attributed by the crossing above it.
        entered_via_reader = any(k in readers for k in chains[key][:-1])
        if fn.env_reads and not entered_via_reader:
            for ln, knob in fn.env_reads:
                record(knob or f"os.environ@{fn.qualname}", chains[key], fn,
                       ln)
        for target, ln, name, knob in fn.calls:
            for callee in graph.resolve(fn, target):
                if callee in readers and not entered_via_reader:
                    # first edge into the reading closure: attribute knobs
                    cfn = graph.funcs[callee]
                    knobs = (
                        {knob} | (readers[callee] - {""})
                        if knob
                        else set(readers[callee])
                    )
                    for kn in knobs:
                        record(
                            kn or f"os.environ@{cfn.qualname}",
                            chains[key] + [callee],
                            cfn,
                            cfn.lineno,
                        )
                if callee not in seen:
                    seen.add(callee)
                    chains[callee] = chains[key] + [callee]
                    frontier.append(callee)

    out = []
    for knob in sorted(hits):
        h = hits[knob]
        fn: _Func = h["fn"]
        via = " -> ".join(q for _m, q in h["chain"])
        n = len(h["crossings"])
        out.append(
            Finding(
                analyzer="knob-binding",
                code="env-read-in-trace",
                severity="ERROR",
                message=(
                    f"{knob} is read inside traced code "
                    f"(`{fn.qualname}` at {fn.module}:{h['line']}, reached "
                    f"from {n} trace-reachable function(s); e.g. {via}): "
                    f"the value binds at TRACE time, so a cached jit "
                    f"executable silently ignores later changes to the "
                    f"knob."
                ),
                # path/symbol deliberately empty: the fingerprint must hash
                # the KNOB alone (anchor), so a baseline entry survives any
                # refactor of the functions that reach the read — the
                # reader's location lives in the message instead.
                anchor=knob,
                fix_hint=(
                    "resolve the knob host-side before entering "
                    "jit/shard_map and pass it as an argument (or bake it "
                    "into the jit cache key), or baseline it with a "
                    "justification if the per-trace binding is the "
                    "documented contract (utils/config.py)."
                ),
            )
        )
    return out


# -- knob-decl (scripts/check_knobs.py core) ----------------------------------


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as f:
        return f.read()


def referenced_knobs(repo: str, package: str, config: str) -> dict:
    """``knob -> [repo-relative files referencing it]`` over the package,
    excluding the declaration site (utils/config.py)."""
    refs: dict[str, list[str]] = {}
    for dirpath, dirnames, filenames in os.walk(package):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            if os.path.samefile(path, config):
                continue
            rel = os.path.relpath(path, repo)
            for knob in set(_KNOB.findall(_read(path))):
                refs.setdefault(knob, []).append(rel)
    return {k: sorted(v) for k, v in sorted(refs.items())}


def knob_decl_findings(repo: str, package: str, config: str,
                       usage: str) -> list[Finding]:
    declared = set(_KNOB.findall(_read(config)))
    documented = set(_KNOB.findall(_read(usage)))
    out = []
    for knob, files in referenced_knobs(repo, package, config).items():
        where = ", ".join(files)
        if knob not in declared:
            out.append(
                Finding(
                    analyzer="knob-decl",
                    code="undeclared-knob",
                    severity="ERROR",
                    message=(
                        f"{knob} (referenced in {where}) is not declared "
                        f"in implicitglobalgrid_tpu/utils/config.py"
                    ),
                    path=files[0],
                    symbol=knob,
                    anchor="declare",
                    fix_hint=(
                        "add it to the knob table in utils/config.py (and "
                        "an accessor if it is read per call)"
                    ),
                )
            )
        if knob not in documented:
            out.append(
                Finding(
                    analyzer="knob-decl",
                    code="undocumented-knob",
                    severity="ERROR",
                    message=(
                        f"{knob} (referenced in {where}) is not documented "
                        f"in docs/usage.md"
                    ),
                    path=files[0],
                    symbol=knob,
                    anchor="document",
                    fix_hint="add a row to the env-var table in docs/usage.md",
                )
            )
    return out


def run_knob_decl(ctx: Context) -> list[Finding]:
    return knob_decl_findings(
        repo=ctx.repo_root,
        package=ctx.package_root,
        config=os.path.join(ctx.package_root, "utils", "config.py"),
        usage=os.path.join(ctx.repo_root, "docs", "usage.md"),
    )
