"""Module-level call graph + jit-boundary inference (stdlib ``ast`` only).

The traced-code set is the load-bearing input to every GC1xx purity rule
and the severity escalation of the GC2xx determinism rules, so it is
computed once here and shared:

1. **Seeds** — functions that enter a JAX trace directly:
   ``jax.jit(f)`` / ``@jax.jit`` / ``@partial(jax.jit, ...)``,
   ``shard_map(f, ...)``, ``pl.pallas_call(kernel, ...)``,
   ``@jax.custom_vjp`` / ``@custom_jvp`` and ``f.defvjp(fwd, bwd)``,
   ``jax.grad``/``value_and_grad``/``vmap``/``pmap``/``checkpoint``/
   ``remat``, and ``jax.lax.{scan,while_loop,fori_loop,cond,map}``
   bodies.  Aliases are normalized through each module's import table,
   so ``from jax import shard_map`` and
   ``from jax.experimental import pallas as pl`` both resolve.
2. **Closure** — traced-ness propagates through resolved call edges
   (calling ``g()`` from traced ``f`` runs ``g`` at trace time) and
   through function *references* (passing ``loss_fn`` to
   ``value_and_grad`` inside a traced step).  Resolution is lexical
   (nested defs, skipping class scopes), then ``self.method`` within
   the innermost class, then module functions, then cross-module
   through ``from ..x import y`` / ``import x as m`` of analyzed
   modules.

The same graph answers determinism-reachability queries: given root
patterns (the step / checkpoint-replay / trace-export entry points),
``reachable_from`` returns every function on such a path plus which
root reaches it — that is what turns a GC201 wall-clock *warning* into
"this one backs a bit-identity gate".
"""

from __future__ import annotations

import ast
import fnmatch
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

# leaf callable names that trace their function-valued arguments.
# Bare-name matches are restricted to the unambiguous ones; generic leaves
# (scan, cond, ...) additionally need a jax-ish prefix to match.
_TRACER_LEAVES = {
    "jit", "pmap", "vmap", "grad", "value_and_grad", "shard_map",
    "pallas_call", "custom_vjp", "custom_jvp", "checkpoint", "remat",
    "scan", "while_loop", "fori_loop", "cond", "map", "associative_scan",
    "switch",
}
_BARE_OK = {"jit", "shard_map", "pallas_call", "custom_vjp", "custom_jvp",
            "value_and_grad"}


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for nested Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class FunctionInfo:
    __slots__ = ("qual", "node", "module", "class_name", "scope",
                 "calls", "refs", "traced_reason", "params")

    def __init__(self, qual: str, node: ast.AST, module: "ModuleInfo",
                 class_name: Optional[str], scope: Tuple[Tuple[str, str], ...]):
        self.qual = qual
        self.node = node
        self.module = module
        self.class_name = class_name
        self.scope = scope          # ((kind, name), ...) enclosing chain
        self.calls: Set[Tuple] = set()   # ("name", n) | ("self", m) | ("attr", base, leaf)
        self.refs: Set[str] = set()      # bare Name loads (potential fn refs)
        self.traced_reason: Optional[str] = None
        self.params: Set[str] = set()

    @property
    def gid(self) -> str:
        return f"{self.module.relpath}::{self.qual}"

    @property
    def line(self) -> int:
        return getattr(self.node, "lineno", 0)


class ModuleInfo:
    __slots__ = ("relpath", "modkey", "tree", "source", "lines",
                 "functions", "classes", "imports")

    def __init__(self, relpath: str, modkey: str, tree: ast.Module,
                 source: str):
        self.relpath = relpath      # repo-relative posix path
        self.modkey = modkey        # package-relative dotted, e.g. "nn.multilayer"
        self.tree = tree
        self.source = source
        self.lines = source.splitlines()
        self.functions: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, Set[str]] = {}       # class -> method names
        self.imports: Dict[str, Tuple] = {}  # alias -> ("module", key) | ("symbol", modkey, name)

    def normalize(self, dotted_name: str) -> str:
        """Rewrite a leading import alias to its target dotted path."""
        head, _, rest = dotted_name.partition(".")
        imp = self.imports.get(head)
        if imp is None:
            return dotted_name
        if imp[0] == "module":
            base = imp[1]
        else:
            base = f"{imp[1]}.{imp[2]}"
        return f"{base}.{rest}" if rest else base


def _resolve_relative(modkey: str, module: Optional[str], level: int) -> str:
    """'from ..ops import x' inside 'parallel.trainer' -> 'ops[.x]'."""
    if level == 0:
        return module or ""
    parts = modkey.split(".") if modkey else []
    # level 1 = current package (drop the module segment), each extra
    # level drops one more package
    base = parts[:-level] if level <= len(parts) else []
    if module:
        base = base + module.split(".")
    return ".".join(base)


class _Collector(ast.NodeVisitor):
    """One pass per module: functions, classes, imports, per-function
    call/ref edges, and trace seeds."""

    def __init__(self, mod: ModuleInfo, graph: "CallGraph"):
        self.mod = mod
        self.graph = graph
        self.stack: List[Tuple[str, str]] = []   # (kind, name)
        self.fn_stack: List[FunctionInfo] = []

    # -- imports -------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            alias = a.asname or a.name.split(".")[0]
            target = a.name if a.asname else a.name.split(".")[0]
            self.mod.imports[alias] = ("module", target)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        base = _resolve_relative(self.mod.modkey, node.module, node.level)
        for a in node.names:
            alias = a.asname or a.name
            self.mod.imports[alias] = ("symbol", base, a.name)

    # -- scopes --------------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.mod.classes.setdefault(node.name, set())
        self.stack.append(("class", node.name))
        self.generic_visit(node)
        self.stack.pop()

    def _qual(self, name: str) -> str:
        return ".".join([n for _, n in self.stack] + [name])

    def _enter_function(self, node) -> None:
        qual = self._qual(node.name)
        class_name = None
        for kind, name in reversed(self.stack):
            if kind == "class":
                class_name = name
                break
        fi = FunctionInfo(qual, node, self.mod, class_name,
                          tuple(self.stack))
        a = node.args
        for arg in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            fi.params.add(arg.arg)
        self.mod.functions[qual] = fi
        if self.stack and self.stack[-1][0] == "class":
            self.mod.classes[self.stack[-1][1]].add(node.name)
        # decorators are evaluated in the ENCLOSING scope
        for dec in node.decorator_list:
            self._check_decorator(dec, fi)
            self.visit(dec)
        self.stack.append(("func", node.name))
        self.fn_stack.append(fi)
        for child in ast.iter_child_nodes(node):
            if child in node.decorator_list:
                continue
            self.visit(child)
        self.fn_stack.pop()
        self.stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._enter_function(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._enter_function(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self.generic_visit(node)

    # -- trace seeds ---------------------------------------------------
    def _is_tracer(self, dotted_name: Optional[str]) -> bool:
        if not dotted_name:
            return False
        norm = self.mod.normalize(dotted_name)
        leaf = norm.split(".")[-1]
        if leaf not in _TRACER_LEAVES:
            return False
        prefix = norm.rsplit(".", 1)[0] if "." in norm else ""
        if prefix:
            return "jax" in prefix or "pallas" in prefix
        return leaf in _BARE_OK

    def _seed_arg(self, arg: ast.AST, reason: str) -> None:
        tgt = None
        if isinstance(arg, ast.Name):
            tgt = ("name", arg.id)
        elif isinstance(arg, ast.Attribute) and \
                isinstance(arg.value, ast.Name) and arg.value.id == "self":
            tgt = ("self", arg.attr)
        if tgt is None:
            return
        fn = self.fn_stack[-1] if self.fn_stack else None
        # defer: the target may live later in this module or in a module
        # not collected yet
        self.graph._pending_arg_seeds.append((self.mod, fn, tgt, reason))

    def _check_decorator(self, dec: ast.AST, fi: FunctionInfo) -> None:
        name = dotted(dec)
        if name is None and isinstance(dec, ast.Call):
            fname = dotted(dec.func)
            if fname and fname.split(".")[-1] == "partial" and dec.args:
                name = dotted(dec.args[0])
            else:
                name = fname
        if name and self._is_tracer(name):
            self.graph._seed(fi.gid,
                             f"@{name} at {self.mod.relpath}:{fi.line}")

    def visit_Call(self, node: ast.Call) -> None:
        fname = dotted(node.func)
        fn = self.fn_stack[-1] if self.fn_stack else None
        # record the call edge
        if fn is not None and fname:
            parts = fname.split(".")
            if len(parts) == 1:
                fn.calls.add(("name", parts[0]))
            elif parts[0] == "self" and len(parts) == 2:
                fn.calls.add(("self", parts[1]))
            elif len(parts) >= 2:
                fn.calls.add(("attr", parts[0], parts[-1]))
        # trace seeds: f.defvjp(fwd, bwd)
        if fname and fname.split(".")[-1] == "defvjp":
            for a in node.args:
                self._seed_arg(a, f"defvjp at {self.mod.relpath}:"
                                  f"{node.lineno}")
        # trace seeds: jit(f) / shard_map(f) / pallas_call(k) / grad(f)...
        seed_name = fname
        if fname and fname.split(".")[-1] == "partial" and node.args:
            seed_name = dotted(node.args[0])
            if seed_name and self._is_tracer(seed_name) and len(node.args) > 1:
                self._seed_arg(node.args[1],
                               f"partial({seed_name}) at "
                               f"{self.mod.relpath}:{node.lineno}")
        elif fname and self._is_tracer(fname) and node.args:
            self._seed_arg(node.args[0],
                           f"{fname} at {self.mod.relpath}:{node.lineno}")
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load) and self.fn_stack:
            self.fn_stack[-1].refs.add(node.id)


class CallGraph:
    """All analyzed modules + the traced set + reachability queries."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}     # modkey -> info
        self.functions: Dict[str, FunctionInfo] = {}  # gid -> info
        self._pending_seeds: List[Tuple[str, str]] = []
        self._pending_arg_seeds: List[Tuple] = []
        self.traced: Dict[str, str] = {}             # gid -> reason
        self._edges: Dict[str, Set[str]] = {}

    # -- construction --------------------------------------------------
    @classmethod
    def build(cls, files: Sequence[Tuple[str, str, str]]) -> "CallGraph":
        """files: (relpath, modkey, source) triples."""
        g = cls()
        collectors = []
        for relpath, modkey, source in files:
            tree = ast.parse(source, filename=relpath)
            mod = ModuleInfo(relpath, modkey, tree, source)
            g.modules[modkey] = mod
            collectors.append(mod)
        # two passes: register all functions first so seeds recorded while
        # visiting module A can resolve into module B
        for mod in collectors:
            _Collector(mod, g).visit(mod.tree)
        for mod in collectors:
            for fi in mod.functions.values():
                g.functions[fi.gid] = fi
        # seeds recorded during collection are replayed now that every
        # function is registered (decorator seeds carry gids; argument
        # seeds carry unresolved callee tuples)
        for gid, reason in g._pending_seeds:
            if gid in g.functions and gid not in g.traced:
                g.traced[gid] = reason
        for mod, fn, tgt, reason in g._pending_arg_seeds:
            gid = g._resolve(mod, fn, tgt)
            if gid is not None and gid not in g.traced:
                g.traced[gid] = reason
        g._close_traced()
        return g

    def _seed(self, gid: str, reason: str) -> None:
        self._pending_seeds.append((gid, reason))

    # -- resolution ----------------------------------------------------
    def _lexical_prefixes(self, fn: Optional[FunctionInfo]):
        """Quals to prepend when looking up a bare name from inside fn:
        own body, then enclosing FUNCTION scopes (class scopes are not
        visible from method bodies), then module level."""
        if fn is None:
            yield ""
            return
        chain = list(fn.scope) + [("func", fn.qual.split(".")[-1])]
        for i in range(len(chain), 0, -1):
            if chain[i - 1][0] != "func":
                continue
            yield ".".join(n for _, n in chain[:i])
        yield ""

    def _resolve(self, mod: ModuleInfo, fn: Optional[FunctionInfo],
                 callee: Tuple) -> Optional[str]:
        kind = callee[0]
        if kind == "name":
            name = callee[1]
            for prefix in self._lexical_prefixes(fn):
                qual = f"{prefix}.{name}" if prefix else name
                if qual in mod.functions:
                    return mod.functions[qual].gid
            imp = mod.imports.get(name)
            if imp and imp[0] == "symbol" and imp[1] in self.modules:
                target = self.modules[imp[1]]
                if imp[2] in target.functions:
                    return target.functions[imp[2]].gid
        elif kind == "self":
            name = callee[1]
            if fn is not None and fn.class_name:
                qual = f"{fn.class_name}.{name}"
                # the class may be nested; search any class-qualified match
                if qual in mod.functions:
                    return mod.functions[qual].gid
                for q, f2 in mod.functions.items():
                    if f2.class_name == fn.class_name and \
                            q.split(".")[-1] == name:
                        return f2.gid
        elif kind == "attr":
            base, leaf = callee[1], callee[2]
            imp = mod.imports.get(base)
            if imp and imp[0] == "module" and imp[1] in self.modules:
                target = self.modules[imp[1]]
                if leaf in target.functions:
                    return target.functions[leaf].gid
            if imp and imp[0] == "symbol":
                # from ..pkg import submodule  (symbol that IS a module)
                subkey = f"{imp[1]}.{imp[2]}" if imp[1] else imp[2]
                if subkey in self.modules:
                    target = self.modules[subkey]
                    if leaf in target.functions:
                        return target.functions[leaf].gid
        return None

    def edges_of(self, fi: FunctionInfo) -> Set[str]:
        cached = self._edges.get(fi.gid)
        if cached is not None:
            return cached
        out: Set[str] = set()
        for callee in fi.calls:
            gid = self._resolve(fi.module, fi, callee)
            if gid is not None:
                out.add(gid)
        for name in fi.refs:
            gid = self._resolve(fi.module, fi, ("name", name))
            if gid is not None and gid != fi.gid:
                out.add(gid)
        self._edges[fi.gid] = out
        return out

    # -- traced closure ------------------------------------------------
    def _close_traced(self) -> None:
        work = list(self.traced)
        while work:
            gid = work.pop()
            fi = self.functions.get(gid)
            if fi is None:
                continue
            reason = f"called from traced {fi.qual}"
            for callee in self.edges_of(fi):
                if callee not in self.traced:
                    self.traced[callee] = reason
                    work.append(callee)

    def is_traced(self, fi: FunctionInfo) -> bool:
        return fi.gid in self.traced

    # -- reachability --------------------------------------------------
    def match(self, patterns: Sequence[str]) -> List[FunctionInfo]:
        """Match 'Class.method' / '*.fit_batch' / 'mod.py::qual' globs
        against every function's gid and qual."""
        out = []
        for fi in self.functions.values():
            for pat in patterns:
                if fnmatch.fnmatch(fi.qual, pat) or \
                        fnmatch.fnmatch(fi.gid, pat):
                    out.append(fi)
                    break
        return out

    def reachable_from(self, roots: Sequence[FunctionInfo]) -> Dict[str, str]:
        """gid -> root qual for everything transitively reachable."""
        seen: Dict[str, str] = {}
        work: List[Tuple[str, str]] = [(r.gid, r.qual) for r in roots]
        while work:
            gid, root = work.pop()
            if gid in seen:
                continue
            seen[gid] = root
            fi = self.functions.get(gid)
            if fi is None:
                continue
            for callee in self.edges_of(fi):
                if callee not in seen:
                    work.append((callee, root))
        return seen


def load_package(root: str, package_dir: str,
                 exclude: Sequence[str] = ()) -> List[Tuple[str, str, str]]:
    """Collect (relpath, modkey, source) for every .py under package_dir."""
    out = []
    base = os.path.join(root, package_dir)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = sorted(d for d in dirnames
                             if d != "__pycache__" and d not in exclude)
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            if any(fnmatch.fnmatch(rel, e) for e in exclude):
                continue
            inner = os.path.relpath(full, base).replace(os.sep, "/")
            modkey = inner[:-3].replace("/", ".")
            if modkey.endswith("__init__"):
                modkey = modkey[: -len("__init__")].rstrip(".")
            with open(full, "r", encoding="utf-8") as f:
                out.append((rel, modkey, f.read()))
    return out
