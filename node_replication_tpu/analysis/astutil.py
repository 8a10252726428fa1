"""AST infrastructure shared by every nrlint rule.

Three pieces of project knowledge live here so the rules stay small:

1. **Suppressions** — `# nrlint: disable=<rule>[,<rule>]` comments,
   parsed with `tokenize` so string literals can't spoof them. A
   suppression covers its own line and the line directly below it (for
   a standalone comment above a long statement).

2. **Name resolution** — per-module import maps plus simple local-alias
   tracking (`exec_fn = log_catchup_all if ... else log_exec_all`), so
   rules can ask "what does this expression denote?" in dotted form
   (`jax.jit`, `numpy.asarray`, `node_replication_tpu.core.log.
   log_exec_all`).

3. **Traced-closure inference** — the set of function scopes that
   execute under JAX tracing. Seeds: jit-family decorators, functions
   (and lambdas) referenced inside the arguments of
   `jax.jit`/`jax.vmap`/`lax.scan`/`lax.cond`/`lax.switch`/
   `pallas_call`/`checkify.checkify`/... calls anywhere in the analyzed
   set, and every transition/window function registered on a
   `Dispatch(...)` constructor (those run in-trace by contract). The
   closure then propagates through the project call graph to a
   fixpoint, across modules, so e.g. `_exec_one` (called by the jitted
   `log_exec_all`) is traced without any annotation.

Host-side escape hatch: a region guarded by an `isinstance(...,
jax.core.Tracer)` test (the project's eager-only idiom, see
`core/log.py:_catchup_union_plan`) is exempt from traced-context rules —
the author is explicitly branching on trace-vs-eager there.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize

# The project package: imports under this root resolve cross-module.
PROJECT_PACKAGE = "node_replication_tpu"

# Calls whose function-valued arguments enter JAX tracing.
TRACING_CALLS = frozenset({
    "jax.jit",
    "jax.pmap",
    "jax.vmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.lax.scan",
    "jax.lax.cond",
    "jax.lax.switch",
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.associative_scan",
    "jax.lax.map",
    "jax.experimental.checkify.checkify",
    "jax.experimental.pallas.pallas_call",
    "jax.experimental.shard_map.shard_map",
    f"{PROJECT_PACKAGE}.utils.checks.checked",
})

# `Dispatch(...)` keyword args whose values are in-trace transition or
# window functions (`make_state` runs eagerly at init and is excluded).
DISPATCH_FN_KWARGS = frozenset({
    "write_ops", "read_ops", "window_apply", "window_plan",
    "window_merge", "window_merge_stacked", "window_apply_stacked",
    "read_stacked",
})
DISPATCH_FN_POSARGS = (2, 3)  # (name, make_state, write_ops, read_ops)

_SUPPRESS_RE = re.compile(
    r"#\s*nrlint:\s*disable\s*=\s*"
    r"([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
)
_SUPPRESS_MENTION_RE = re.compile(r"#\s*nrlint\b")


def parse_suppressions(
    source: str,
) -> tuple[dict[int, frozenset[str]], list[int]]:
    """Parse suppression comments.

    Returns `(suppressions, malformed)`: line number -> rule ids
    suppressed there, plus the lines of malformed `# nrlint` comments.
    ONLY the exact `# nrlint: disable=<rule>[,<rule>]` form suppresses —
    a typo (`disable host-sync-in-jit`, `nrlint disable=...`) must not
    silently disarm every rule on the line, so any other `# nrlint`
    mention is reported as malformed instead."""
    out: dict[int, frozenset[str]] = {}
    malformed: list[int] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            line = tok.start[0]
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                rules = frozenset(
                    r.strip() for r in m.group(1).split(",")
                )
                out[line] = out.get(line, frozenset()) | rules
            elif _SUPPRESS_MENTION_RE.search(tok.string):
                malformed.append(line)
    except (tokenize.TokenError, SyntaxError):
        pass
    return out, malformed


def _module_name_for(path: str) -> str:
    """Dotted module name, walking up through __init__.py packages."""
    path = os.path.abspath(path)
    parts = [os.path.splitext(os.path.basename(path))[0]]
    d = os.path.dirname(path)
    while os.path.isfile(os.path.join(d, "__init__.py")):
        parts.append(os.path.basename(d))
        d = os.path.dirname(d)
    if parts[0] == "__init__":
        parts = parts[1:] or parts
    return ".".join(reversed(parts))


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


class ModuleInfo:
    """One parsed file: tree with parent links, imports, defs, aliases."""

    def __init__(self, path: str, source: str | None = None):
        self.path = path
        if source is None:
            with open(path, encoding="utf-8") as fh:
                source = fh.read()
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.module_name = _module_name_for(path)
        self.suppressions, self.malformed_suppressions = (
            parse_suppressions(source)
        )
        # parent links (NodeVisitor-free: one walk)
        self.tree._nrl_parent = None  # type: ignore[attr-defined]
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._nrl_parent = node  # type: ignore[attr-defined]
        # imports: local name -> dotted target
        self.imports: dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.imports[a.asname] = a.name
                    else:
                        # `import jax.numpy` binds the root name `jax`
                        root = a.name.split(".")[0]
                        self.imports[root] = root
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{node.module}.{a.name}"
                    )
        # module-level function defs by name
        self.top_defs: dict[str, ast.AST] = {
            n.name: n
            for n in self.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }

    # -------------------------------------------------- tree navigation

    def parent(self, node: ast.AST) -> ast.AST | None:
        return getattr(node, "_nrl_parent", None)

    def enclosing_functions(self, node: ast.AST):
        """Innermost-first chain of enclosing function-like scopes."""
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, _FUNC_NODES):
                yield cur
            cur = self.parent(cur)

    def enclosing_class(self, node: ast.AST) -> ast.ClassDef | None:
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            if isinstance(cur, _FUNC_NODES):
                return None
            cur = self.parent(cur)
        return None

    def in_eager_guard(self, node: ast.AST) -> bool:
        """Inside an `if` whose test mentions `Tracer` — the project's
        explicit trace-vs-eager branch; exempt from traced-context
        rules on both arms (the author is handling the split)."""
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, ast.If) and "Tracer" in ast.dump(cur.test):
                return True
            cur = self.parent(cur)
        return False

    # ----------------------------------------------------- name lookup

    def dotted(self, node: ast.AST) -> str | None:
        """Resolve `Name`/`Attribute` chains to a dotted external name
        through the import map (`lax.scan` -> `jax.lax.scan`)."""
        parts: list[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        base = self.imports.get(cur.id)
        if base is None:
            return None
        parts.append(base)
        return ".".join(reversed(parts))


@dataclasses.dataclass
class Diagnostic:
    path: str
    line: int
    col: int
    rule_id: str
    severity: str
    message: str
    suppressed: bool = False

    def format(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return (
            f"{self.path}:{self.line}:{self.col}: "
            f"{self.rule_id} {self.severity}: {self.message}{tag}"
        )


class Project:
    """All analyzed modules + the cross-module traced closure."""

    def __init__(self, modules: list[ModuleInfo]):
        self.modules = modules
        self.by_name = {m.module_name: m for m in modules}
        # project symbol table: dotted name -> (module, def node)
        self.symbols: dict[str, tuple[ModuleInfo, ast.AST]] = {}
        for m in modules:
            for name, node in m.top_defs.items():
                self.symbols[f"{m.module_name}.{name}"] = (m, node)
        self.dispatch_fns: set[int] = set()  # id() of def nodes
        self._traced: set[int] = set()       # id() of function scopes
        # id(scope) -> name -> [("def"|"alias", node), ...] in walk
        # order; built lazily so each scope body is walked ONCE no
        # matter how many names resolve inside it (the naive re-walk
        # was quadratic and dominated whole-package build time)
        self._scope_index: dict[int, dict[str, list]] = {}
        self._infer()

    # ------------------------------------------------------- queries

    def is_traced_scope(self, fn: ast.AST) -> bool:
        return id(fn) in self._traced

    def traced_context(self, mod: ModuleInfo, node: ast.AST):
        """The innermost traced function scope enclosing `node`, or
        None. Any enclosing traced scope counts: a nested def inside a
        traced function executes in-trace when called."""
        for fn in mod.enclosing_functions(node):
            if id(fn) in self._traced:
                return fn
        return None

    def is_dispatch_fn(self, fn: ast.AST) -> bool:
        return id(fn) in self.dispatch_fns

    # ------------------------------------------------------ inference

    def _mark(self, fn: ast.AST, worklist: list) -> None:
        if id(fn) not in self._traced:
            self._traced.add(id(fn))
            worklist.append(fn)

    def _scope_names(self, scope: ast.AST) -> dict[str, list]:
        """Name -> [(kind, node)] for defs and single-Name-target
        assigns anywhere under `scope`, in the same statement-major
        walk order the resolver historically observed."""
        idx = self._scope_index.get(id(scope))
        if idx is None:
            idx = {}
            body = scope.body if isinstance(scope.body, list) else []
            for stmt in body:
                for node in ast.walk(stmt):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        idx.setdefault(node.name, []).append(
                            ("def", node)
                        )
                    elif (
                        isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                    ):
                        idx.setdefault(node.targets[0].id, []).append(
                            ("alias", node)
                        )
            self._scope_index[id(scope)] = idx
        return idx

    def _resolve_callable_name(
        self, mod: ModuleInfo, at: ast.AST, name: str,
        seen: set[str] | None = None,
    ) -> list[ast.AST]:
        """Candidate def nodes a bare name may denote at `at`: local
        defs and simple aliases in enclosing scopes, module-level defs,
        then project imports."""
        seen = seen if seen is not None else set()
        if name in seen:
            return []
        seen.add(name)
        out: list[ast.AST] = []
        scopes = list(mod.enclosing_functions(at))
        for scope in scopes:
            for kind, node in self._scope_names(scope).get(name, ()):
                if kind == "def":
                    out.append(node)
                else:
                    # alias: union every name its value mentions.
                    # A name being CALLED in the value
                    # (`replay = make_replay(...)`) is a maker run
                    # at setup time: the alias denotes whatever it
                    # RETURNS, so contribute the maker's nested
                    # defs, not the maker's own host-side body.
                    called = {
                        id(c.func)
                        for c in ast.walk(node.value)
                        if isinstance(c, ast.Call)
                        and isinstance(c.func, ast.Name)
                    }
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Name) and isinstance(
                            sub.ctx, ast.Load
                        ):
                            hits = self._resolve_callable_name(
                                mod, at, sub.id, seen
                            )
                            if id(sub) in called:
                                for fn in hits:
                                    out.extend(
                                        s for s in ast.walk(fn)
                                        if s is not fn
                                        and isinstance(
                                            s, _FUNC_NODES
                                        )
                                    )
                            else:
                                out.extend(hits)
                        elif isinstance(sub, ast.Lambda):
                            out.append(sub)
            if out:
                return out
        if name in mod.top_defs:
            return [mod.top_defs[name]]
        target = mod.imports.get(name)
        if target and target.startswith(PROJECT_PACKAGE):
            hit = self.symbols.get(target)
            if hit:
                return [hit[1]]
        return out

    def _mark_callable_expr(
        self, mod: ModuleInfo, expr: ast.AST, worklist: list
    ) -> None:
        """Mark every function a jit-family argument expression could
        denote: lambdas inside it, plus every loaded Name resolved.

        A Name that is being CALLED inside the expression
        (`jax.jit(make_step(...))`) is a maker running at setup time,
        not the traced callable; the traced callable is whatever it
        returns, so the maker's NESTED defs are marked instead of the
        maker's own (host-side) body."""
        called_makers = {
            id(node.func)
            for node in ast.walk(expr)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
        }
        for node in ast.walk(expr):
            if isinstance(node, ast.Lambda):
                self._mark(node, worklist)
            elif isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Load
            ):
                for fn in self._resolve_callable_name(
                    mod, expr, node.id
                ):
                    if id(node) in called_makers:
                        for sub in ast.walk(fn):
                            if sub is not fn and isinstance(
                                sub, _FUNC_NODES
                            ):
                                self._mark(sub, worklist)
                    else:
                        self._mark(fn, worklist)

    def _seed(self, worklist: list) -> None:
        for mod in self.modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        target = dec.func if isinstance(
                            dec, ast.Call
                        ) else dec
                        d = mod.dotted(target)
                        if d in TRACING_CALLS:
                            self._mark(node, worklist)
                        elif (
                            isinstance(dec, ast.Call)
                            and mod.dotted(dec.func)
                            in ("functools.partial", "partial")
                            and any(
                                mod.dotted(a) in TRACING_CALLS
                                for a in dec.args
                            )
                        ):
                            self._mark(node, worklist)
                if not isinstance(node, ast.Call):
                    continue
                d = mod.dotted(node.func)
                if d in TRACING_CALLS:
                    for arg in list(node.args) + [
                        kw.value for kw in node.keywords
                    ]:
                        self._mark_callable_expr(mod, arg, worklist)
                elif d is not None and (
                    d == f"{PROJECT_PACKAGE}.ops.encoding.Dispatch"
                    or d.endswith(".Dispatch")
                    or d == "Dispatch"
                ):
                    self._seed_dispatch(mod, node, worklist)

    def _seed_dispatch(
        self, mod: ModuleInfo, call: ast.Call, worklist: list
    ) -> None:
        exprs: list[ast.AST] = []
        for i in DISPATCH_FN_POSARGS:
            if i < len(call.args):
                exprs.append(call.args[i])
        for kw in call.keywords:
            if kw.arg in DISPATCH_FN_KWARGS:
                exprs.append(kw.value)
        for expr in exprs:
            for node in ast.walk(expr):
                fns: list[ast.AST] = []
                if isinstance(node, ast.Lambda):
                    fns = [node]
                elif isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load
                ):
                    fns = self._resolve_callable_name(
                        mod, expr, node.id
                    )
                for fn in fns:
                    self.dispatch_fns.add(id(fn))
                    self._mark(fn, worklist)

    def _infer(self) -> None:
        worklist: list = []
        self._seed(worklist)
        # propagate through the call graph: calls made inside a traced
        # scope (by bare name or project-dotted name) mark their defs
        owner: dict[int, ModuleInfo] = {}
        for mod in self.modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, _FUNC_NODES):
                    owner[id(node)] = mod
        while worklist:
            fn = worklist.pop()
            mod = owner.get(id(fn))
            if mod is None:
                continue
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    if isinstance(node.func, ast.Name):
                        for cand in self._resolve_callable_name(
                            mod, node, node.func.id
                        ):
                            self._mark(cand, worklist)
                    else:
                        d = mod.dotted(node.func)
                        if d and d.startswith(PROJECT_PACKAGE):
                            hit = self.symbols.get(d)
                            if hit:
                                self._mark(hit[1], worklist)
