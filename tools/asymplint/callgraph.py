"""Module-local call graph + jit-entry detection for the purity rule.

Scope is deliberately one module: a function handed to ``jax.jit`` /
``shard_map`` / ``pl.pallas_call`` is walked together with every
module-local function it (transitively) calls by name.  Cross-module
callees are a different module's problem — they get walked when *their*
module is swept, and chasing imports would make the rule quadratic and
flaky.  This mirrors how the engine is actually shaped: ``tick`` and its
``_phase*`` helpers live in one file.

Name resolution is scope-aware, not a flat bare-name index: every tick
builder in ``core/engine.py`` defines its own nested ``tick``, so
``jax.jit(tick)`` must bind to the ``tick`` of the *enclosing* builder,
never the last one defined in the module.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

# Callables whose first positional argument (or decorated function) is
# traced. vmap/grad trace too, but every vmap in this repo is applied
# inside an already-jitted function, so the jit entry covers it.
TRACING_WRAPPERS = frozenset({"jit", "pallas_call", "shard_map", "pmap"})

# Modules whose use inside traced code is a bug: they execute on the
# host at trace time and constant-fold into the compiled program.
BANNED_MODULES = frozenset({"numpy", "random", "time", "os", "io",
                            "secrets", "datetime"})
# The engine's host spans: a span opened at trace time times the trace,
# not the run.
HOST_ONLY = frozenset({"repro.core.trace"})
BANNED_BUILTINS = frozenset({"print", "open", "input", "breakpoint"})

_FUNC = (ast.FunctionDef, ast.AsyncFunctionDef)


def _callable_name(func: ast.AST) -> str:
    """Last path component of a call target: jax.jit -> 'jit'."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def module_aliases(tree: ast.Module) -> dict[str, str]:
    """Imported-name -> dotted origin ('np' -> 'numpy')."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


@dataclass
class ModuleGraph:
    """All function defs, scope-aware resolution, call edges."""
    by_name: dict[str, list[ast.AST]] = field(default_factory=dict)
    parents: dict[int, ast.AST] = field(default_factory=dict)
    calls: dict[int, list[ast.AST]] = field(default_factory=dict)
    aliases: dict[str, str] = field(default_factory=dict)

    @classmethod
    def build(cls, tree: ast.Module) -> "ModuleGraph":
        g = cls(aliases=module_aliases(tree))
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                g.parents[id(child)] = node
            if isinstance(node, _FUNC):
                g.by_name.setdefault(node.name, []).append(node)
        all_fns = [fn for fns in g.by_name.values() for fn in fns]
        for fn in all_fns:
            edges: list[ast.AST] = []
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Name):
                    target = g.resolve(sub.func.id, sub)
                    if target is not None:
                        edges.append(target)
            g.calls[id(fn)] = edges
        return g

    def _func_ancestors(self, node: ast.AST) -> list[ast.AST]:
        """Enclosing function chain, innermost first."""
        chain, cur = [], self.parents.get(id(node))
        while cur is not None:
            if isinstance(cur, _FUNC):
                chain.append(cur)
            cur = self.parents.get(id(cur))
        return chain

    def resolve(self, name: str, at_node: ast.AST) -> ast.AST | None:
        """Bind ``name`` as seen from ``at_node``'s scope."""
        cands = self.by_name.get(name)
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        visible = {id(fn) for fn in self._func_ancestors(at_node)}
        best, best_depth = None, -1
        for cand in cands:
            anc = self._func_ancestors(cand)
            if not anc:
                depth = 0                      # module level: always visible
            elif id(anc[0]) in visible:
                depth = len(anc)               # sibling in an open scope
            else:
                continue                       # defined in a closed scope
            if depth >= best_depth:            # ties: later def wins
                best, best_depth = cand, depth
        return best

    def jit_entries(self, tree: ast.Module):
        """Yield (function_node, report_line) for every traced root."""
        for node in ast.walk(tree):
            if isinstance(node, _FUNC):
                for dec in node.decorator_list:
                    if self._is_tracing(dec):
                        yield node, node.lineno
            elif isinstance(node, ast.Call):
                if _callable_name(node.func) in TRACING_WRAPPERS and \
                        node.args:
                    fn = self._unwrap_target(node.args[0], node)
                    if fn is not None:
                        yield fn, node.args[0].lineno

    def _unwrap_target(self, expr: ast.AST, at_node: ast.AST,
                       depth: int = 0) -> ast.AST | None:
        """The function a traced-callable expression ultimately names.

        Handles ``tick``, ``lambda``, ``partial(kernel_fn, ...)``, a
        name previously assigned a partial, and ``make_step(cfg)`` —
        for a factory call the factory itself is the root: its nested
        defs are what trace, and ``impure_uses`` recurses into them.
        """
        if depth > 4 or expr is None:
            return None
        if isinstance(expr, ast.Lambda):
            return expr
        if isinstance(expr, ast.Name):
            fn = self.resolve(expr.id, at_node)
            if fn is not None:
                return fn
            host = self._func_ancestors(at_node)
            scope = host[0] if host else None
            if scope is not None:      # e.g. kernel = partial(_kern, ...)
                for sub in ast.walk(scope):
                    if isinstance(sub, ast.Assign) and any(
                            isinstance(t, ast.Name) and t.id == expr.id
                            for t in sub.targets):
                        return self._unwrap_target(sub.value, sub,
                                                   depth + 1)
            return None
        if isinstance(expr, ast.Call):
            name = _callable_name(expr.func)
            if name == "partial" and expr.args:
                return self._unwrap_target(expr.args[0], at_node,
                                           depth + 1)
            if name in TRACING_WRAPPERS:
                return None            # the inner call is its own entry
            factory = self.resolve(name, at_node) if \
                isinstance(expr.func, ast.Name) else None
            return factory
        return None

    def _is_tracing(self, dec: ast.AST) -> bool:
        """@jax.jit, @jit, @partial(jax.jit, ...)."""
        if _callable_name(dec) in TRACING_WRAPPERS:
            return True
        if isinstance(dec, ast.Call):
            if _callable_name(dec.func) in TRACING_WRAPPERS:
                return True
            if _callable_name(dec.func) == "partial" and dec.args and \
                    _callable_name(dec.args[0]) in TRACING_WRAPPERS:
                return True
        return False

    def reachable(self, entry: ast.AST) -> list[ast.AST]:
        """entry + every module-local function transitively called."""
        seen, out, stack = set(), [], [entry]
        while stack:
            fn = stack.pop()
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            out.append(fn)
            stack.extend(self.calls.get(id(fn), ()))
            if isinstance(fn, ast.Lambda):     # lambdas have no call edges
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Name):
                        target = self.resolve(sub.func.id, sub)
                        if target is not None:
                            stack.append(target)
        return out

    def impure_uses(self, fn: ast.AST):
        """Yield (line, description) for host-side ops inside ``fn``.

        Annotations and default-arg expressions are skipped: both
        evaluate at def time, outside the trace.
        """
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            yield from self._scan(stmt)

    def _scan(self, node: ast.AST):
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                yield from self._scan(node.value)
            return
        if isinstance(node, _FUNC):
            for stmt in node.body:   # nested def: body traces, sig doesn't
                yield from self._scan(stmt)
            return
        if isinstance(node, ast.Attribute):
            root = node
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                origin = self.aliases.get(root.id, "")
                if (origin.split(".")[0] in BANNED_MODULES
                        or origin in HOST_ONLY):
                    yield (node.lineno,
                           f"`{root.id}.{node.attr}` resolves to host "
                           f"module `{origin}`")
                    return   # one finding per attribute chain is enough
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            name = node.func.id
            origin = self.aliases.get(name, "")
            if origin.split(".")[0] in BANNED_MODULES:
                yield (node.lineno,
                       f"`{name}()` is `{origin}` — host call at trace "
                       "time")
            elif name in BANNED_BUILTINS and name not in self.by_name \
                    and not origin:
                yield (node.lineno,
                       f"host builtin `{name}()` called")
        for child in ast.iter_child_nodes(node):
            yield from self._scan(child)
