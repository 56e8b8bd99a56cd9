"""Shared AST machinery: import resolution, scope/def lookup, traced-region
discovery, and the tracer-taint walk the hygiene passes are built on.

Everything here is a *static approximation*.  The guiding rule is
asymmetric cost: a missed hazard is cheap (the next contributor's retrace
is caught in review), a false positive is expensive (it trains people to
sprinkle suppressions) — so where the analysis cannot decide, it stays
silent.  Taint starts at the parameters of a traced function and flows
through assignments; it is *dropped* through the accessors that are
static at trace time (``.shape``/``.ndim``/``.dtype``, ``len()``,
``isinstance``, ``x is None``), which is what keeps idiomatic jax code
clean without suppressions.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field


# -- imports ------------------------------------------------------------------


def build_imports(tree: ast.Module) -> dict:
    """Local name -> dotted module path it refers to."""
    imports = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    imports[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    imports[head] = head
        elif isinstance(node, ast.ImportFrom):
            mod = ("." * node.level) + (node.module or "")
            for a in node.names:
                local = a.asname or a.name
                imports[local] = f"{mod}.{a.name}" if mod else a.name
    return imports


def dotted_name(node) -> str | None:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def qualify(node, imports: dict) -> str | None:
    """Fully-qualified dotted name of an expression, resolving the leading
    segment through the module's imports (``lax.psum`` -> ``jax.lax.psum``,
    ``pl.pallas_call`` -> ``jax.experimental.pallas.pallas_call``)."""
    d = dotted_name(node)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    base = imports.get(head, head)
    return f"{base}.{rest}" if rest else base


# -- scopes -------------------------------------------------------------------

FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def _shallow_stmts(body):
    """Statements of a scope, descending into control flow but NOT into
    nested function/class bodies."""
    stack = list(body)
    while stack:
        stmt = stack.pop(0)
        yield stmt
        if isinstance(stmt, FUNC_NODES + (ast.ClassDef, ast.Lambda)):
            continue
        for fld in ("body", "orelse", "finalbody"):
            stack.extend(getattr(stmt, fld, []) or [])
        for handler in getattr(stmt, "handlers", []) or []:
            stack.extend(handler.body)
        for item in getattr(stmt, "cases", []) or []:  # match statements
            stack.extend(item.body)


@dataclass
class Scope:
    node: object  # Module or function node
    parent: "Scope | None"
    defs: dict = field(default_factory=dict)  # name -> FunctionDef/Lambda
    consts: dict = field(default_factory=dict)  # name -> ast constant expr

    def lookup(self, name: str):
        s = self
        while s is not None:
            if name in s.defs:
                return s.defs[name]
            s = s.parent
        return None

    def lookup_const(self, name: str):
        s = self
        while s is not None:
            if name in s.consts:
                return s.consts[name]
            s = s.parent
        return None


def build_scopes(tree: ast.Module) -> dict:
    """Map every function node (and the module) to its ``Scope``."""
    scopes = {}

    def visit(node, parent: Scope | None):
        scope = Scope(node=node, parent=parent)
        scopes[node] = scope
        body = node.body if not isinstance(node, ast.Lambda) else []
        for stmt in _shallow_stmts(body):
            if isinstance(stmt, FUNC_NODES):
                scope.defs[stmt.name] = stmt
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                tgt = stmt.targets[0]
                if isinstance(tgt, ast.Name):
                    if isinstance(stmt.value, ast.Lambda):
                        scope.defs[tgt.id] = stmt.value
                    else:
                        scope.consts[tgt.id] = stmt.value
        # recurse into nested functions (wherever they appear)
        for child in ast.walk(node):
            if child is node:
                continue
            if isinstance(child, FUNC_NODES + (ast.Lambda,)):
                if _owner(child, node, scopes):
                    visit(child, scope)
        return scope

    def _owner(child, node, scopes):
        # only recurse from the nearest enclosing function: walk from the
        # module finds every nested fn, so guard against revisiting
        return child not in scopes and _nearest_func(child, tree) is node

    # precompute parent links once
    parents = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent

    def _nearest_func(node, root):
        p = parents.get(node)
        while p is not None:
            if isinstance(p, FUNC_NODES + (ast.Lambda,)):
                return p
            p = parents.get(p)
        return root

    visit(tree, None)
    return scopes


# -- traced-region discovery --------------------------------------------------

#: trace-entry callables -> positions of the traced function arguments
#: (negative tuple entry means "a list of callables at this position")
TRACING_CALLS = {
    "jax.jit": (0,),
    "jax.pjit": (0,),
    "jax.vmap": (0,),
    "jax.pmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
    "jax.jacfwd": (0,),
    "jax.jacrev": (0,),
    "jax.hessian": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.associative_scan": (0,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2),
    "jax.lax.switch": (1,),
    "jax.shard_map": (0,),
    "jax.experimental.pallas.pallas_call": (0,),
}

#: decorators that make the decorated def a traced region
TRACING_DECORATORS = {
    "jax.jit", "jax.pjit", "jax.vmap", "jax.pmap", "jax.checkpoint",
    "jax.remat", "jax.grad", "jax.value_and_grad",
}

_JIT_NAMES = {"jax.jit", "jax.pjit"}


@dataclass
class TracedUse:
    node: object  # the function node
    reason: str  # "jit-decorated function", "scan body", ...
    static_names: set = field(default_factory=set)
    #: the jit()/partial(jit) call carrying static_argnums etc, if any
    jit_call: object = None


def _param_names(fn) -> list:
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args)]


def _defaulted_names(fn) -> set:
    """Parameters carrying a default value.  In a traced body these are
    the ``lambda ..., G=G:`` / ``def body(c, x, seg=seg):`` closure-
    avoidance idiom — scan/cond/jit call the body with the declared
    positional signature only, so a defaulted param holds its concrete
    Python default, not a tracer."""
    a = fn.args
    pos = _param_names(fn)
    names = set(pos[len(pos) - len(a.defaults):]) if a.defaults else set()
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None:
            names.add(p.arg)
    return names


def _static_names_from_call(call: ast.Call, fn) -> set:
    """Resolve static_argnums/static_argnames on a jit(...) call against
    the traced function's positional parameters."""
    names = set()
    params = _param_names(fn) if fn is not None else []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = (
                [kw.value] if isinstance(kw.value, ast.Constant)
                else list(getattr(kw.value, "elts", []))
            )
            names.update(
                v.value for v in vals
                if isinstance(v, ast.Constant) and isinstance(v.value, str)
            )
        elif kw.arg == "static_argnums":
            vals = (
                [kw.value] if isinstance(kw.value, ast.Constant)
                else list(getattr(kw.value, "elts", []))
            )
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    if 0 <= v.value < len(params):
                        names.add(params[v.value])
    return names


_REASONS = {
    "jax.lax.scan": "scan body",
    "jax.lax.map": "lax.map body",
    "jax.lax.associative_scan": "associative_scan body",
    "jax.lax.while_loop": "while_loop function",
    "jax.lax.fori_loop": "fori_loop body",
    "jax.lax.cond": "cond branch",
    "jax.lax.switch": "switch branch",
    "jax.shard_map": "shard_map body",
    "jax.experimental.pallas.pallas_call": "pallas kernel",
}


def find_traced(sf) -> dict:
    """Map function node -> ``TracedUse`` for every function the file
    syntactically hands to the tracer (jit decoration, jit()/vmap() call
    wrapping, scan/shard_map/cond/... body position).  Cached per file."""
    if "traced" in sf.cache:
        return sf.cache["traced"]
    tree = sf.tree
    traced: dict = {}
    if tree is None:
        sf.cache["traced"] = traced
        return traced
    imports = build_imports(tree)
    scopes = build_scopes(tree)

    def mark(fn, reason, static=(), jit_call=None):
        if fn is None or not isinstance(fn, FUNC_NODES + (ast.Lambda,)):
            return
        if fn in traced:
            traced[fn].static_names.update(static)
            return
        traced[fn] = TracedUse(
            node=fn, reason=reason, static_names=set(static),
            jit_call=jit_call,
        )

    # enclosing-scope map for Name -> def resolution at each call site
    parents = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent

    def nearest_scope(node):
        p = parents.get(node)
        while p is not None:
            if isinstance(p, FUNC_NODES + (ast.Lambda,)) and p in scopes:
                return scopes[p]
            p = parents.get(p)
        return scopes[tree]

    call_scope = {
        node: nearest_scope(node)
        for node in ast.walk(tree) if isinstance(node, ast.Call)
    }

    def resolve(arg, scope):
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name) and scope is not None:
            return scope.lookup(arg.id)
        return None

    # decorators
    for fnode in scopes:
        if not isinstance(fnode, FUNC_NODES):
            continue
        for dec in fnode.decorator_list:
            q = qualify(dec, imports)
            if q in TRACING_DECORATORS:
                mark(fnode, "jit-decorated function"
                     if q in _JIT_NAMES else f"@{q.split('.')[-1]} function")
            elif isinstance(dec, ast.Call):
                qf = qualify(dec.func, imports)
                if qf in ("functools.partial", "partial") and dec.args:
                    inner = qualify(dec.args[0], imports)
                    if inner in TRACING_DECORATORS:
                        static = (
                            _static_names_from_call(dec, fnode)
                            if inner in _JIT_NAMES else set()
                        )
                        mark(fnode, "jit-decorated function"
                             if inner in _JIT_NAMES
                             else f"@{inner.split('.')[-1]} function",
                             static=static, jit_call=dec)
                elif qf in TRACING_DECORATORS:
                    static = (
                        _static_names_from_call(dec, fnode)
                        if qf in _JIT_NAMES else set()
                    )
                    mark(fnode, "jit-decorated function"
                         if qf in _JIT_NAMES
                         else f"@{qf.split('.')[-1]} function",
                         static=static, jit_call=dec)

    # call sites
    for call, scope in call_scope.items():
        q = qualify(call.func, imports)
        if q not in TRACING_CALLS:
            continue
        reason = _REASONS.get(q, "traced function")
        for pos in TRACING_CALLS[q]:
            if pos >= len(call.args):
                continue
            arg = call.args[pos]
            cands = (
                list(getattr(arg, "elts", []))
                if isinstance(arg, (ast.List, ast.Tuple)) else [arg]
            )
            for cand in cands:
                fn = resolve(cand, scope)
                if fn is None:
                    continue
                if q in _JIT_NAMES:
                    mark(fn, "jit-wrapped function",
                         static=_static_names_from_call(call, fn),
                         jit_call=call)
                else:
                    mark(fn, reason)
    sf.cache["traced"] = traced
    return traced


# -- taint analysis -----------------------------------------------------------

#: attribute reads that are static at trace time — accessing them on a
#: tracer yields plain Python, so taint stops here
STATIC_ATTRS = {
    "shape", "ndim", "dtype", "size", "itemsize", "nbytes", "aval",
    "sharding", "weak_type", "names",
}

#: calls whose result is static / host-side regardless of argument taint
SAFE_CALLS = {
    "len", "isinstance", "issubclass", "type", "callable", "hasattr",
    "id", "repr", "str", "format",
}

#: host-synchronizing conversions — flagged when applied to a tracer
BOOL_CASTS = {"bool", "float", "int", "complex"}
HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
HOST_SYNC_CALLS = {
    "numpy.asarray", "numpy.array", "numpy.float32", "numpy.float64",
}


@dataclass(frozen=True)
class TaintEvent:
    kind: str  # "if" | "while" | "assert" | "bool-cast" | "host-sync" | "for-iter"
    node: object
    reason: str  # which traced region this was found in
    detail: str = ""


def _is_none_check(node: ast.Compare) -> bool:
    if not all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
        return False
    operands = [node.left, *node.comparators]
    return any(
        isinstance(o, ast.Constant) and o.value is None for o in operands
    )


class _TaintWalker(ast.NodeVisitor):
    def __init__(self, imports, reason, tainted, events, analyzed):
        self.imports = imports
        self.reason = reason
        self.tainted = set(tainted)
        self.events = events
        self.analyzed = analyzed

    # -- expression taint ----------------------------------------------------

    def taints(self, node) -> bool:
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return self.taints(node.value)
        if isinstance(node, ast.Compare):
            if _is_none_check(node):
                return False
            return any(self.taints(c) for c in [node.left, *node.comparators])
        if isinstance(node, ast.Call):
            q = qualify(node.func, self.imports)
            name = (q or "").split(".")[-1]
            if q in SAFE_CALLS or name in SAFE_CALLS:
                return False
            parts = [node.args, [kw.value for kw in node.keywords]]
            if isinstance(node.func, ast.Attribute):
                parts.append([node.func.value])
            return any(self.taints(a) for group in parts for a in group)
        if isinstance(node, ast.Lambda):
            return False
        return any(self.taints(c) for c in ast.iter_child_nodes(node)
                   if isinstance(c, ast.expr))

    # -- events --------------------------------------------------------------

    def _event(self, kind, node, detail=""):
        self.events.append(
            TaintEvent(kind=kind, node=node, reason=self.reason, detail=detail)
        )

    def _bind(self, target, tainted: bool):
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted)

    # -- statements ----------------------------------------------------------

    def visit_Assign(self, node):
        self.visit(node.value)
        t = self.taints(node.value)
        for tgt in node.targets:
            self._bind(tgt, t)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._bind(node.target, self.taints(node.value))

    def visit_AugAssign(self, node):
        self.visit(node.value)
        if self.taints(node.value):
            self._bind(node.target, True)

    def visit_NamedExpr(self, node):
        self.visit(node.value)
        self._bind(node.target, self.taints(node.value))

    def visit_If(self, node):
        if self.taints(node.test):
            self._event("if", node, ast.unparse(node.test))
        self.visit(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_While(self, node):
        if self.taints(node.test):
            self._event("while", node, ast.unparse(node.test))
        self.visit(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_Assert(self, node):
        if self.taints(node.test):
            self._event("assert", node, ast.unparse(node.test))
        self.generic_visit(node)

    def visit_For(self, node):
        if self.taints(node.iter):
            self._event("for-iter", node, ast.unparse(node.iter))
        self._bind(node.target, self.taints(node.iter))
        self.visit(node.iter)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def _check_comprehension(self, node):
        for gen in node.generators:
            if self.taints(gen.iter):
                self._event("for-iter", node, ast.unparse(gen.iter))
        self.generic_visit(node)

    visit_ListComp = _check_comprehension
    visit_SetComp = _check_comprehension
    visit_DictComp = _check_comprehension
    visit_GeneratorExp = _check_comprehension

    def visit_Call(self, node):
        q = qualify(node.func, self.imports) or ""
        name = q.split(".")[-1]
        if name in BOOL_CASTS and q == name and node.args:
            if self.taints(node.args[0]):
                self._event("bool-cast", node, f"{name}()")
        elif q in HOST_SYNC_CALLS and node.args:
            if self.taints(node.args[0]):
                self._event("host-sync", node, q)
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in HOST_SYNC_METHODS
            and self.taints(node.func.value)
        ):
            self._event("host-sync", node, f".{node.func.attr}()")
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        # nested defs trace as part of the enclosing region: closures see
        # the enclosing taint, their params carry whatever flows in
        if node in self.analyzed:
            return
        self.analyzed.add(node)
        inner = _TaintWalker(
            self.imports, self.reason,
            self.tainted | (set(_param_names(node)) - _defaulted_names(node)),
            self.events, self.analyzed,
        )
        for stmt in node.body:
            inner.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        inner = _TaintWalker(
            self.imports, self.reason,
            self.tainted
            | ({a.arg for a in node.args.args} - _defaulted_names(node)),
            self.events, self.analyzed,
        )
        inner.visit(node.body)


def taint_events(sf) -> list:
    """All tracer-taint events across the file's traced regions (cached)."""
    if "taint_events" in sf.cache:
        return sf.cache["taint_events"]
    events: list = []
    if sf.tree is None:
        sf.cache["taint_events"] = events
        return events
    imports = build_imports(sf.tree)
    traced = find_traced(sf)
    analyzed: set = set()
    for fn, use in traced.items():
        if fn in analyzed:
            continue
        analyzed.add(fn)
        params = (
            {a.arg for a in fn.args.args}
            if isinstance(fn, ast.Lambda) else set(_param_names(fn))
        )
        tainted = (
            params - use.static_names - _defaulted_names(fn)
            - {"self", "cls"}
        )
        walker = _TaintWalker(imports, use.reason, tainted, events, analyzed)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            walker.visit(stmt)
    sf.cache["taint_events"] = events
    return events


# -- constant resolution (pallas pass) ----------------------------------------


def resolve_int(node, scope: Scope | None):
    """Best-effort static int value of an expression: literals, module/
    local constants, and arithmetic over those.  None when undecidable."""
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, int) else None
    if isinstance(node, ast.Name) and scope is not None:
        bound = scope.lookup_const(node.id)
        if bound is not None and bound is not node:
            return resolve_int(bound, scope)
        return None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = resolve_int(node.operand, scope)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        left = resolve_int(node.left, scope)
        right = resolve_int(node.right, scope)
        if left is None or right is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.FloorDiv):
                return left // right
            if isinstance(node.op, ast.Mod):
                return left % right
        except (ZeroDivisionError, ValueError):
            return None
    return None
