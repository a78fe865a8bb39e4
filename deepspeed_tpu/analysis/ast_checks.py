"""Family B: AST lint over ``deepspeed_tpu/`` for retrace hazards.

The jaxpr family (``jaxpr_checks``) sees everything a trace reaches but
only for the programs it traces; this family is the broad, syntactic
complement — it walks every ``.py`` file and flags hazard *patterns* inside
**jitted regions**:

- a function def decorated with ``jax.jit`` / ``functools.partial(jax.jit,
  ...)`` (or wrapped at an assignment ``f = jax.jit(g, ...)``), and
- a function passed as the body/branch of ``lax.scan`` / ``lax.while_loop``
  / ``lax.cond`` / ``lax.fori_loop`` anywhere (scan bodies are traced even
  when the def site is a plain module function).

Within a region the checker tracks which local names are (conservatively)
traced: the region's own non-static parameters seed the set, and any name
assigned from an expression that mentions a tracked name or calls into
``jnp``/``jax.lax``/``jax.nn``/``jax.random`` joins it. Closure variables
are deliberately NOT tracked — branching on ``self.tp``/``greedy``-style
trace-constants is the codebase's bread and butter and must not be flagged.
That makes the checker precise rather than complete: it catches the
retrace/ConcretizationTypeError hazards that enter through the traced
arguments, which is where every real incident has come from.
"""

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from .findings import Finding

_CONTROL_FLOW_FNS = {"scan", "while_loop", "cond", "fori_loop", "switch",
                     "associative_scan"}
_TRACED_MODULES = {"jnp", "lax"}            # jnp.x(...), lax.x(...)
_NP_CONSTRUCTORS = {"zeros", "ones", "full", "empty", "array", "asarray",
                    "arange", "linspace", "concatenate", "stack", "where"}
_HOST_COERCIONS = {"float", "int", "bool", "complex"}
_HOST_METHODS = {"item", "tolist"}


def _dotted(node: ast.AST) -> str:
    """'jax.lax.scan' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit")


def _jit_static_info(call: ast.Call) -> Tuple[Set[str], Set[int]]:
    """static_argnames/static_argnums from a jax.jit(...) /
    functools.partial(jax.jit, ...) call's keywords (literal values only —
    computed static specs are themselves a retrace smell, but not ours to
    prove here)."""
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, int):
                    nums.add(el.value)
    return names, nums


@dataclasses.dataclass
class _Region:
    """One jitted region: a function def whose parameters are traced."""
    node: ast.AST                      # FunctionDef / Lambda
    kind: str                          # "jit" | "scan-body" | ...
    static_names: Set[str]
    static_nums: Set[int]

    def param_roots(self) -> Set[str]:
        args = self.node.args
        ordered = [a.arg for a in args.posonlyargs + args.args]
        roots = set()
        for i, name in enumerate(ordered):
            if name in ("self", "cls"):
                continue
            if name in self.static_names or i in self.static_nums:
                continue
            roots.add(name)
        roots.update(a.arg for a in args.kwonlyargs
                     if a.arg not in self.static_names)
        return roots


def _find_regions(tree: ast.AST) -> List[_Region]:
    """Jitted regions in one module (see module docstring)."""
    regions: List[_Region] = []
    defs: Dict[str, ast.AST] = {}
    lax_fns: Set[str] = set()      # `from jax.lax import scan as s` names
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node
        elif isinstance(node, ast.ImportFrom) and node.module == "jax.lax":
            lax_fns.update(a.asname or a.name for a in node.names
                           if a.name in _CONTROL_FLOW_FNS)

    for node in ast.walk(tree):
        # decorated defs
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jax_jit(dec):
                    regions.append(_Region(node, "jit", set(), set()))
                elif isinstance(dec, ast.Call):
                    target = dec.func
                    if _is_jax_jit(target):
                        names, nums = _jit_static_info(dec)
                        regions.append(_Region(node, "jit", names, nums))
                    elif _dotted(target) in ("functools.partial", "partial") \
                            and dec.args and _is_jax_jit(dec.args[0]):
                        names, nums = _jit_static_info(dec)
                        regions.append(_Region(node, "jit", names, nums))
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        # f = jax.jit(g, static_argnames=...)
        if fn in ("jax.jit", "jit") and node.args:
            inner = node.args[0]
            if isinstance(inner, ast.Name) and inner.id in defs:
                names, nums = _jit_static_info(node)
                regions.append(_Region(defs[inner.id], "jit", names, nums))
            elif isinstance(inner, ast.Lambda):
                regions.append(_Region(inner, "jit", *_jit_static_info(node)))
        # lax.scan(body, ...), lax.cond(p, t, f), lax.while_loop(c, b, ...)
        elif fn and fn.rsplit(".", 1)[-1] in _CONTROL_FLOW_FNS:
            if "." in fn:
                if fn.rsplit(".", 2)[-2] != "lax":
                    continue
            elif fn not in lax_fns:
                # a bare `scan(...)`/`switch(...)` counts only when the
                # name was imported from jax.lax — a host-side helper
                # that happens to share the name must not turn its
                # callback args into "jitted regions"
                continue
            for arg in node.args:
                if isinstance(arg, ast.Name) and arg.id in defs:
                    regions.append(_Region(defs[arg.id], "scan-body",
                                           set(), set()))
                elif isinstance(arg, ast.Lambda):
                    regions.append(_Region(arg, "scan-body", set(), set()))
    # dedupe by node identity (a def can be both decorated and scanned)
    seen: Set[int] = set()
    out = []
    for r in regions:
        if id(r.node) not in seen:
            seen.add(id(r.node))
            out.append(r)
    return out


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _calls_traced_module(node: ast.AST) -> bool:
    for call in ast.walk(node):
        if isinstance(call, ast.Call):
            fn = _dotted(call.func)
            head = fn.split(".", 1)[0]
            if head in _TRACED_MODULES or fn.startswith("jax."):
                return True
    return False


def _tracked_names(region: _Region) -> Set[str]:
    """Fixpoint of 'this local name holds a traced value'."""
    tracked = region.param_roots()
    body = region.node.body if not isinstance(region.node, ast.Lambda) else []
    stmts = [s for node in body for s in ast.walk(node)]
    for _ in range(4):   # shallow chains; 4 passes covers the codebase
        grew = False
        for st in stmts:
            if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                value = st.value
                if value is None:
                    continue
                rhs_traced = bool(_names_in(value) & tracked) \
                    or _calls_traced_module(value)
                if not rhs_traced:
                    continue
                targets = st.targets if isinstance(st, ast.Assign) \
                    else [st.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name) and n.id not in tracked:
                            tracked.add(n.id)
                            grew = True
        if not grew:
            break
    return tracked


def _own_statements(region: _Region, all_regions: List[_Region]):
    """Every node of this region EXCLUDING nested jitted regions (they are
    checked with their own root sets). Lambda bodies are walked too — a
    `lambda c, x: (c + float(x), c)` scan body must not escape just for
    being an expression."""
    nested = {id(r.node) for r in all_regions if r.node is not region.node}
    out = []
    stack = ([region.node.body] if isinstance(region.node, ast.Lambda)
             else list(region.node.body))
    while stack:
        node = stack.pop()
        if id(node) in nested:
            continue
        out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def check_module(path: str, source: str) -> List[Finding]:
    """All Family B findings for one file (suppressions NOT yet applied)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("GL101", path, e.lineno or 0,
                        f"file does not parse: {e.msg}")]
    findings: List[Finding] = []
    regions = _find_regions(tree)
    static_name_pool: Set[str] = set()
    for r in regions:
        static_name_pool |= r.static_names

    for region in regions:
        name = getattr(region.node, "name", "<lambda>")
        tracked = _tracked_names(region)
        nodes = _own_statements(region, regions)
        for node in nodes:
            findings.extend(_check_node(path, name, node, tracked,
                                        region.static_names))

    # GL102 — unhashable literals bound to known static argument names,
    # but ONLY at calls that plausibly reach a jit: the jitted defs
    # themselves or the runner's dispatch-wrapper methods. A host helper
    # that merely shares a kwarg name ('width=', 'steps=') must not trip
    # an error-severity finding.
    jit_callees = ({getattr(r.node, "name", None) for r in regions}
                   | set(DISPATCH_DONATIONS)) - {None}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func).rsplit(".", 1)[-1]
        if callee not in jit_callees:
            continue
        for kw in node.keywords:
            if kw.arg in static_name_pool and isinstance(
                    kw.value, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                               ast.DictComp, ast.SetComp)):
                findings.append(Finding(
                    "GL102", path, kw.value.lineno,
                    f"static jit argument '{kw.arg}' receives an "
                    "unhashable literal — the jit cache key cannot hold "
                    "it (TypeError at dispatch, or a retrace per call "
                    "if coerced)", context=_dotted(node.func)))
    return findings


def _is_identity_test(expr: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` / ``isinstance(x, T)`` inspect the
    Python OBJECT, not the traced value — always trace-safe."""
    if isinstance(expr, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
        return True
    if isinstance(expr, ast.Call) and _dotted(expr.func) in (
            "isinstance", "hasattr", "callable"):
        return True
    if isinstance(expr, ast.BoolOp):
        return all(_is_identity_test(v) for v in expr.values)
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
        return _is_identity_test(expr.operand)
    return False


def _check_node(path: str, region_name: str, node: ast.AST,
                tracked: Set[str], static_names: Set[str]) -> List[Finding]:
    out: List[Finding] = []

    def traced_expr(expr: ast.AST) -> bool:
        if _is_identity_test(expr):
            return False
        return bool((_names_in(expr) - static_names) & tracked) \
            or _calls_traced_module(expr)

    # GL101 — Python control flow on traced values
    if isinstance(node, (ast.If, ast.While)):
        if traced_expr(node.test):
            kind = "if" if isinstance(node, ast.If) else "while"
            out.append(Finding(
                "GL101", path, node.lineno,
                f"Python `{kind}` on a traced value — inside a jitted "
                "region this is a ConcretizationTypeError (or a retrace "
                "per distinct value if the operand is ever made static); "
                "use lax.cond/jnp.where", context=region_name))
    elif isinstance(node, ast.Assert) and traced_expr(node.test):
        out.append(Finding(
            "GL101", path, node.lineno,
            "Python `assert` on a traced value — dead under jit (traced "
            "once, never re-evaluated); use checkify or an in-graph "
            "latch like the serving finite-check", context=region_name))

    if not isinstance(node, ast.Call):
        return out
    fn = _dotted(node.func)

    def args_traced() -> bool:
        return any(bool((_names_in(a) - static_names) & tracked)
                   for a in node.args)

    # GL104 — host coercions
    if fn in _HOST_COERCIONS and node.args and not isinstance(
            node.args[0], ast.Constant) and args_traced():
        out.append(Finding(
            "GL104", path, node.lineno,
            f"`{fn}()` on a traced value forces a host sync (or raises "
            "under transfer guard) inside the compiled path",
            context=region_name))
    elif isinstance(node.func, ast.Attribute) \
            and node.func.attr in _HOST_METHODS:
        if traced_expr(node.func.value):
            out.append(Finding(
                "GL104", path, node.lineno,
                f"`.{node.func.attr}()` on a traced value is a "
                "device->host transfer inside the compiled path",
                context=region_name))
    elif fn.startswith("np.") or fn.startswith("numpy."):
        tail = fn.split(".", 1)[1]
        if tail in _NP_CONSTRUCTORS:
            out.append(Finding(
                "GL104", path, node.lineno,
                f"`{fn}()` inside a jitted region builds a HOST array — "
                "on a traced operand it device-syncs; on constants it "
                "bakes f64 trace-time values (use jnp)",
                context=region_name))
        elif tail in ("float64", "float32", "int64") and args_traced():
            out.append(Finding(
                "GL104", path, node.lineno,
                f"`{fn}()` coerces a traced value through numpy "
                "(host sync + strong f64 promotion)", context=region_name))

    # GL103 — float64 dtype drift
    for kw in node.keywords:
        if kw.arg == "dtype" and _dotted(kw.value) in (
                "float", "np.float64", "numpy.float64", "jnp.float64"):
            out.append(Finding(
                "GL103", path, node.lineno,
                f"dtype={_dotted(kw.value)} in a jitted region: silently "
                "downcast to f32 with x64 disabled, doubled "
                "bandwidth/promotion drift otherwise — name a concrete "
                "32-bit (or narrower) dtype", context=region_name))
    if isinstance(node.func, ast.Attribute) and node.func.attr == "astype" \
            and node.args and _dotted(node.args[0]) in (
                "float", "np.float64", "numpy.float64", "jnp.float64"):
        out.append(Finding(
            "GL103", path, node.lineno,
            "`.astype(float)` is float64 — promotion drift in a jitted "
            "region (name a concrete dtype)", context=region_name))

    # GL105 — print at trace time
    if fn == "print":
        out.append(Finding(
            "GL105", path, node.lineno,
            "print() in a jitted region runs ONCE at trace time — use "
            "jax.debug.print if per-step output is intended (and budget "
            "it: GL001 counts the resulting callback)",
            context=region_name))
    return out


# ---------------------------------------------------------------------------
# GL002 (AST half): donated-carry rebinding at dispatch sites
# ---------------------------------------------------------------------------

#: callee attr name -> (positions of donated args AT THE CALL SITE,
#: counting positional args only). Derived from the runner's jit
#: donate_argnums shifted by any leading non-jit params of the wrapper
#: (frame_loop_spec takes draft_runner first, run takes chunk first).
#: tests/test_static_analysis.py cross-checks these against the live
#: ``Traced.donate_argnums`` so the table cannot rot silently.
DISPATCH_DONATIONS: Dict[str, Tuple[int, ...]] = {
    "frame_loop": tuple(range(7, 17)),
    "frame_loop_spec": tuple(range(9, 22)),
    "run": (6, 7),
    # KV memory-hierarchy page movers (kv_cache.py): both donate the two
    # pools they rewrite in place (COW copies / swap-in restores)
    "copy_blocks": (0, 1),
    "scatter_pages": (0, 1),
}


def check_donation_sites(path: str, source: str,
                         registry: Optional[Dict[str, Tuple[int, ...]]] = None
                         ) -> List[Finding]:
    """Every call to a donating runner entry point must rebind each donated
    argument from the call's result tuple in the SAME statement — the
    pattern ``(toks, emit, self.cached, ...) = runner.frame_loop(...,
    self.cached, ...)``. A dispatch that keeps using the old reference
    reads a donated (dead) buffer."""
    registry = DISPATCH_DONATIONS if registry is None else registry
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return []
    findings: List[Finding] = []
    # scopes to scan: each function def, plus the module top level. A
    # donated argument counts as rebound if ANY assignment in the same
    # scope targets the same expression — covering both the one-statement
    # tuple-unpack idiom and the assign-then-rebind refactor of it.
    scopes = [n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    scopes.append(tree)

    def scope_walk(scope):
        """Nodes of this scope only — nested defs are their own scope."""
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            n = stack.pop()
            yield n
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                stack.extend(ast.iter_child_nodes(n))

    for scope in scopes:
        rebound: List[str] = []
        calls = []
        for node in scope_walk(scope):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    elts = t.elts if isinstance(t, ast.Tuple) else [t]
                    rebound.extend(ast.unparse(e) for e in elts)
            if not isinstance(node, (ast.Assign, ast.Expr)):
                continue
            value = node.value
            if isinstance(value, ast.Call) \
                    and isinstance(value.func, ast.Attribute) \
                    and value.func.attr in registry:
                calls.append(value)
        for value in calls:
            name = value.func.attr
            for pos in registry[name]:
                if pos >= len(value.args):
                    continue   # fewer positional args (kwargs form) — skip
                arg = value.args[pos]
                if isinstance(arg, ast.Constant):
                    continue
                if ast.unparse(arg) not in rebound:
                    findings.append(Finding(
                        "GL002", path, value.lineno,
                        f"call to {name}() donates argument "
                        f"{ast.unparse(arg)!r} (position {pos}) but no "
                        "assignment in the enclosing scope rebinds it "
                        "from the results — the caller keeps a reference "
                        "to a dead buffer", context=name))
    return findings
