"""Family A: jaxpr-level invariant checks for the compiled serving stack.

Each check consumes a ``TracedProgram`` — a lazily-traced serving entry
point (``programs.build_serving_programs`` traces the REAL frame loops on
tiny abstract shapes; the test fixtures trace deliberately-broken ones) —
and walks the resulting ``ClosedJaxpr``:

- **GL001 TransferGuard** — no host-sync primitive (callbacks, debug
  prints, infeed/outfeed) anywhere in a serving program; scan bodies are
  reported as such. A trace that dies on an implicit ``np.*`` coercion
  (TracerArrayConversionError) is the same bug caught earlier and is
  reported under the same rule.
- **GL002 DonationChecker** — every donated input aval has a matching
  output aval (a donated buffer with no same-shape/dtype output is never
  reused by XLA: the donation silently buys nothing and the caller still
  loses the buffer).
- **GL003 CollectiveChecker** — inside ``shard_map`` manual regions:
  every collective names an axis that is manual on the enclosing mesh,
  every ``ppermute`` permutation is a true permutation (distinct sources,
  distinct targets, no data created or lost), and every output DECLARED
  replicated (empty out spec) is replica-invariant by dataflow — a taint
  pass seeded at sharded inputs and ``axis_index``, cleared only by a
  collective reduction over the tainted axis. This is the static
  replacement for the ``check_vma=False`` the frame loops compile with.
  Scope note: a *dropped* psum whose surrounding program still reduces
  later produces replica-invariant-but-WRONG values — that is a parity
  bug the dynamic token-parity suites own; this pass owns replica
  VARIANCE (e.g. a dropped logit all-gather, where each shard argmaxes
  its local vocab slice and the "replicated" carries silently fork).
- **GL004 RetraceBudget** — tracing the entry point twice with identical
  (bucket-compatible) shapes must produce byte-identical jaxprs; anything
  else is a retrace per call in production (the static complement of
  ``compile_count_total()``).
"""

import dataclasses
import os
import traceback
from typing import Callable, List, Optional, Sequence, Set

from .findings import Finding

JAXPR_PATH = "<jaxpr>"     # pseudo-path for program-level findings

#: primitives that synchronize with / call back into the host
HOST_SYNC_PRIMITIVES = {
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback",
    "outside_call", "infeed", "outfeed", "host_callback_call",
}

#: collective primitives and the param carrying their axis name(s)
_COLLECTIVE_AXIS_PARAM = {
    "psum": "axes", "pmax": "axes", "pmin": "axes",
    "ppermute": "axis_name", "pbroadcast": "axis_name",
    "all_gather": "axis_name", "all_to_all": "axis_name",
    "reduce_scatter": "axis_name", "psum_scatter": "axis_name",
    "axis_index": "axis_name",
}
#: of those, the reductions that make their output replica-invariant over
#: the reduced axis (ppermute/axis_index/all_to_all do NOT)
_INVARIANT_MAKERS = {"psum", "pmax", "pmin", "all_gather"}


@dataclasses.dataclass
class TracedProgram:
    """A serving entry point plus everything the checks need.

    ``trace`` runs the actual ``jax.jit(...).trace(...)`` (or
    ``jax.make_jaxpr``) lazily: trace-time failures are findings, not
    crashes — an implicit host coercion raises TracerArrayConversionError
    (GL001) and an unbound collective axis raises NameError (GL003).
    ``retrace`` must rebuild the jit from scratch so the comparison cannot
    be satisfied by a cache hit.

    ``variant``/``counterpart`` exist for the Family C cost pass
    (``cost_model``): a program traced with a non-default collective
    lowering ("quantized"/"overlap") names the exact-collectives program
    it must be payload-compared against. The default registry is all
    ``variant="exact"``."""
    name: str
    trace: Callable[[], object]          # () -> object with .jaxpr
    donate_argnums: Sequence[int] = ()   # FLAT indices (match .in_avals)
    donate_user_args: Sequence[int] = ()  # user positional args (pytrees=1)
    retrace: Optional[Callable[[], object]] = None
    variant: str = "exact"               # "exact" | "quantized" | "overlap"
    counterpart: str = ""                # exact twin's name (cost variants)
    # trips the cost pass charges a counted loop of the program at, where
    # the loop's bound is an operand (a serving frame's ``n_steps``)
    loop_trips: Optional[int] = None

    _traced: object = dataclasses.field(default=None, repr=False)
    _trace_error: Optional[BaseException] = dataclasses.field(
        default=None, repr=False)

    def traced(self):
        if self._traced is None and self._trace_error is None:
            try:
                self._traced = self.trace()
            except Exception as e:      # noqa: BLE001 — converted to findings
                self._trace_error = e
        if self._trace_error is not None:
            raise self._trace_error
        return self._traced


def _closed(traced):
    """Normalize a trace result to its ClosedJaxpr: accepts either a
    ``jax.stages.Traced`` (``.jaxpr`` is the ClosedJaxpr) or a ClosedJaxpr
    itself (``.jaxpr`` is the raw Jaxpr) — fixtures use ``jax.make_jaxpr``,
    the program registry uses ``jit(...).trace(...)``."""
    inner = traced.jaxpr
    return inner if hasattr(inner, "jaxpr") else traced


def _subjaxprs(params):
    """Yield every inner (jaxpr, primitive-param-key) of an eqn's params."""
    for key, val in params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):                    # Jaxpr
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr                         # ClosedJaxpr


def _walk_eqns(jaxpr, in_scan=False):
    """DFS over every eqn in a jaxpr, yielding (eqn, inside_scan_body)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_scan
        child_in_scan = in_scan or eqn.primitive.name in ("scan", "while")
        for sub in _subjaxprs(eqn.params):
            yield from _walk_eqns(sub, child_in_scan)


def _trace_failure(prog: TracedProgram) -> Optional[BaseException]:
    try:
        prog.traced()
        return None
    except Exception as e:               # noqa: BLE001
        return e


def failure_frame(err: BaseException) -> str:
    """``file.py:NN in fn`` for the most useful traceback frame of a trace
    failure: the INNERMOST frame inside this repo (the serving/analysis
    code that actually drifted), falling back to the innermost frame
    overall when the whole stack is framework-internal. A GL000 finding
    without this is near-undebuggable from the JSON output — the program
    name says *what* failed to trace, never *where*."""
    frames = traceback.extract_tb(err.__traceback__) if err.__traceback__ \
        else []
    if not frames:
        return "<no traceback>"
    here = os.path.abspath(__file__)           # this checker module only:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    pick = next(
        (f for f in reversed(frames)
         # the TracedProgram re-raise in THIS file is plumbing, never the
         # cause — but analysis/programs.py (registry shape drift) very
         # much can be, so only this module is excluded
         if os.path.abspath(f.filename).startswith(repo)
         and os.path.abspath(f.filename) != here),
        frames[-1])
    where = os.path.basename(pick.filename)
    return f"{where}:{pick.lineno} in {pick.name}"


# ---------------------------------------------------------------------------
# GL001 — TransferGuard
# ---------------------------------------------------------------------------

def check_transfer(prog: TracedProgram) -> List[Finding]:
    err = _trace_failure(prog)
    if err is not None:
        tname = type(err).__name__
        if "Tracer" in tname or "Concretization" in tname:
            return [Finding(
                "GL001", JAXPR_PATH, 0,
                f"tracing aborts with {tname}: an implicit host coercion "
                f"(np.*/float()/bool()) sits in the compiled path: {err}",
                context=prog.name)]
        return []     # unrelated trace failure: some other check owns it
    findings = []
    for eqn, in_scan in _walk_eqns(_closed(prog.traced()).jaxpr):
        pname = eqn.primitive.name
        if pname in HOST_SYNC_PRIMITIVES or pname.endswith("_callback"):
            where = ("inside a scan body — it fires EVERY step of every "
                     "frame" if in_scan else "in the frame program")
            findings.append(Finding(
                "GL001", JAXPR_PATH, 0,
                f"host-sync primitive `{pname}` {where}; the serving "
                "contract is zero in-frame device-to-host traffic",
                context=prog.name))
    return findings


# ---------------------------------------------------------------------------
# GL002 — DonationChecker (jaxpr half; ast_checks owns the dispatch sites)
# ---------------------------------------------------------------------------

def check_donation(prog: TracedProgram) -> List[Finding]:
    if _trace_failure(prog) is not None:
        return []
    tr = prog.traced()
    donate = tuple(prog.donate_argnums or getattr(tr, "donate_argnums", ()))
    if not donate:
        return []
    closed = _closed(tr)
    in_avals = tuple(closed.in_avals)
    outs = list(closed.out_avals)
    findings = []
    for i in donate:
        if i >= len(in_avals):
            findings.append(Finding(
                "GL002", JAXPR_PATH, 0,
                f"donate_argnums index {i} is out of range for the "
                f"{len(in_avals)} traced inputs (static-arg shift?)",
                context=prog.name))
            continue
        aval = in_avals[i]
        key = (aval.shape, aval.dtype)
        match = next((j for j, o in enumerate(outs)
                      if (o.shape, o.dtype) == key), None)
        if match is None:
            findings.append(Finding(
                "GL002", JAXPR_PATH, 0,
                f"donated input {i} ({aval.str_short()}) has no "
                "matching output aval: XLA cannot reuse the buffer, the "
                "donation is dead weight and the caller still loses the "
                "reference", context=prog.name))
        else:
            outs.pop(match)    # one output consumes one donation
    return findings


# ---------------------------------------------------------------------------
# GL003 — CollectiveChecker
# ---------------------------------------------------------------------------

def _axis_names(eqn) -> Sequence[str]:
    key = _COLLECTIVE_AXIS_PARAM.get(eqn.primitive.name)
    if key is None:
        return ()
    val = eqn.params.get(key)
    if val is None:
        return ()
    names = val if isinstance(val, (tuple, list)) else (val,)
    return [n for n in names if isinstance(n, str)]


def _taint_jaxpr(jaxpr, in_taints, manual_axes: Set[str]):
    """Forward taint pass: which outputs can differ across shards of the
    ``manual_axes``? Taints are per-var sets of axis names."""
    env = {}

    def read(v):
        return env.get(v, frozenset()) if hasattr(v, "count") else frozenset()

    for var, t in zip(jaxpr.invars, in_taints):
        env[var] = frozenset(t)
    for cv in jaxpr.constvars:
        env[cv] = frozenset()
    for eqn in jaxpr.eqns:
        pname = eqn.primitive.name
        in_taint = frozenset().union(*[read(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        axes = set(_axis_names(eqn))
        if pname == "axis_index":
            out_taint = in_taint | (axes & manual_axes)
        elif pname in _INVARIANT_MAKERS and axes:
            out_taint = in_taint - axes
        elif pname == "scan":
            out_taint = _taint_scan(eqn, read, manual_axes)
            for v, t in zip(eqn.outvars, out_taint):
                env[v] = t
            continue
        elif pname == "while":
            outs = _taint_while(eqn, read, manual_axes)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue
        elif pname == "cond":
            branch_outs = [
                _taint_jaxpr(b.jaxpr, [read(v) for v in eqn.invars[1:]],
                             manual_axes)
                for b in eqn.params["branches"]]
            pred_taint = read(eqn.invars[0])
            for v, ts in zip(eqn.outvars, zip(*branch_outs)):
                env[v] = frozenset().union(pred_taint, *ts)
            continue
        elif pname in ("pjit", "closed_call", "core_call", "remat_call",
                       "custom_jvp_call", "custom_vjp_call", "checkpoint",
                       "remat"):
            inner = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    inner = eqn.params[key]
                    break
            if inner is not None:
                ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                outs = _taint_jaxpr(ij, [read(v) for v in eqn.invars],
                                    manual_axes)
                for v, t in zip(eqn.outvars, outs):
                    env[v] = t
                continue
            out_taint = in_taint
        else:
            out_taint = in_taint
        for v in eqn.outvars:
            env[v] = out_taint
    return [read(v) for v in jaxpr.outvars]


def _taint_while(eqn, read, manual_axes):
    """Fixpoint taint for a while_loop: recurse into the body (taint can
    be INTRODUCED inside it — axis_index in the body escapes a
    pass-through analysis), grow carry taints until stable, and if the
    COND is shard-varying the trip count diverges, tainting every carry."""
    cond_j = eqn.params["cond_jaxpr"].jaxpr
    body_j = eqn.params["body_jaxpr"].jaxpr
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    cconsts = [read(v) for v in eqn.invars[:cn]]
    bconsts = [read(v) for v in eqn.invars[cn:cn + bn]]
    carry = [read(v) for v in eqn.invars[cn + bn:]]
    for _ in range(len(carry) + 2):
        outs = _taint_jaxpr(body_j, bconsts + carry, manual_axes)
        new_carry = [c | o for c, o in zip(carry, outs)]
        if new_carry == carry:
            break
        carry = new_carry
    cond_out = _taint_jaxpr(cond_j, cconsts + carry, manual_axes)
    if cond_out and cond_out[0]:
        carry = [c | cond_out[0] for c in carry]
    return carry


def _taint_scan(eqn, read, manual_axes):
    """Fixpoint taint for a scan: carry taints grow until stable."""
    body = eqn.params["jaxpr"].jaxpr
    nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
    consts = [read(v) for v in eqn.invars[:nc]]
    carry = [read(v) for v in eqn.invars[nc:nc + ncar]]
    xs = [read(v) for v in eqn.invars[nc + ncar:]]
    for _ in range(ncar + 2):
        outs = _taint_jaxpr(body, consts + carry + xs, manual_axes)
        new_carry = [c | o for c, o in zip(carry, outs[:ncar])]
        if new_carry == carry:
            break
        carry = new_carry
    outs = _taint_jaxpr(body, consts + carry + xs, manual_axes)
    return [c | o for c, o in zip(carry, outs[:ncar])] + outs[ncar:]


def _spec_axes(spec) -> frozenset:
    """Mesh axes a shard_map in/out ``PartitionSpec`` shards over."""
    return frozenset(ax for entry in spec if entry is not None
                     for ax in (entry if isinstance(entry, tuple) else (entry,)))


def check_collectives(prog: TracedProgram) -> List[Finding]:
    err = _trace_failure(prog)
    if err is not None:
        msg = str(err)
        if isinstance(err, NameError) or "axis name" in msg \
                or "unbound" in msg:
            return [Finding(
                "GL003", JAXPR_PATH, 0,
                f"tracing aborts binding a collective axis: {msg} — a "
                "psum/ppermute/all_gather names an axis no enclosing "
                "mesh defines", context=prog.name)]
        return []
    findings = []
    for eqn, _ in _walk_eqns(_closed(prog.traced()).jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        manual = set(eqn.params["manual_axes"])
        body = eqn.params["jaxpr"]
        body = body.jaxpr if hasattr(body, "jaxpr") else body
        # (a) axis existence + (b) ppermute permutation validity
        for inner, _ in _walk_eqns(body):
            for ax in _axis_names(inner):
                if ax not in manual:
                    findings.append(Finding(
                        "GL003", JAXPR_PATH, 0,
                        f"`{inner.primitive.name}` names axis '{ax}' "
                        f"which is not manual on the enclosing shard_map "
                        f"mesh (manual axes: {sorted(manual)})",
                        context=prog.name))
            if inner.primitive.name == "ppermute":
                perm = list(inner.params.get("perm", ()))
                srcs = [s for s, _ in perm]
                dsts = [d for _, d in perm]
                if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
                    findings.append(Finding(
                        "GL003", JAXPR_PATH, 0,
                        f"ppermute perm {perm} repeats a source or "
                        "target shard — not a permutation (data is "
                        "dropped or double-delivered)", context=prog.name))
                elif set(srcs) != set(dsts):
                    findings.append(Finding(
                        "GL003", JAXPR_PATH, 0,
                        f"ppermute perm {perm} has senders and receivers "
                        "that are not the same shard set — a ring "
                        "exchange built from this loses chunks",
                        context=prog.name))
        # (c) replicated-declared outputs must be replica-invariant
        in_taints = [_spec_axes(spec) & manual
                     for spec in eqn.params["in_specs"]]
        out_taints = _taint_jaxpr(body, in_taints, manual)
        for i, (spec, taint) in enumerate(
                zip(eqn.params["out_specs"], out_taints)):
            declared = _spec_axes(spec)
            leaked = taint - declared
            if not declared and leaked:
                findings.append(Finding(
                    "GL003", JAXPR_PATH, 0,
                    f"shard_map output {i} is declared REPLICATED but is "
                    f"shard-varying over {sorted(leaked)} by dataflow "
                    "(derives from a sharded input or axis_index with no "
                    "collective reduction in between) — with "
                    "check_vma=False this silently returns shard 0's "
                    "value", context=prog.name))
    return findings


# ---------------------------------------------------------------------------
# GL004 — RetraceBudget
# ---------------------------------------------------------------------------

def check_retrace(prog: TracedProgram) -> List[Finding]:
    if prog.retrace is None or _trace_failure(prog) is not None:
        return []
    try:
        first = str(_closed(prog.traced()))       # cached first trace
        second = str(_closed(prog.retrace()))     # fresh build + trace
        if first != second:
            # jax's pretty printer hoists a pjit sub-jaxpr (jnp.where,
            # floor_divide, ...) into a shared ``let _whereN = .. in``
            # binding only when its call sites reuse the SAME cached
            # jaxpr object, and whether they do depends on global
            # tracing-cache LRU state left behind by whatever else the
            # registry traced in between — so two semantically identical
            # traces can print differently on cache warmth alone.
            # Confirm on a level playing field: two fresh traces, each
            # from a cold tracing cache. Real offenders (counters,
            # dict/set order, wall-clock constants) still diverge
            # cold-vs-cold; printer-sharing artifacts do not.
            import jax
            jax.clear_caches()
            first = str(_closed(prog.retrace()))
            jax.clear_caches()
            second = str(_closed(prog.retrace()))
    except Exception as e:               # noqa: BLE001
        return [Finding(
            "GL004", JAXPR_PATH, 0,
            f"re-trace failed ({type(e).__name__}: {e}) — the entry "
            "point cannot be traced reproducibly", context=prog.name)]
    if first == second:
        return []
    diff_at = next((i for i, (a, b) in enumerate(
        zip(first.splitlines(), second.splitlines())) if a != b), None)
    detail = ("lengths differ" if diff_at is None
              else f"first divergence at jaxpr line {diff_at}")
    return [Finding(
        "GL004", JAXPR_PATH, 0,
        "two traces with identical bucket-compatible shapes produced "
        f"DIFFERENT jaxprs ({detail}): the jit cache key cannot be "
        "stable, so production pays a retrace per call — trace-time "
        "state (counters, dict/set iteration order, fresh closures) is "
        "leaking into the program", context=prog.name)]


ALL_JAXPR_CHECKS = (check_transfer, check_donation, check_collectives,
                    check_retrace)


def check_variant_program(prog: TracedProgram) -> List[Finding]:
    """GL001/GL002 (+ loud GL000) for the cost registry's non-default
    collective lowerings: GL003's taint pass cannot prove the ppermute
    ring replica-invariant (ring algebra, not local dataflow) and GL004
    is already pinned by the exact twin of the same entry point, so the
    variant twins run the transfer/donation checks only."""
    out = check_transfer(prog) + check_donation(prog)
    return _with_gl000(prog, out)


def check_program(prog: TracedProgram) -> List[Finding]:
    out: List[Finding] = []
    for check in ALL_JAXPR_CHECKS:
        out.extend(check(prog))
    return _with_gl000(prog, out)


def _with_gl000(prog: TracedProgram, out: List[Finding]) -> List[Finding]:
    err = _trace_failure(prog)
    if err is not None and not out:
        # the trace died for a reason no rule classifies (signature drift,
        # bad registry shapes, ...): a silent [] here would report "clean"
        # for a program that was never analyzed — fail loud; the innermost
        # repo traceback frame makes the abort debuggable from JSON output
        out.append(Finding(
            "GL000", JAXPR_PATH, 0,
            f"tracing failed at {failure_frame(err)} with {err!r} — the "
            "jaxpr checks (GL001-GL004) did not run for this program",
            context=prog.name))
    return out
