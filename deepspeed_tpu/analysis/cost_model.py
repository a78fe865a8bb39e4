"""Family C: graft-cost — a static jaxpr cost model for the serving stack.

The CPU virtual mesh can prove token-parity but not speed: SERVING_r08's
per-chip ratio measures sharding *overhead*, so every performance claim the
serving stack makes (T3 ring overlap, EQuARX int8 exchanges, O(batch)
boundaries) was enforced only by tolerance tests. This pass makes the
traced serving programs a *quantitative* contract: it interprets each
program's ClosedJaxpr into a :class:`CostReport` — matmul FLOPs, HBM bytes,
per-axis collective wire bytes, frame-boundary D2H bytes — and gates four
rules on the result:

- **GL201 cost-regression** — every metric of every program is compared
  against the committed ``.graft-cost-baseline.json``; drift beyond
  tolerance (either direction — growth is a regression, shrink is a stale
  baseline) fails. Updating the baseline is an explicit
  ``--update-cost-baseline``, and the diff belongs in the PR description.
- **GL202 collective-lowering contract** — the ``tp_quantized_collectives``
  program's int8 wire bytes must be <= 0.5x the exact program's total
  collective payload (+ f32 scales), and the ``tp_overlap_collectives``
  ring program's total wire bytes must EQUAL the exact program's
  (2(N-1) ppermute chunks x chunk bytes == the psum's ring cost) — the
  arXiv 2506.17615 / 2401.16677 claims proven statically, per program.
- **GL203 boundary-transfer budget** — the bytes the host reads back per
  frame (``HOST_READ_OUTPUTS``) must fit the emission stream plus
  O(batch) per-row lanes: nothing a frame returns to the host may scale
  with sequence length, vocab, or pool size. The dynamic transfer guard
  proves zero D2H happens *inside* a frame; this rule bounds the SIZE of
  what crosses at the boundary.
- **GL204 redundant collectives** — the same operand reduced twice over
  the same axis, a collective applied to an already replica-invariant
  value, or an all-gather whose result is summed straight back down:
  N x the wire bytes for a value one collective computes.

Counting rules (the golden-value tests in ``tests/test_cost_model.py`` pin
these exactly — change them only together):

- **FLOPs** count ``dot_general``/``conv_general_dilated`` only
  (2 x batch x M x N x K): the roofline numerator. Elementwise work is
  deliberately excluded.
- **HBM bytes** are modeled per eqn as operand bytes read + result bytes
  written, times the eqn's execution multiplicity (the product of
  enclosing scan trip counts). A buffer is charged at the multiplicity it
  was *produced* at, so loop-invariant inputs — the params, a scan's
  consts and stacked xs — are charged ONCE per frame while carries (the
  KV pools) are charged per step: the scan-carry analysis behind "param
  bytes count once per frame".
- **Collective payload** is the wire bytes each device SENDS under the
  standard ring schedule: ``psum`` = 2(N-1)/N x bytes, ``all_gather`` =
  (N-1) x shard bytes, ``reduce_scatter``/``all_to_all`` = (N-1)/N x
  bytes, ``ppermute`` = bytes. This (not "operand bytes") is what makes
  GL202's identities exact: a psum decomposed into 2(N-1) ppermute chunks
  of bytes/N costs the same wire as the psum itself.
- Inside ``shard_map`` avals are per-shard, so every metric is PER DEVICE.
- ``while_loop`` trip counts are unknown statically: the body is charged
  once and ``unbounded_loops`` is flagged in the report. The exception is
  a counted loop whose bound is an operand (a serving frame's ``n_steps``,
  ``model_runner._run_steps``): the registry names the trips it traced the
  program for (``TracedProgram.loop_trips``, the frame's capacity) and the
  loop is charged as a scan of that length.
- ``cond`` branches charge the elementwise MAX across branches.

Like the findings baseline, the cost baseline is content-addressed per
program: keyed by registry name (which encodes shape bucket, tp degree and
lowering variant), never by source position.
"""

import dataclasses
import json
import math
import os
import re
from typing import Dict, List, Optional, Sequence

from .findings import Finding
from .jaxpr_checks import (JAXPR_PATH, TracedProgram, _axis_names, _closed,
                           _trace_failure)

COST_BASELINE_VERSION = 1
#: the committed ledger at the repo root (three levels up from analysis/)
COST_BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".graft-cost-baseline.json")
#: relative drift per metric before GL201 fires. Static costs are exact —
#: the tolerance only absorbs deliberate tiny-constant churn (a new stat
#: lane, one more boundary flag), not real growth.
DEFAULT_TOLERANCE = 0.02

#: wire bytes each device sends, as a fraction of operand bytes, under the
#: standard ring schedule (N = product of the named axis sizes)
_WIRE_FACTOR = {
    "psum": lambda n: 2 * (n - 1) / n,
    "pmax": lambda n: 2 * (n - 1) / n,
    "pmin": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: n - 1,          # operand = the local shard
    "reduce_scatter": lambda n: (n - 1) / n,
    "psum_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "pbroadcast": lambda n: 1.0,
}

_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:     # tokens etc.
        return 0
    return int(math.prod(shape)) * dtype.itemsize


def _is_literal(v) -> bool:
    return not hasattr(v, "count")         # jax.core.Literal has no .count


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------


class _Measurer:
    """One pass over a ClosedJaxpr accumulating the cost metrics.

    ``env`` maps each Var to the multiplicity it was PRODUCED at; a read
    is charged at ``min(birth, reader multiplicity)``, which is what makes
    loop-invariant operands (scan consts/xs — the params) count once per
    frame while carries count per step."""

    def __init__(self, loop_trips: Optional[int] = None):
        self.loop_trips = loop_trips
        self.flops = 0
        self.hbm_read = 0.0
        self.hbm_write = 0.0
        self.coll_ops: Dict[str, int] = {}
        self.coll_payload: Dict[str, float] = {}
        self.payload_by_dtype: Dict[str, float] = {}
        self.unbounded_loops = 0

    # -- var bookkeeping ----------------------------------------------------

    def _birth(self, env, v, mult):
        if _is_literal(v):
            return mult
        return env.get(v, mult)

    def _charge_reads(self, env, invars, mult):
        self.hbm_read += sum(
            _aval_bytes(v.aval) * min(self._birth(env, v, mult), mult)
            for v in invars)

    def _bind(self, env, outvars, mult):
        for v in outvars:
            env[v] = mult

    # -- entry --------------------------------------------------------------

    def measure(self, closed):
        jaxpr = closed.jaxpr
        env = {}
        for v in jaxpr.invars:
            env[v] = 1
        for v in jaxpr.constvars:
            env[v] = 1
        self._walk(jaxpr, env, 1, {})

    def _walk(self, jaxpr, env, mult, axis_sizes):
        for cv in jaxpr.constvars:
            env.setdefault(cv, 1)
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            if p == "scan":
                self._scan(eqn, env, mult, axis_sizes)
            elif p == "while":
                self._while(eqn, env, mult, axis_sizes)
            elif p == "cond":
                self._cond(eqn, env, mult, axis_sizes)
            elif p == "shard_map":
                self._shard_map(eqn, env, mult, axis_sizes)
            elif any(hasattr(eqn.params.get(k), "jaxpr")
                     or hasattr(eqn.params.get(k), "eqns")
                     for k in _CALL_JAXPR_KEYS):
                self._call(eqn, env, mult, axis_sizes)
            else:
                self._leaf(eqn, env, mult, axis_sizes)

    # -- structured primitives ----------------------------------------------

    def _scan(self, eqn, env, mult, axis_sizes):
        trip = int(eqn.params["length"])
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        # consts + stacked xs are consumed once per scan EXECUTION — the
        # "params count once per frame" rule; the init carry is charged by
        # the first iteration's body read
        self._charge_reads(env, eqn.invars[:nc], mult)
        self._charge_reads(env, eqn.invars[nc + ncar:], mult)
        body = eqn.params["jaxpr"].jaxpr
        benv = dict(env)
        bviews = body.invars
        for bv in bviews[:nc]:
            benv[bv] = 0                   # already charged at the eqn
        for bv in bviews[nc:nc + ncar]:
            benv[bv] = mult * trip         # a fresh carry every iteration
        for bv in bviews[nc + ncar:]:
            benv[bv] = 0                   # the stacked xs were charged once
        self._walk(body, benv, mult * trip, axis_sizes)
        self._bind(env, eqn.outvars, mult)

    def _while(self, eqn, env, mult, axis_sizes):
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond = eqn.params["cond_jaxpr"].jaxpr
        body = eqn.params["body_jaxpr"].jaxpr
        if self.loop_trips is not None and _is_counted(cond):
            # a counted loop whose bound is an OPERAND (a serving frame's
            # ``n_steps``): charged as a scan of the trips the registry
            # names — consts once an execution, a fresh carry every trip
            trip = self.loop_trips
            self._charge_reads(env, eqn.invars[:cn + bn], mult)
            for inner, nconsts in ((cond, cn), (body, bn)):
                benv = dict(env)
                for bv in inner.invars[:nconsts]:
                    benv[bv] = 0           # already charged at the eqn
                for bv in inner.invars[nconsts:]:
                    benv[bv] = mult * trip
                self._walk(inner, benv, mult * trip, axis_sizes)
            self._bind(env, eqn.outvars, mult)
            return
        # trip count is dynamic and nothing names it: charge ONE trip and
        # flag it — a serving program should never contain such a loop, so
        # the report makes it visible
        self.unbounded_loops += 1
        self._charge_reads(env, eqn.invars, mult)
        for inner in (cond, body):
            benv = dict(env)
            for bv in inner.invars:
                benv[bv] = 0
            self._walk(inner, benv, mult, axis_sizes)
        self._bind(env, eqn.outvars, mult)

    def _cond(self, eqn, env, mult, axis_sizes):
        self._charge_reads(env, eqn.invars, mult)
        branch_costs = []
        for br in eqn.params["branches"]:
            sub = _Measurer(self.loop_trips)
            benv = {}
            for bv, ov in zip(br.jaxpr.invars, eqn.invars[1:]):
                benv[bv] = 0               # operands charged at the eqn
            sub._walk(br.jaxpr, benv, mult, axis_sizes)
            branch_costs.append(sub)
        self._merge_max(branch_costs)
        self._bind(env, eqn.outvars, mult)

    def _merge_max(self, subs: Sequence["_Measurer"]):
        if not subs:
            return
        self.flops += max(s.flops for s in subs)
        self.hbm_read += max(s.hbm_read for s in subs)
        self.hbm_write += max(s.hbm_write for s in subs)
        self.unbounded_loops += max(s.unbounded_loops for s in subs)
        for attr in ("coll_ops", "coll_payload", "payload_by_dtype"):
            mine = getattr(self, attr)
            for key in {k for s in subs for k in getattr(s, attr)}:
                mine[key] = mine.get(key, 0) + max(
                    getattr(s, attr).get(key, 0) for s in subs)

    def _shard_map(self, eqn, env, mult, axis_sizes):
        mesh = eqn.params["mesh"]
        sizes = {**axis_sizes,
                 **{name: int(size) for name, size in
                    zip(mesh.axis_names, mesh.devices.shape)}}
        body = eqn.params["jaxpr"]
        body = body.jaxpr if hasattr(body, "jaxpr") else body
        benv = dict(env)
        for bv, ov in zip(body.invars, eqn.invars):
            benv[bv] = self._birth(env, ov, mult)
        self._walk(body, benv, mult, sizes)
        self._bind(env, eqn.outvars, mult)

    def _call(self, eqn, env, mult, axis_sizes):
        inner = next(eqn.params[k] for k in _CALL_JAXPR_KEYS
                     if k in eqn.params)
        body = inner.jaxpr if hasattr(inner, "jaxpr") else inner
        benv = dict(env)
        for bv, ov in zip(body.invars, eqn.invars):
            benv[bv] = self._birth(env, ov, mult)
        self._walk(body, benv, mult, axis_sizes)
        self._bind(env, eqn.outvars, mult)

    # -- leaf primitives ----------------------------------------------------

    def _leaf(self, eqn, env, mult, axis_sizes):
        p = eqn.primitive.name
        self._charge_reads(env, eqn.invars, mult)
        self.hbm_write += sum(_aval_bytes(v.aval) for v in eqn.outvars) * mult
        if p == "dot_general":
            self.flops += self._dot_flops(eqn) * mult
        elif p == "conv_general_dilated":
            self.flops += self._conv_flops(eqn) * mult
        if p in _WIRE_FACTOR:
            self._collective(eqn, mult, axis_sizes)
        self._bind(env, eqn.outvars, mult)

    @staticmethod
    def _dot_flops(eqn) -> int:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = math.prod(lhs[i] for i in lb)
        contract = math.prod(lhs[i] for i in lc)
        m = math.prod(lhs[i] for i in range(len(lhs))
                      if i not in set(lb) | set(lc))
        n = math.prod(rhs[i] for i in range(len(rhs))
                      if i not in set(rb) | set(rc))
        return 2 * batch * m * n * contract

    @staticmethod
    def _conv_flops(eqn) -> int:
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval.shape
        out = eqn.outvars[0].aval.shape
        groups = eqn.params.get("feature_group_count", 1)
        spatial = math.prod(rhs[i] for i in dn.rhs_spec[2:])
        in_ch = rhs[dn.rhs_spec[1]]
        return 2 * math.prod(out) * in_ch * spatial // max(groups, 1)

    def _collective(self, eqn, mult, axis_sizes):
        axes = [ax for ax in _axis_names(eqn) if ax in axis_sizes]
        if not axes:
            return
        n = math.prod(axis_sizes[ax] for ax in axes)
        if n <= 1:
            return
        operand_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
        payload = _WIRE_FACTOR[eqn.primitive.name](n) * operand_bytes * mult
        key = "+".join(sorted(axes))
        self.coll_ops[key] = self.coll_ops.get(key, 0) + mult
        self.coll_payload[key] = self.coll_payload.get(key, 0) + payload
        dt = str(eqn.invars[0].aval.dtype)
        self.payload_by_dtype[dt] = self.payload_by_dtype.get(dt, 0) + payload


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

#: program base name -> flat output indices the HOST materializes at the
#: frame boundary (np.asarray in _run_frame_resilient / stats_delta /
#: nonfinite_uids / resync_committed). Maintained exactly like
#: ast_checks.DISPATCH_DONATIONS: tests/test_cost_model.py cross-checks
#: shapes against the live traces so a loop that grows an output cannot
#: silently rot the table.
HOST_READ_OUTPUTS: Dict[str, Sequence[int]] = {
    # (toks, emit, cached, produced, last_tok, done, poison, nonfinite,
    #  stats, rng, k, v)
    "frame_loop": (0, 1, 2, 7, 8),
    # (toks, emit, cached, produced, last_tok, penult, done, poison,
    #  nonfinite, stats, rng, k, v, dk, dv)
    "frame_loop_spec": (0, 1, 2, 8, 9),
    "run": (0,),                           # host-step path reads its logits
    "copy_blocks": (),                     # donated pools only
    "scatter_pages": (),
    "gather_pages": (0, 1),                # swap-out D2H-reads the pages
}

#: the frame loops carry the GL203 budget; `run` (the chunked host-step
#: path reads (B, V) logits by contract) and the page movers (gather_pages
#: IS a bulk D2H, that's its job) are reported but not gated
D2H_BUDGET_SCOPE = ("frame_loop", "frame_loop_spec")

#: bytes of per-row boundary lanes GL203 allows beyond the emission stream
#: (cached/produced watermarks, latches, a stats row): 16 int32 lanes. The
#: flat slack stays SMALL relative to the tiny registry shapes (B=4) so a
#: seq-len-scaled leak of even a few hundred bytes per row still trips the
#: budget at lint scale, not just at production scale.
_D2H_ROW_ALLOWANCE = 64
_D2H_SLACK = 128


@dataclasses.dataclass
class CostReport:
    """Per-device static cost of one traced serving program."""
    name: str
    variant: str
    counterpart: str
    flops: int
    hbm_read: int
    hbm_write: int
    d2h_bytes: int
    coll_ops: Dict[str, int]
    coll_payload: Dict[str, int]           # mesh axis -> wire bytes
    payload_by_dtype: Dict[str, int]
    unbounded_loops: int = 0

    @property
    def total_payload(self) -> int:
        return sum(self.coll_payload.values())

    @property
    def int8_payload(self) -> int:
        """One-byte quantized wire bytes: int8 AND fp8 (e4m3/e5m2)
        collective operands — both payload formats of the quantized
        lowering, identical width, so GL202's <=0.5x-of-exact contract
        applies to either. The metric keeps its historical
        ``collective_payload_int8`` name (baseline schema)."""
        return sum(v for k, v in self.payload_by_dtype.items()
                   if k == "int8" or k.startswith("float8"))

    def metrics(self) -> Dict[str, int]:
        """The flat metric dict GL201 diffs against the baseline."""
        return {
            "flops": self.flops,
            "hbm_read": self.hbm_read,
            "hbm_write": self.hbm_write,
            "d2h_bytes": self.d2h_bytes,
            "collective_ops": sum(self.coll_ops.values()),
            "collective_payload": self.total_payload,
            "collective_payload_int8": self.int8_payload,
        }

    def as_json(self) -> Dict:
        return {"name": self.name, "variant": self.variant,
                **self.metrics(),
                "collectives_by_axis": dict(sorted(self.coll_payload.items())),
                "payload_by_dtype": dict(sorted(
                    self.payload_by_dtype.items())),
                "unbounded_loops": self.unbounded_loops}


def _base_name(name: str) -> str:
    return name.split("[")[0]


def _is_counted(cond) -> bool:
    """A ``while``'s condition is ``counter < bound`` and nothing else."""
    return len(cond.eqns) == 1 and cond.eqns[0].primitive.name == "lt"


def measure_jaxpr(closed, loop_trips: Optional[int] = None) -> _Measurer:
    m = _Measurer(loop_trips)
    m.measure(closed)
    return m


def measure_program(prog: TracedProgram) -> Optional[CostReport]:
    """Interpret one traced program into a CostReport; ``None`` when the
    trace fails (GL000 from the jaxpr family already owns that)."""
    if _trace_failure(prog) is not None:
        return None
    closed = _closed(prog.traced())
    m = measure_jaxpr(closed, prog.loop_trips)
    reads = HOST_READ_OUTPUTS.get(_base_name(prog.name), ())
    out_avals = list(closed.out_avals)
    d2h = sum(_aval_bytes(out_avals[i]) for i in reads
              if i < len(out_avals))
    return CostReport(
        name=prog.name, variant=prog.variant,
        counterpart=prog.counterpart, flops=int(m.flops),
        hbm_read=int(round(m.hbm_read)), hbm_write=int(round(m.hbm_write)),
        d2h_bytes=int(d2h),
        coll_ops={k: int(v) for k, v in sorted(m.coll_ops.items())},
        coll_payload={k: int(round(v))
                      for k, v in sorted(m.coll_payload.items())},
        payload_by_dtype={k: int(round(v))
                          for k, v in sorted(m.payload_by_dtype.items())},
        unbounded_loops=m.unbounded_loops)


# ---------------------------------------------------------------------------
# GL201 — cost regression vs the committed baseline
# ---------------------------------------------------------------------------


def load_cost_baseline(path: str) -> Dict:
    with open(path) as fh:
        data = json.load(fh)
    if data.get("version") != COST_BASELINE_VERSION:
        raise ValueError(f"{path}: unrecognized cost-baseline version "
                         f"{data.get('version')!r}")
    return data


def write_cost_baseline(path: str, reports: List[CostReport],
                        tolerance: float = DEFAULT_TOLERANCE) -> None:
    data = {"version": COST_BASELINE_VERSION, "tolerance": tolerance,
            "programs": {r.name: r.metrics()
                         for r in sorted(reports, key=lambda r: r.name)}}
    with open(path, "w") as fh:
        json.dump(data, fh, indent=2, sort_keys=True)
        fh.write("\n")


def check_cost_baseline(reports: List[CostReport], baseline: Dict,
                        include_tp: bool = True) -> List[Finding]:
    """GL201: every metric of every program within tolerance of the
    committed baseline — growth is a regression, shrink is a stale
    baseline; both need an explicit ``--update-cost-baseline``."""
    tol = float(baseline.get("tolerance", DEFAULT_TOLERANCE))
    base = baseline.get("programs", {})
    findings = []
    seen = set()
    for r in reports:
        seen.add(r.name)
        b = base.get(r.name)
        if b is None:
            findings.append(Finding(
                "GL201", JAXPR_PATH, 0,
                "program has no cost-baseline entry — a new serving "
                "program lands with its costs recorded "
                "(--update-cost-baseline) so the next PR diffs against "
                "them", context=r.name))
            continue
        for key, val in r.metrics().items():
            bval = b.get(key)
            if bval is None:
                findings.append(Finding(
                    "GL201", JAXPR_PATH, 0,
                    f"metric '{key}' missing from the cost baseline — "
                    "re-record with --update-cost-baseline",
                    context=r.name))
                continue
            if abs(val - bval) > tol * max(abs(bval), 1):
                direction = "grew" if val > bval else "shrank"
                pct = (100.0 * (val - bval) / bval) if bval else float("inf")
                findings.append(Finding(
                    "GL201", JAXPR_PATH, 0,
                    f"{key} {direction} beyond tolerance: baseline {bval}, "
                    f"now {val} ({pct:+.1f}%, tolerance "
                    f"{tol:.1%}) — explain the change in the PR and "
                    "re-record with --update-cost-baseline",
                    context=r.name))
    for name in sorted(set(base) - seen):
        if not include_tp and "[tp=8" in name:
            continue            # --no-tp run: tp entries legitimately absent
        findings.append(Finding(
            "GL201", JAXPR_PATH, 0,
            "stale cost-baseline entry: program is no longer traced by the "
            "registry — remove it with --update-cost-baseline (or restore "
            "its registration)", context=name))
    return findings


# ---------------------------------------------------------------------------
# GL202 — quantized / overlap payload contracts
# ---------------------------------------------------------------------------


def check_collective_contracts(reports: List[CostReport]) -> List[Finding]:
    by_name = {r.name: r for r in reports}
    findings = []
    for r in reports:
        if r.variant == "exact":
            continue
        exact = by_name.get(r.counterpart)
        if exact is None:
            findings.append(Finding(
                "GL202", JAXPR_PATH, 0,
                f"{r.variant} variant has no exact counterpart in the "
                "registry — the payload contract cannot be checked",
                context=r.name))
            continue
        etotal = exact.total_payload
        if r.variant == "quantized":
            findings.extend(_check_quantized(r, exact, etotal))
        elif r.variant == "overlap":
            findings.extend(_check_overlap(r, exact, etotal))
    return findings


def _check_quantized(r: CostReport, exact: CostReport,
                     etotal: int) -> List[Finding]:
    out = []
    if r.int8_payload == 0:
        out.append(Finding(
            "GL202", JAXPR_PATH, 0,
            "tp_quantized_collectives is set but the traced program "
            "exchanges no int8 payload — the flag is dead weight",
            context=r.name))
        return out
    if etotal and r.int8_payload > 0.5 * etotal:
        out.append(Finding(
            "GL202", JAXPR_PATH, 0,
            f"int8 wire bytes {r.int8_payload} exceed 0.5x the exact "
            f"program's total collective payload ({etotal}): the "
            "quantized lowering moves more than half the traffic it "
            "claims to halve (ratio "
            f"{r.int8_payload / etotal:.3f})", context=r.name))
    if etotal and r.total_payload >= etotal:
        out.append(Finding(
            "GL202", JAXPR_PATH, 0,
            f"total collective payload {r.total_payload} (int8 "
            f"{r.int8_payload} + scales/exact remainder "
            f"{r.total_payload - r.int8_payload}) is not below the exact "
            f"program's {etotal}: quantization buys no net traffic",
            context=r.name))
    return out


def _check_overlap(r: CostReport, exact: CostReport,
                   etotal: int) -> List[Finding]:
    # the T3 ring must carry EXACTLY the exact psum's wire bytes:
    # 2(N-1) ppermute hops x (bytes/N) chunks == 2(N-1)/N x bytes. More
    # means redundant chunks; less means the ring drops data.
    if math.isclose(r.total_payload, etotal, rel_tol=1e-9, abs_tol=8):
        return []
    return [Finding(
        "GL202", JAXPR_PATH, 0,
        f"ring-overlap total wire bytes {r.total_payload} != exact "
        f"program's {etotal}: the 2(N-1)-chunk ppermute decomposition no "
        "longer carries the full all-reduce payload (a chunking bug — "
        "too many hops, or dropped chunks)", context=r.name)]


# ---------------------------------------------------------------------------
# GL203 — boundary D2H budget
# ---------------------------------------------------------------------------


def check_d2h_budget(report: CostReport, prog: TracedProgram
                     ) -> List[Finding]:
    base = _base_name(report.name)
    if base not in D2H_BUDGET_SCOPE or _trace_failure(prog) is not None:
        return []
    out_avals = list(_closed(prog.traced()).out_avals)
    reads = HOST_READ_OUTPUTS[base]
    if any(i >= len(out_avals) for i in reads):
        return [Finding(
            "GL203", JAXPR_PATH, 0,
            f"HOST_READ_OUTPUTS indexes output {max(reads)} but the "
            f"program has {len(out_avals)} outputs — the table drifted "
            "from the loop's return signature", context=report.name)]
    toks = out_avals[0]
    batch = toks.shape[1] if len(toks.shape) > 1 else 1
    stream = _aval_bytes(toks)
    if len(reads) > 1 and 1 in reads:
        stream += _aval_bytes(out_avals[1])          # the emit mask
    budget = stream + _D2H_ROW_ALLOWANCE * batch + _D2H_SLACK
    if report.d2h_bytes <= budget:
        return []
    return [Finding(
        "GL203", JAXPR_PATH, 0,
        f"host-read outputs total {report.d2h_bytes} bytes per frame, over "
        f"the boundary budget of {budget} (emission stream {stream} + "
        f"{_D2H_ROW_ALLOWANCE}/row x {batch} rows + {_D2H_SLACK} slack): "
        "a host-read output scales with something other than the batch — "
        "sequence length, vocab, or pool size crossing the boundary every "
        "frame", context=report.name)]


# ---------------------------------------------------------------------------
# GL204 — redundant collectives
# ---------------------------------------------------------------------------

#: value-preserving ops a gathered result may pass through before a
#: reduction still counts as "immediately reduced" (exp/softmax chains are
#: deliberately NOT here: a softmax over gathered logits is legitimate)
_PASSTHROUGH = {"convert_element_type", "mul", "add", "sub", "neg",
                "reshape", "transpose", "broadcast_in_dim"}
_MAX_CHAIN = 3


def check_redundant_collectives(prog: TracedProgram) -> List[Finding]:
    if _trace_failure(prog) is not None:
        return []
    findings: List[Finding] = []
    _scan_redundant(_closed(prog.traced()).jaxpr, prog.name, findings, {})
    return findings


def _scan_redundant(jaxpr, prog_name: str, findings: List[Finding],
                    axis_sizes: Dict[str, int]) -> None:
    seen_psums = set()              # (operand var, axes) already reduced
    invariant = {}                  # var -> axes it is replica-invariant over
    gather_chain = {}               # var -> (hops since all_gather, degree N)
    for eqn in jaxpr.eqns:
        p = eqn.primitive.name
        if p == "shard_map":
            mesh = eqn.params["mesh"]
            axis_sizes = {**axis_sizes,
                          **{name: int(size) for name, size in
                             zip(mesh.axis_names, mesh.devices.shape)}}
        axes = frozenset(_axis_names(eqn))
        if p == "psum" and axes:
            for v in eqn.invars:
                if _is_literal(v):
                    continue
                key = (v, axes)
                if key in seen_psums:
                    findings.append(Finding(
                        "GL204", JAXPR_PATH, 0,
                        f"the same operand is psummed twice over axis "
                        f"{sorted(axes)} — one all-reduce computes it; the "
                        "second doubles the wire bytes for an identical "
                        "value", context=prog_name))
                seen_psums.add(key)
                if axes & invariant.get(v, frozenset()):
                    findings.append(Finding(
                        "GL204", JAXPR_PATH, 0,
                        f"psum over {sorted(axes)} of a value that is "
                        "already replica-invariant on that axis (the "
                        "output of a psum/all_gather): this multiplies by "
                        "the axis size — almost certainly a double-"
                        "reduction bug", context=prog_name))
        if p in ("psum", "pmax", "pmin", "all_gather") and axes:
            for o in eqn.outvars:
                invariant[o] = axes | invariant.get(o, frozenset())
        if p == "all_gather" and axes:
            degree = math.prod(axis_sizes.get(ax, 1) for ax in axes)
            if degree > 1:
                for o in eqn.outvars:
                    gather_chain[o] = (0, degree)
        elif p in _PASSTHROUGH:
            tagged = [gather_chain[v] for v in eqn.invars
                      if not _is_literal(v) and v in gather_chain]
            if tagged and min(t[0] for t in tagged) < _MAX_CHAIN:
                hops, degree = min(tagged)
                for o in eqn.outvars:
                    gather_chain[o] = (hops + 1, degree)
        elif p == "reduce_sum":
            # only a reduction that collapses the gather-degree extent is
            # the redundant shape — summing a gathered tensor over an
            # unrelated dim (a feature-dim norm, say) is legitimate
            for v in eqn.invars:
                if _is_literal(v) or v not in gather_chain:
                    continue
                _, degree = gather_chain[v]
                shape = getattr(v.aval, "shape", ())
                reduced = [shape[ax] for ax in eqn.params.get("axes", ())
                           if ax < len(shape)]
                if any(ext == degree for ext in reduced):
                    findings.append(Finding(
                        "GL204", JAXPR_PATH, 0,
                        "an all-gather's result is summed straight back "
                        "down (gather -> elementwise -> reduce_sum over "
                        "the gathered extent): this moves (N-1)x the "
                        "bytes of the reduce-scatter/psum that computes "
                        "the same value", context=prog_name))
        for sub in _subjaxprs_of(eqn):
            _scan_redundant(sub, prog_name, findings, axis_sizes)


def _subjaxprs_of(eqn):
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr


# ---------------------------------------------------------------------------
# the gate + the report table
# ---------------------------------------------------------------------------


def run_cost_checks(progs: List[TracedProgram],
                    baseline: Optional[Dict] = None,
                    include_tp: bool = True):
    """Family C in one call: measure every program, then GL201 (when a
    baseline is given), GL202, GL203, GL204. Returns (findings, reports).
    Programs that fail to trace yield no report — the jaxpr family's GL000
    owns surfacing that."""
    findings: List[Finding] = []
    reports: List[CostReport] = []
    for prog in progs:
        rep = measure_program(prog)
        if rep is None:
            continue
        reports.append(rep)
        findings.extend(check_d2h_budget(rep, prog))
        findings.extend(check_redundant_collectives(prog))
    findings.extend(check_collective_contracts(reports))
    if baseline is not None:
        findings.extend(check_cost_baseline(reports, baseline,
                                            include_tp=include_tp))
    return findings, reports


def render_cost_table(reports: List[CostReport]) -> str:
    """Markdown table of every program's cost metrics (``--cost-report``)."""
    headers = ("program", "flops", "hbm_read", "hbm_write",
               "coll_payload", "coll_ops", "d2h_bytes")
    rows = [headers, tuple("---" for _ in headers)]
    for r in sorted(reports, key=lambda r: r.name):
        rows.append((r.name, f"{r.flops:,}", f"{r.hbm_read:,}",
                     f"{r.hbm_write:,}", f"{r.total_payload:,}",
                     str(sum(r.coll_ops.values())), f"{r.d2h_bytes:,}"))
    widths = [max(len(row[i]) for row in rows) for i in range(len(headers))]
    return "\n".join(
        "| " + " | ".join(cell.ljust(w) for cell, w in zip(row, widths))
        + " |" for row in rows)


# ----------------------------------------------------------------------
# frame-cost QUERY API over a committed baseline (the simulator's
# price list: sim/ replays traffic with frame costs read from HERE —
# no frames executed)
# ----------------------------------------------------------------------

# metric keys every ledger entry carries (CostReport.metrics())
COST_METRIC_KEYS = ("flops", "hbm_read", "hbm_write", "d2h_bytes",
                    "collective_ops", "collective_payload",
                    "collective_payload_int8")


class FrameCostQuery:
    """Query API over one committed ``.graft-cost-baseline.json``.

    The ledger prices every traced serving program statically (GL201) —
    this class makes it QUERYABLE by frame shape instead of by exact
    program name: ``select(width=8, spec=True, tp=8, quant=True)``
    resolves to ``frame_loop_spec[w=8][tp=8,quant]`` and returns its
    FLOPs / HBM bytes / collective wire bytes. The trace-driven fleet
    simulator prices every virtual frame through here; a kernel change
    that shifts the ledger shifts the sim's capacity answers with it.
    """

    def __init__(self, baseline: Dict):
        if baseline.get("version") != COST_BASELINE_VERSION:
            raise ValueError(
                f"cost baseline version {baseline.get('version')!r} != "
                f"{COST_BASELINE_VERSION}")
        self.programs: Dict[str, Dict] = baseline["programs"]
        self._widths = sorted({
            int(m.group(1)) for name in self.programs
            for m in [re.search(r"\[w=(\d+)[,\]]", name)] if m})

    @classmethod
    def load(cls, path: str = COST_BASELINE_PATH) -> "FrameCostQuery":
        return cls(load_cost_baseline(path))

    def metrics(self, name: str) -> Dict[str, float]:
        """Ledger metrics for one exact program name (KeyError with the
        available names when absent — a renamed program must fail loudly,
        not price frames at zero)."""
        try:
            return self.programs[name]
        except KeyError:
            raise KeyError(
                f"program {name!r} not in the cost baseline; available: "
                f"{sorted(self.programs)}") from None

    def frame_program(self, *, width: int = 1, spec: bool = False,
                      tp: int = 1, quant: bool = False, fp8: bool = False,
                      ring: bool = False, repair: bool = False) -> str:
        """Resolve a frame SHAPE to the ledger's program name.

        ``width`` snaps to the nearest traced width bucket (the ledger
        traces one narrow and one wide frame_loop; chunked-prefill frames
        of any chunk size price from the wide bucket — the calibration
        layer in ``sim.cost`` scales by the actual width). Exactly one of
        the tp-variant flags (quant/fp8/ring) may be set with tp > 1."""
        if not self._widths:
            raise ValueError("cost baseline has no frame_loop[w=...] "
                             "programs to price frames from")
        w = min(self._widths, key=lambda b: (abs(b - width), b))
        base = "frame_loop_spec" if spec else "frame_loop"
        head = f"{base}[w={w},repair]" if repair else f"{base}[w={w}]"
        if tp > 1:
            variant = ("quant" if quant else "fp8" if fp8
                       else "ring" if ring else None)
            suffix = f"[tp={tp},{variant}]" if variant else f"[tp={tp}]"
        else:
            suffix = "[quant]" if quant else ""
        name = head + suffix
        if name not in self.programs and tp > 1:
            # heterogeneous ledgers may trace one tp degree only — fall
            # back to the traced tp suffix rather than KeyError on e.g.
            # tp=4 when only tp=8 was traced (the calibration constants
            # absorb the degree difference)
            tail = f",{variant}]" if variant else "]"
            cands = [n for n in self.programs
                     if n.startswith(head + "[tp=") and n.endswith(tail)
                     and (variant or "," not in n[len(head):])]
            if cands:
                name = sorted(cands)[0]
        return name

    def select(self, **shape) -> Dict[str, float]:
        """``metrics(frame_program(**shape))`` — the one-call form."""
        return self.metrics(self.frame_program(**shape))
