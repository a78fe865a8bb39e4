"""graft-cost (analysis Family C): the static jaxpr cost model's own suite.

Three layers, mirroring ``test_static_analysis.py``:

1. **Golden-value units** — the counting rules of ``cost_model`` pinned on
   hand-built jaxprs with exact expected numbers: a single ``dot_general``
   (FLOPs + HBM bytes), a ``psum`` on the 8-way mesh (ring wire bytes),
   and a 2-trip ``scan`` (consts charged once per frame, carries per
   step). Change a counting rule and these fail loudly with the arithmetic
   in front of you.
2. **Rule fixtures** — GL204 fires on the duplicated-psum /
   double-reduce / gather-then-reduce fixtures and stays silent on the
   clean twin; GL202/GL201/GL203 are exercised on synthetic reports and a
   doctored baseline, including the CLI exiting 1 on a cost regression.
3. **The repo gate** — every registered serving program (tp=1 AND tp=8,
   quantized and ring twins included) measures into a CostReport, the
   committed ``.graft-cost-baseline.json`` matches, the quantized program
   provably moves <= 0.5x the exact program's wire bytes, and the ring
   program moves EXACTLY the exact program's wire bytes.
"""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis import cost_model as C
from deepspeed_tpu.analysis.ast_checks import DISPATCH_DONATIONS
from deepspeed_tpu.analysis.jaxpr_checks import TracedProgram

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "deepspeed_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "graft_lint")
COST_BASELINE = os.path.join(ROOT, ".graft-cost-baseline.json")


def _fixture(name):
    spec = importlib.util.spec_from_file_location(
        f"graft_cost_fixture_{name}", os.path.join(FIXTURES, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _measure(fn, *args, loop_trips=None):
    return C.measure_jaxpr(jax.make_jaxpr(fn)(*args), loop_trips)


# ---------------------------------------------------------------------------
# golden-value units: the counting rules, with the arithmetic spelled out
# ---------------------------------------------------------------------------


def test_dot_general_flops_and_hbm_golden():
    a = jnp.ones((4, 8), jnp.float32)
    b = jnp.ones((8, 16), jnp.float32)
    m = _measure(jnp.dot, a, b)
    assert m.flops == 2 * 4 * 16 * 8                 # 2 x M x N x K = 1024
    assert m.hbm_read == (4 * 8 + 8 * 16) * 4        # operands once = 640
    assert m.hbm_write == 4 * 16 * 4                 # result once = 256
    assert m.coll_payload == {} and m.unbounded_loops == 0


def test_batched_dot_general_flops_golden():
    a = jnp.ones((2, 4, 8), jnp.float32)
    b = jnp.ones((2, 8, 16), jnp.float32)
    m = _measure(lambda x, y: jnp.einsum("bmk,bkn->bmn", x, y), a, b)
    assert m.flops == 2 * 2 * 4 * 16 * 8             # batch dim multiplies


def test_psum_ring_wire_bytes_golden():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))
    mapped = jax.shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False)
    m = C.measure_jaxpr(jax.make_jaxpr(mapped)(jnp.ones((16,), jnp.float32)))
    # ring all-reduce: each device sends 2(N-1)/N x operand bytes
    assert m.coll_payload == {"tp": 2 * 7 / 8 * 64}  # = 112.0
    assert m.coll_ops == {"tp": 1}
    assert m.payload_by_dtype == {"float32": 112.0}


def test_all_gather_wire_bytes_golden():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))
    mapped = jax.shard_map(
        lambda x: jax.lax.all_gather(x, "tp", axis=0, tiled=True),
        mesh=mesh, in_specs=P("tp"), out_specs=P(), check_vma=False)
    m = C.measure_jaxpr(jax.make_jaxpr(mapped)(jnp.ones((8, 4), jnp.float32)))
    # each device forwards its (1, 4) f32 shard to the N-1 others
    assert m.coll_payload == {"tp": 7 * 16}


def test_scan_consts_once_carries_per_step_golden():
    """THE scan-carry analysis: a 2-trip scan charges its const (the param
    analog) ONCE per frame and its carry (the KV-pool analog) per step."""
    w = jnp.ones((4, 4), jnp.float32)
    c0 = jnp.ones((4, 4), jnp.float32)

    def f(w, c0):
        return jax.lax.scan(lambda c, _: (jnp.dot(c, w), None), c0, None,
                            length=2)

    m = _measure(f, w, c0)
    assert m.flops == 2 * (2 * 4 * 4 * 4)            # one matmul per trip
    # read: w once (64B, scan const) + carry per trip (2 x 64B) = 192
    assert m.hbm_read == 64 + 2 * 64
    assert m.hbm_write == 2 * 64                     # carry written per trip


def test_while_loop_flagged_unbounded():
    def f(x):
        return jax.lax.while_loop(lambda c: c[0, 0] < 3.0,
                                  lambda c: c + 1.0, x)
    m = _measure(f, jnp.zeros((2, 2), jnp.float32))
    assert m.unbounded_loops == 1
    # what the registry names for counted loops does not bound this one
    named = _measure(f, jnp.zeros((2, 2), jnp.float32), loop_trips=2)
    assert named.unbounded_loops == 1


def test_counted_loop_with_an_operand_bound_charges_the_named_trips():
    """A serving frame's steps (``model_runner._run_steps``): a ``while``
    whose condition is ``counter < bound``, the bound an operand. Named 2
    trips it is charged as the 2-trip scan above (the const once, the carry
    a trip); unnamed it stays one trip and flagged."""
    w = jnp.ones((4, 4), jnp.float32)
    c0 = jnp.ones((4, 4), jnp.float32)

    def f(w, c0, n):
        return jax.lax.while_loop(
            lambda s: s[0] < n, lambda s: (s[0] + 1, jnp.dot(s[1], w)),
            (jnp.zeros((), jnp.int32), c0))

    m = _measure(f, w, c0, jnp.int32(2), loop_trips=2)
    assert m.unbounded_loops == 0
    assert m.flops == 2 * (2 * 4 * 4 * 4)            # one matmul per trip
    # w once (64 B) and the carry a trip (2 x 64 B) as the scan, and the
    # loop's scalars (bound, counter, predicate) on top
    assert 0 < m.hbm_read - (64 + 2 * 64) <= 32
    assert 2 * 64 <= m.hbm_write <= 2 * 64 + 16
    one = _measure(f, w, c0, jnp.int32(2))
    assert one.unbounded_loops == 1 and one.flops == 2 * 4 * 4 * 4


# ---------------------------------------------------------------------------
# GL204 fixtures
# ---------------------------------------------------------------------------


def test_gl204_fires_on_duplicated_psum():
    got = C.check_redundant_collectives(_fixture("bad_cost").dup_psum())
    assert [f.rule for f in got] == ["GL204"]
    assert "psummed twice" in got[0].message


def test_gl204_fires_on_double_reduction():
    got = C.check_redundant_collectives(_fixture("bad_cost").double_reduce())
    assert [f.rule for f in got] == ["GL204"]
    assert "already replica-invariant" in got[0].message


def test_gl204_fires_on_gather_then_reduce():
    got = C.check_redundant_collectives(
        _fixture("bad_cost").gather_then_reduce())
    assert [f.rule for f in got] == ["GL204"]
    assert "summed straight back down" in got[0].message


def test_gl204_clean_negative():
    assert C.check_redundant_collectives(_fixture("bad_cost").clean()) == []


# ---------------------------------------------------------------------------
# GL201 / GL202 / GL203 on synthetic reports
# ---------------------------------------------------------------------------


def _report(name, variant="exact", counterpart="", **over):
    base = dict(flops=1000, hbm_read=2000, hbm_write=1000, d2h_bytes=64,
                coll_ops={"tp": 4}, coll_payload={"tp": 1000},
                payload_by_dtype={"float32": 1000})
    base.update(over)
    return C.CostReport(name=name, variant=variant, counterpart=counterpart,
                        **base)


def test_gl201_flags_drift_in_both_directions(tmp_path):
    r = _report("frame_loop[w=1]")
    path = str(tmp_path / "cost.json")
    C.write_cost_baseline(path, [r])
    base = C.load_cost_baseline(path)
    assert C.check_cost_baseline([r], base) == []
    grown = dataclasses.replace(r, flops=1100)
    got = C.check_cost_baseline([grown], base)
    assert [f.rule for f in got] == ["GL201"] and "grew" in got[0].message
    shrunk = dataclasses.replace(r, flops=900)
    got = C.check_cost_baseline([shrunk], base)
    assert [f.rule for f in got] == ["GL201"] and "shrank" in got[0].message
    within = dataclasses.replace(r, flops=1010)    # 1% < 2% tolerance
    assert C.check_cost_baseline([within], base) == []


def test_gl201_flags_missing_and_stale_programs(tmp_path):
    r = _report("frame_loop[w=1]")
    path = str(tmp_path / "cost.json")
    C.write_cost_baseline(path, [r])
    base = C.load_cost_baseline(path)
    got = C.check_cost_baseline([r, _report("new_loop")], base)
    assert [f.rule for f in got] == ["GL201"]
    assert "no cost-baseline entry" in got[0].message
    got = C.check_cost_baseline([], base)
    assert "stale" in got[0].message
    # tp entries are legitimately absent from a --no-tp run
    C.write_cost_baseline(path, [r, _report("frame_loop[w=1][tp=8]")])
    base = C.load_cost_baseline(path)
    assert C.check_cost_baseline([r], base, include_tp=False) == []


def test_gl202_quantized_contract_synthetic():
    exact = _report("frame_loop[w=1][tp=8]")
    good = _report("frame_loop[w=1][tp=8,quant]", variant="quantized",
                   counterpart="frame_loop[w=1][tp=8]",
                   coll_payload={"tp": 450},
                   payload_by_dtype={"int8": 300, "float32": 150})
    assert C.check_collective_contracts([exact, good]) == []
    # int8 above half the exact total: the claim is broken
    fat = dataclasses.replace(good, coll_payload={"tp": 800},
                              payload_by_dtype={"int8": 700,
                                                "float32": 100})
    got = C.check_collective_contracts([exact, fat])
    assert [f.rule for f in got] == ["GL202"]
    assert "exceed 0.5x" in got[0].message
    # int8 wire absent entirely: the flag is dead
    dead = dataclasses.replace(good, payload_by_dtype={"float32": 450})
    got = C.check_collective_contracts([exact, dead])
    assert any("no int8 payload" in f.message for f in got)
    # no counterpart in the registry: loud, not vacuous
    got = C.check_collective_contracts([good])
    assert any("no exact counterpart" in f.message for f in got)


def test_gl202_overlap_contract_synthetic():
    exact = _report("frame_loop[w=1][tp=8]")
    ring = _report("frame_loop[w=1][tp=8,ring]", variant="overlap",
                   counterpart="frame_loop[w=1][tp=8]",
                   coll_ops={"tp": 15})
    assert C.check_collective_contracts([exact, ring]) == []
    short = dataclasses.replace(ring, coll_payload={"tp": 875})
    got = C.check_collective_contracts([exact, short])
    assert [f.rule for f in got] == ["GL202"]
    assert "chunking bug" in got[0].message


def _frame_like_program(cached_shape):
    """A 12-output program shaped like frame_loop's return tuple, with the
    ``cached`` output (host-read index 2) at an arbitrary shape."""
    b = 4

    def f(x):
        toks = jnp.zeros((2, b), jnp.int32)
        emit = jnp.zeros((2, b), bool)
        cached = jnp.zeros(cached_shape, jnp.int32)
        row_i = jnp.zeros((b,), jnp.int32)
        row_b = jnp.zeros((b,), bool)
        stats = jnp.zeros((7,), jnp.int32)
        return (toks, emit, cached, row_i, row_i, row_b, row_b, row_b,
                stats, x, x, x)

    def trace():
        return jax.make_jaxpr(f)(jnp.zeros((2,), jnp.uint32))

    return TracedProgram(name="frame_loop[w=1]", trace=trace, retrace=trace)


def test_gl203_bounds_boundary_reads_to_the_batch():
    ok = _frame_like_program((4,))
    rep = C.measure_program(ok)
    assert C.check_d2h_budget(rep, ok) == []
    # a host-read output that scales with sequence length blows the budget
    bad = _frame_like_program((4, 4096))
    rep = C.measure_program(bad)
    got = C.check_d2h_budget(rep, bad)
    assert [f.rule for f in got] == ["GL203"]
    assert "boundary budget" in got[0].message


def test_gl203_detects_host_read_table_drift():
    b = 4

    def f(x):
        return (jnp.zeros((2, b), jnp.int32),)      # 1 output, table wants 9

    prog = TracedProgram(name="frame_loop[w=1]",
                         trace=lambda: jax.make_jaxpr(f)(jnp.zeros((2,))),
                         retrace=None)
    rep = C.measure_program(prog)
    got = C.check_d2h_budget(rep, prog)
    assert [f.rule for f in got] == ["GL203"]
    assert "table drifted" in got[0].message


# ---------------------------------------------------------------------------
# the repo gate: every registered program, against the committed baseline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cost_programs():
    from deepspeed_tpu.analysis.programs import build_cost_programs
    return build_cost_programs(include_tp=True)


@pytest.fixture(scope="module")
def cost_reports(cost_programs):
    return [C.measure_program(p) for p in cost_programs]


def test_every_registered_program_measures(cost_reports):
    """Acceptance: the cost table has a row — FLOPs, HBM bytes, collective
    payload, D2H bytes — for every registered serving program at tp=1 AND
    tp=8, and the measurement itself is deterministic."""
    assert all(r is not None for r in cost_reports)
    names = {r.name for r in cost_reports}
    for base in ("frame_loop[w=1]", "frame_loop[w=8]", "frame_loop_spec[w=1]",
                 "frame_loop_spec[w=8]"):
        assert base in names and f"{base}[tp=8]" in names, base
    for r in cost_reports:
        assert r.hbm_read > 0 and r.hbm_write > 0
        assert r.unbounded_loops == 0, (r.name, "while_loop in a frame?")
        if "[tp=8" in r.name:
            assert r.total_payload > 0, (r.name, "tp program, no wire bytes")


def test_cost_registry_covers_every_dispatch_site(cost_programs):
    """Family C coverage completeness: every runner entry point with a
    donation contract (= every dispatch site) is cost-measured too, so a
    new serving loop cannot skip the ledger."""
    bases = {p.name.split("[")[0] for p in cost_programs}
    missing = {k for k in DISPATCH_DONATIONS if k not in bases}
    assert not missing, f"dispatch sites with no cost coverage: {missing}"


def test_host_read_table_matches_live_traces(cost_programs):
    """HOST_READ_OUTPUTS honesty (the GL203 analog of the donation-table
    cross-check): the indices resolve on every live trace, and the
    emission stream leads the outputs with the (steps, B[, gamma+1])
    shapes the budget formula assumes."""
    from deepspeed_tpu.analysis.jaxpr_checks import _closed
    checked = set()
    for prog in cost_programs:
        base = prog.name.split("[")[0]
        if base not in C.HOST_READ_OUTPUTS:
            continue
        checked.add(base)
        outs = list(_closed(prog.traced()).out_avals)
        reads = C.HOST_READ_OUTPUTS[base]
        assert all(i < len(outs) for i in reads), (prog.name, reads)
        if base in C.D2H_BUDGET_SCOPE:
            toks = outs[0]
            # (steps, B[, gamma+1]): the registry's frames hold 2 steps
            assert toks.shape[0] == 2 and len(toks.shape) in (2, 3), \
                prog.name
            for i in reads:
                # every boundary lane beyond the stream is O(batch)-small
                if i > 1:
                    assert C._aval_bytes(outs[i]) <= 64 * toks.shape[1], \
                        (prog.name, i)
    assert checked == set(C.HOST_READ_OUTPUTS), (
        f"untraced HOST_READ_OUTPUTS entries: "
        f"{set(C.HOST_READ_OUTPUTS) - checked}")


def test_repo_cost_gate_clean(cost_programs):
    """THE acceptance gate: Family C over the full registry vs the
    committed baseline — zero findings, with GL202 proving the int8 path
    <= 0.5x and the ring path == 1.0x of the exact wire bytes."""
    baseline = C.load_cost_baseline(COST_BASELINE)
    findings, reports = C.run_cost_checks(cost_programs, baseline=baseline)
    assert not findings, "graft-cost findings:\n" + "\n".join(
        f.render() for f in findings)
    by_name = {r.name: r for r in reports}
    quant = [r for r in reports if r.variant == "quantized"]
    ring = [r for r in reports if r.variant == "overlap"]
    assert quant and ring, "variant twins missing from the cost registry"
    for r in quant:
        exact = by_name[r.counterpart]
        assert 0 < r.int8_payload <= 0.5 * exact.total_payload, (
            r.name, r.int8_payload, exact.total_payload)
        assert r.total_payload < exact.total_payload
    for r in ring:
        exact = by_name[r.counterpart]
        assert r.total_payload == exact.total_payload, (
            r.name, r.total_payload, exact.total_payload)
        # the ring IS chunked: 2(N-1) ppermute hops replace each psum
        assert sum(r.coll_ops.values()) > sum(exact.coll_ops.values())


def test_cost_report_table_lists_every_program(cost_reports):
    table = C.render_cost_table(cost_reports)
    for r in cost_reports:
        assert r.name in table
    header = table.splitlines()[0]
    for col in ("flops", "hbm_read", "hbm_write", "coll_payload",
                "d2h_bytes"):
        assert col in header


def test_cli_exits_1_on_cost_regression(tmp_path, cost_reports, capsys):
    """Acceptance: GL201 exits 1 when a program's cost regresses beyond
    tolerance. Runs the real CLI main() against a doctored baseline whose
    frame_loop[w=1] flops claim is 10% below the live trace (scoped
    --no-tp so only the tp=1 engine re-traces)."""
    from deepspeed_tpu.analysis.lint import main
    doctored = {r.name: r.metrics() for r in cost_reports
                if "[tp=8" not in r.name}
    doctored["frame_loop[w=1]"] = dict(doctored["frame_loop[w=1]"],
                                       flops=int(
        doctored["frame_loop[w=1]"]["flops"] * 0.9))
    path = tmp_path / "cost.json"
    path.write_text(json.dumps({"version": C.COST_BASELINE_VERSION,
                                "tolerance": 0.02,
                                "programs": doctored}))
    scan = tmp_path / "empty.py"
    scan.write_text("")
    rc = main(["--no-tp", "--cost-baseline", str(path), str(scan)])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "GL201" in out and "flops grew" in out
    assert "frame_loop[w=1]" in out
