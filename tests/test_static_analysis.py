"""graft-lint: the analyzer's own test suite.

Three layers:

1. **Fixture goldens** — each jaxpr rule (GL001 transfer, GL002 donation,
   GL003 collective, GL004 retrace) demonstrably FIRES on its
   deliberately-broken fixture in ``tests/fixtures/graft_lint/`` and stays
   silent on the clean counterparts; the AST rules golden-match the
   ``# expect: GLxxx`` markers in ``bad_ast.py``.
2. **Registry honesty** — ``ast_checks.DISPATCH_DONATIONS`` (the call-site
   donation table) is cross-checked against the LIVE ``Traced.donate_argnums``
   of every serving program, so the table cannot rot when a loop grows a
   carry.
3. **The repo gate** — a full ``deepspeed_tpu/`` run (both families, tp
   programs included on the conftest's 8-device mesh) must be clean modulo
   the committed baseline. This is the regression test every later PR runs
   under.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.analysis import findings as F
from deepspeed_tpu.analysis.ast_checks import (DISPATCH_DONATIONS,
                                               check_donation_sites,
                                               check_module)
from deepspeed_tpu.analysis.jaxpr_checks import (check_collectives,
                                                 check_donation,
                                                 check_program,
                                                 check_retrace,
                                                 check_transfer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "deepspeed_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "graft_lint")
BASELINE = os.path.join(ROOT, ".graft-lint-baseline.json")


def _fixture(name):
    spec = importlib.util.spec_from_file_location(
        f"graft_lint_fixture_{name}", os.path.join(FIXTURES, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Family A rules fire on their fixtures
# ---------------------------------------------------------------------------


def test_transfer_guard_fires_on_bad_scan_body():
    prog = _fixture("bad_scan_body").make_program()
    got = check_transfer(prog)
    assert [f.rule for f in got] == ["GL001"]
    assert "scan body" in got[0].message
    assert got[0].context == "fixture:bad_scan_body"
    # the donation/retrace checks stay silent: the carry round-trips and
    # the trace is deterministic — rules must not bleed into each other
    assert check_donation(prog) == []
    assert check_retrace(prog) == []


def test_donation_checker_fires_on_unmatched_aval():
    prog = _fixture("bad_donation").make_program()
    got = check_donation(prog)
    assert [f.rule for f in got] == ["GL002"]
    assert "no matching output aval" in got[0].message
    assert check_transfer(prog) == []


def test_donation_checker_fires_on_unrebound_dispatch():
    src = _fixture("bad_donation").BAD_DISPATCH_SRC
    got = check_donation_sites("fixture.py", src,
                               registry={"frame_loop": (1,)})
    assert [f.rule for f in got] == ["GL002"]
    assert "self.kv.k" in got[0].message
    # the real dispatch pattern — donated carry rebound in the same
    # statement — must pass under the same registry
    ok = "toks, emit, self.kv.k = runner.frame_loop(params, self.kv.k)\n"
    assert check_donation_sites("ok.py", ok, registry={"frame_loop": (1,)}) \
        == []
    # ...as must the assign-then-rebind refactor of it (the dead
    # reference is overwritten within the same scope)
    ok2 = ("def dispatch(self, runner, params):\n"
           "    toks, emit, new_k = runner.frame_loop(params, self.kv.k)\n"
           "    self.kv.k = new_k\n"
           "    return toks, emit\n")
    assert check_donation_sites("ok2.py", ok2,
                                registry={"frame_loop": (1,)}) == []


def test_collective_checker_fires_on_wrong_axis():
    got = check_collectives(_fixture("bad_collective").wrong_axis())
    assert [f.rule for f in got] == ["GL003"]
    assert "axis" in got[0].message


def test_collective_checker_fires_on_bad_ring():
    got = check_collectives(_fixture("bad_collective").bad_ring())
    assert [f.rule for f in got] == ["GL003"]
    assert "ppermute" in got[0].message


def test_collective_checker_fires_on_leaky_replicated_output():
    mod = _fixture("bad_collective")
    got = check_collectives(mod.leaky_output())
    assert [f.rule for f in got] == ["GL003"]
    assert "REPLICATED" in got[0].message
    # the clean psum twin must NOT trip the taint pass
    assert check_collectives(mod.clean()) == []


def test_taint_pass_descends_into_while_bodies():
    """Shard-variance INTRODUCED inside a while_loop body (axis_index on
    the carry) must not escape the taint pass just because the loop's
    inputs were replicated."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.analysis.jaxpr_checks import TracedProgram

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("tp",))

    def body(x):
        def step(c):
            return c + jax.lax.axis_index("tp").astype(jnp.float32)
        return jax.lax.while_loop(lambda c: c < 3.0, step, jnp.sum(x))

    mapped = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)

    def trace():
        return jax.make_jaxpr(mapped)(jnp.ones((8,), jnp.float32))

    got = check_collectives(TracedProgram(name="fixture:while_taint",
                                          trace=trace, retrace=trace))
    assert [f.rule for f in got] == ["GL003"]
    assert "REPLICATED" in got[0].message


def test_retrace_budget_fires_on_trace_time_state():
    got = check_retrace(_fixture("bad_retrace").make_program())
    assert [f.rule for f in got] == ["GL004"]
    assert "DIFFERENT jaxprs" in got[0].message


def test_unclassified_trace_failure_is_loud_not_vacuous():
    """A program whose trace dies for a reason no rule classifies
    (signature drift, bad registry shapes) must surface as GL000 — never
    as a silent 'clean' with GL001-GL004 unrun."""
    from deepspeed_tpu.analysis.jaxpr_checks import (TracedProgram,
                                                     check_program)

    def broken():
        raise TypeError("missing a required argument: 'kpool'")

    got = check_program(TracedProgram(name="fixture:drifted", trace=broken,
                                      retrace=broken))
    assert [f.rule for f in got] == ["GL000"]
    assert "TypeError" in got[0].message


def test_gl000_carries_the_innermost_traceback_frame():
    """A GL000 finding names the file:line (and function) the trace abort
    was raised from plus the exception repr — without it, a trace abort is
    near-undebuggable from the JSON output (the program name says WHAT
    failed, never WHERE)."""
    from deepspeed_tpu.analysis.jaxpr_checks import (TracedProgram,
                                                     check_program)

    def _deep_helper():
        raise ValueError("registry shape drifted")

    def broken():
        return _deep_helper()

    got = check_program(TracedProgram(name="fixture:located", trace=broken,
                                      retrace=broken))
    assert [f.rule for f in got] == ["GL000"]
    msg = got[0].message
    assert "test_static_analysis.py:" in msg and "in _deep_helper" in msg
    assert "ValueError('registry shape drifted')" in msg


# ---------------------------------------------------------------------------
# Family B golden: the # expect: markers in bad_ast.py are the spec
# ---------------------------------------------------------------------------


def test_ast_rules_golden_match_fixture_markers():
    path = os.path.join(FIXTURES, "bad_ast.py")
    with open(path) as fh:
        src = fh.read()
    import re
    expected = set()
    for i, line in enumerate(src.splitlines(), start=1):
        m = re.search(r"# expect: (GL\d{3})\s*$", line)
        if m:
            expected.add((m.group(1), i))
    assert expected, "fixture lost its markers"
    found = F.apply_suppressions(check_module("bad_ast.py", src),
                                 {"bad_ast.py": src})
    got = {(f.rule, f.line) for f in found}
    assert got == expected, (f"analyzer drifted from fixture spec:\n"
                             f"  missing: {sorted(expected - got)}\n"
                             f"  extra:   {sorted(got - expected)}")


def test_lambda_scan_bodies_are_walked():
    """A hazard nested inside a lambda scan body must not escape just for
    being an expression — the most common scan-body shape."""
    src = ("import jax.lax as lax\n"
           "lax.scan(lambda c, x: (c + float(x), c), 0.0, xs)\n")
    got = check_module("lam.py", src)
    assert [f.rule for f in got] == ["GL104"], got


def test_unhashable_static_requires_a_jit_callee():
    """GL102 must not flag a host helper that merely shares a kwarg name
    with some jit's static_argnames."""
    src = ("import functools, jax\n"
           "@functools.partial(jax.jit, static_argnames=('width',))\n"
           "def f(x, width):\n"
           "    return x\n"
           "def make_plot(width=None):\n"
           "    return width\n"
           "make_plot(width=[1, 2, 3])\n"     # host call: NOT a finding
           "f(1, width=[1, 2, 3])\n")         # jit call: IS a finding
    got = [f for f in check_module("w.py", src) if f.rule == "GL102"]
    assert len(got) == 1 and got[0].line == 8, got


def test_bare_control_flow_names_require_lax_import():
    """A host-side helper named `switch`/`scan` must not turn its callback
    arguments into 'jitted regions'; a bare name IS a region root when it
    was imported from jax.lax."""
    host = ("def switch(flag, handler):\n"
            "    return handler(flag)\n"
            "def on_change(arr):\n"
            "    return float(arr)\n"
            "switch(1, on_change)\n")
    assert check_module("host.py", host) == []
    real = ("from jax.lax import scan\n"
            "def body(carry, _):\n"
            "    if carry > 0:\n"
            "        return carry, carry\n"
            "    return carry - 1, carry\n"
            "scan(body, 0, None, length=3)\n")
    got = check_module("real.py", real)
    assert [f.rule for f in got] == ["GL101"]


def test_suppression_pragma_parsing():
    src = ("x = 1  # graft-lint: disable=GL104 -- why\n"
           "# graft-lint: disable=GL101,GL103\n"
           "y = 2\n")
    sup = F.suppressed_lines(src)
    assert sup[1] == {"GL104"}
    assert sup[2] == {"GL101", "GL103"}    # the comment line itself
    assert sup[3] == {"GL101", "GL103"}    # ...and the line it annotates
    # a justification spilling onto further comment lines must not void
    # the suppression of the code line below it
    multi = ("# graft-lint: disable=GL104 -- this coercion is fine\n"
             "# because the value is a trace-time constant\n"
             "\n"
             "x = float(y)\n")
    assert "GL104" in F.suppressed_lines(multi).get(4, set())


def test_baseline_roundtrip_and_filter(tmp_path):
    f1 = F.Finding("GL104", "a.py", 3, "msg", context="fn")
    f2 = F.Finding("GL101", "b.py", 9, "other", context="g")
    path = str(tmp_path / "base.json")
    F.write_baseline(path, [f1])
    fps = F.load_baseline(path)
    assert F.filter_baseline([f1, f2], fps) == [f2]
    # fingerprints are line-independent: moving the finding keeps it
    moved = F.Finding("GL104", "a.py", 300, "msg", context="fn")
    assert moved.fingerprint == f1.fingerprint


# ---------------------------------------------------------------------------
# registry honesty + the repo gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_programs():
    from deepspeed_tpu.analysis.programs import build_serving_programs
    return build_serving_programs(include_tp=True)


#: leading wrapper-only params of each runner entry point (the jit sees
#: the args after them), mirroring the call-site shift in DISPATCH_DONATIONS
_WRAPPER_OFFSET = {"frame_loop": 0, "frame_loop_spec": 1, "run": 1,
                   "copy_blocks": 0, "scatter_pages": 0}


def test_dispatch_donation_table_matches_live_traces(serving_programs):
    seen = set()
    for prog in serving_programs:
        base = prog.name.split("[")[0]
        if base not in DISPATCH_DONATIONS:
            continue
        seen.add(base)
        expect = tuple(sorted(i + _WRAPPER_OFFSET[base]
                              for i in prog.donate_user_args))
        assert tuple(sorted(DISPATCH_DONATIONS[base])) == expect, (
            f"{base}: DISPATCH_DONATIONS says "
            f"{sorted(DISPATCH_DONATIONS[base])}, live trace donates "
            f"{expect} — a loop grew/lost a carry; update ast_checks")
    assert seen == set(DISPATCH_DONATIONS), (
        f"programs registry no longer traces {set(DISPATCH_DONATIONS) - seen}")


def test_registry_completeness_against_dispatch_sites(serving_programs):
    """Every dispatch site in DISPATCH_DONATIONS is traced in its FULL
    production variant matrix: both tp degrees for the shard_map loops,
    both widths for the frame loops (a draft engine dispatches its WIDE
    prefill frames through frame_loop_spec too), and the
    nonfinite_policy="repair" twins of every frame program. A new serving
    loop that registers its donation contract but not its trace cannot
    slip past Family A (GL001-GL004) — and Family C shares this registry,
    so it cannot skip the cost ledger either."""
    names = {p.name for p in serving_programs}
    expected = set()
    for tp in ("", "[tp=8]"):
        for w in ("w=1", "w=8"):
            expected |= {f"frame_loop[{w}]{tp}",
                         f"frame_loop[{w},repair]{tp}",
                         f"frame_loop_spec[{w}]{tp}",
                         f"frame_loop_spec[{w},repair]{tp}"}
    # host-step + page-mover programs never compile under shard_map
    expected |= {"run[chunk=8]", "copy_blocks", "scatter_pages",
                 "gather_pages"}
    # the frame programs are the only ones that scan: a program that
    # generates tokens beside them would be a registry entry of another base
    assert {n.split("[")[0] for n in names} == {
        n.split("[")[0] for n in expected}
    missing = expected - names
    assert not missing, f"registry is missing production variants: " \
                        f"{sorted(missing)}"
    # ...and the matrix covers every donation-contract dispatch site
    bases = {n.split("[")[0] for n in expected}
    assert set(DISPATCH_DONATIONS) <= bases


def test_repo_lint_clean(serving_programs):
    """THE regression gate: both families over the real repo, clean modulo
    the committed baseline — the static twin of the serving parity suites.
    Reuses the module-scoped traced programs (the expensive half)."""
    from deepspeed_tpu.analysis.lint import run_ast_family
    findings, sources = run_ast_family([PKG])
    for prog in serving_programs:
        findings.extend(check_program(prog))
    findings = F.apply_suppressions(findings, sources)
    new = F.filter_baseline(findings, F.load_baseline(BASELINE))
    assert not new, "new graft-lint findings:\n" + "\n".join(
        f.render() for f in new)


def test_cli_ast_only_smoke():
    """bin/dstpu_lint surface: --ast-only --format json runs without jax
    and exits 0 on the (clean) repo."""
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", "--ast-only",
         "--format", "json", "--baseline", BASELINE, PKG],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    data = json.loads(out.stdout)
    assert data["findings"] == []


def test_cli_broken_baseline_is_internal_error_not_findings(tmp_path):
    """A corrupt/mismatched baseline must exit 2 (internal error), never 1
    — CI gates on 1 meaning 'new findings'."""
    bad_base = tmp_path / "base.json"
    bad_base.write_text("{not json")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", "--ast-only",
         "--baseline", str(bad_base), PKG],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "cannot read baseline" in out.stderr
    # a typo'd (nonexistent) baseline path must not silently run
    # baseline-less either
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", "--ast-only",
         "--baseline", str(tmp_path / "no-such.json"), PKG],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    # a typo'd SCAN path must not report "clean" on zero files either
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", "--ast-only",
         str(tmp_path / "no-such-dir")],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "no such file" in out.stderr


def test_wrapper_ast_only_skips_framework_import():
    """bin/dstpu_lint --ast-only loads the analyzer standalone: the
    deepspeed_tpu package (and with it jax, on vanilla environments) is
    never imported — the pre-commit-speed contract."""
    probe = ("import sys, runpy\n"
             "sys.argv = ['dstpu_lint', '--ast-only',\n"
             f"            {os.path.join(PKG, 'analysis')!r}]\n"
             "try:\n"
             f"    runpy.run_path({os.path.join(ROOT, 'bin', 'dstpu_lint')!r},"
             " run_name='__main__')\n"
             "except SystemExit as e:\n"
             "    assert e.code == 0, e.code\n"
             "assert 'deepspeed_tpu' not in sys.modules, 'package imported'\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


def test_cli_exits_nonzero_on_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "@jax.jit\n"
                   "def f(x):\n"
                   "    return float(x)\n")
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.analysis.lint", "--ast-only",
         str(bad)],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "GL104" in out.stdout


def test_baseline_fingerprints_are_cwd_independent(tmp_path):
    """Finding paths anchor to the scanned target's parent, so a baseline
    written from one directory matches when lint runs from another — the
    third-party --write-baseline adoption flow."""
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "@jax.jit\n"
                   "def f(x):\n"
                   "    return float(x)\n")
    base = tmp_path / "base.json"
    args = [sys.executable, "-m", "deepspeed_tpu.analysis.lint",
            "--ast-only", "--baseline", str(base)]
    wrote = subprocess.run(args + ["--write-baseline", str(bad)],
                           cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=120,
                           env={**os.environ, "PYTHONPATH": ROOT})
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    for cwd in (str(tmp_path), ROOT):
        out = subprocess.run(args + [str(bad)], cwd=cwd,
                             capture_output=True, text=True, timeout=120,
                             env={**os.environ, "PYTHONPATH": ROOT})
        assert out.returncode == 0, (cwd, out.stdout, out.stderr)
    # ...and across scan granularities: inside a repo root marker, the
    # whole-dir scan and the single-file scan fingerprint identically
    (tmp_path / "setup.py").write_text("")
    for target in (str(bad), str(tmp_path)):
        out = subprocess.run(args + [target], cwd=ROOT,
                             capture_output=True, text=True, timeout=120,
                             env={**os.environ, "PYTHONPATH": ROOT})
        assert out.returncode == 0, (target, out.stdout, out.stderr)
