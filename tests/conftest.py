"""Test harness configuration.

Analog of the reference's ``tests/unit/common.py`` DistributedTest pattern:
multi-chip logic is tested on a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count`` (SURVEY.md §4's TPU-build
implication) — ZeRO/pipeline/MoE/SP collectives execute for real across 8
simulated devices in one process.
"""

import os

# Must run before any backend is initialized: the suite asks for the CPU
# explicitly (nothing in the package defaults to it), with eight virtual
# devices for the multi-chip logic.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# GRAFT_SANITIZE=1 arms the dynamic sanitizers (see "sanitizer mode"
# below): in-frame transfer guards on every serving test, strict rank
# promotion, NaN debugging on non-fault suites, and per-suite retrace
# budgets. Off by default so tier-1 timing is untouched.
SANITIZE = os.environ.get("GRAFT_SANITIZE", "0") == "1"

#: test modules that drive the frame serving loops — the suites the
#: sanitizer applies the in-frame transfer guard and retrace budget to
SERVING_SUITES = ("test_frame_serving", "test_serving_telemetry",
                  "test_serving_scheduler", "test_serving_faults",
                  "test_serving_tp", "test_kv_hierarchy", "test_router",
                  "test_disagg", "test_service", "test_tracing",
                  "test_quantized_serving")

#: fault-injection suites intentionally produce NaN logits (poison rows):
#: jax_debug_nans would abort the machinery under test
NAN_SUITES = ("test_serving_faults", "test_kv_hierarchy")

#: per-suite ceiling on compiled programs PER RUNNER (compile_count_total —
#: the monotonic recompile counter). Generous vs the handful of shape
#: buckets a healthy suite compiles; a retrace-per-frame bug blows past it
#: immediately. The static twin is graft-lint rule GL004.
RETRACE_BUDGET = {"default": 64}


def guard_frame_dispatch(monkeypatch):
    """THE single definition of "in-frame": wrap
    ``DeviceSlotTable.dispatch_frame`` in a device->host transfer guard.
    Everything outside it (admission, absorb, stats_delta, quarantine
    reads) is frame-BOUNDARY work and stays unguarded. Shared by the
    ``frame_transfer_guard`` fixture (the dedicated per-suite guard tests)
    and the GRAFT_SANITIZE=1 blanket mode, so the dynamic guard and the
    static TransferGuard check (graft-lint GL001) agree on scope."""
    from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
    orig = DeviceSlotTable.dispatch_frame

    def guarded(self, *a, **kw):
        with jax.transfer_guard_device_to_host("disallow"):
            return orig(self, *a, **kw)

    monkeypatch.setattr(DeviceSlotTable, "dispatch_frame", guarded)


@pytest.fixture
def frame_transfer_guard(monkeypatch):
    """Opt-in fixture: the serving suites' zero-in-frame-transfer
    acceptance tests request this instead of re-defining the guard."""
    guard_frame_dispatch(monkeypatch)


@pytest.fixture(autouse=True)
def _sanitize(request, monkeypatch):
    """Sanitizer mode (GRAFT_SANITIZE=1): every serving test runs under
    the in-frame transfer guard, everything runs with strict rank
    promotion, and non-fault tests run with jax_debug_nans — the dynamic
    complements of graft-lint GL001/GL103 and the finite-check."""
    if not SANITIZE:
        yield
        return
    module = request.node.module.__name__.rsplit(".", 1)[-1]
    if module not in SERVING_SUITES:
        # the sanitizers police the SERVING stack's invariants; the
        # training/ops suites have their own (looser) broadcasting idiom
        yield
        return
    guard_frame_dispatch(monkeypatch)
    prev_rank = jax.config.jax_numpy_rank_promotion
    jax.config.update("jax_numpy_rank_promotion", "raise")
    prev_nans = jax.config.jax_debug_nans
    if module not in NAN_SUITES:
        jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_numpy_rank_promotion", prev_rank)
        jax.config.update("jax_debug_nans", prev_nans)


@pytest.fixture(autouse=True, scope="module")
def _retrace_budget(request):
    """Sanitizer mode: assert a per-suite retrace budget over every
    PagedModelRunner the module creates, via the monotonic
    ``compile_count_total()``. Catches the silent perf cliff (a retrace
    per serve() call) that per-test recompile assertions can miss when
    the engine is module-scoped."""
    module = request.node.name.rsplit("/", 1)[-1].rsplit(".", 1)[0]
    if not SANITIZE or module not in SERVING_SUITES:
        yield
        return
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    runners = []
    orig_init = PagedModelRunner.__init__

    def tracking_init(self, *a, **kw):
        orig_init(self, *a, **kw)
        runners.append(self)

    PagedModelRunner.__init__ = tracking_init
    try:
        yield
    finally:
        PagedModelRunner.__init__ = orig_init
        budget = RETRACE_BUDGET.get(module, RETRACE_BUDGET["default"])
        over = [(r, r.compile_count_total()) for r in runners
                if r.compile_count_total() > budget]
        assert not over, (
            f"{module}: retrace budget exceeded — "
            + ", ".join(f"runner compiled {n} programs (budget {budget}): "
                        f"{r.compile_count()}" for r, n in over))


def pytest_sessionfinish(session, exitstatus):
    """Sanitizer mode: print the graft-cost delta vs the committed
    baseline at session teardown, next to the per-suite retrace budgets —
    the dynamic session ends with the static ledger's verdict on the
    programs it just exercised. Only runs when a serving/analysis suite
    was collected (the tracing costs ~15s; a config-only run shouldn't
    pay it)."""
    if not SANITIZE:
        return
    suites = SERVING_SUITES + ("test_static_analysis", "test_cost_model")
    items = getattr(session, "items", []) or []
    if not any(it.nodeid.rsplit("/", 1)[-1].split(".py")[0] in suites
               for it in items):
        return
    try:
        import logging
        logging.getLogger("DeepSpeedTPU").setLevel(logging.ERROR)
        from deepspeed_tpu.analysis.cost_model import (load_cost_baseline,
                                                       run_cost_checks)
        from deepspeed_tpu.analysis.programs import build_cost_programs
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        baseline = load_cost_baseline(
            os.path.join(root, ".graft-cost-baseline.json"))
        findings, reports = run_cost_checks(build_cost_programs(),
                                            baseline=baseline)
        drift = [f for f in findings if f.rule == "GL201"]
        if drift:
            print(f"\n[graft-sanitize] cost-report delta: {len(drift)} "
                  "metric(s) off baseline:")
            for f in drift:
                print(f"[graft-sanitize]   {f.render()}")
        else:
            print(f"\n[graft-sanitize] cost report matches baseline "
                  f"({len(reports)} programs; retrace budgets above)")
        other = [f for f in findings if f.rule != "GL201"]
        for f in other:
            print(f"[graft-sanitize]   {f.render()}")
    except Exception as e:   # noqa: BLE001 — teardown must never mask results
        print(f"\n[graft-sanitize] cost-report delta unavailable: "
              f"{type(e).__name__}: {e}")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: anything wall-clock-sensitive (telemetry
    # latency-value assertions, benchmarks) carries this marker so the
    # deterministic CPU suite never flakes on timing
    config.addinivalue_line(
        "markers", "slow: wall-clock-sensitive or long-running; excluded "
        "from the tier-1 CPU suite (-m 'not slow')")
    # chaos tests are deterministic (scripted FaultInjector schedules, no
    # randomness, no wall-clock assertions) and run IN tier-1: fault
    # handling that is only exercised nightly is fault handling that rots
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection serving tests "
        "(tests/test_serving_faults.py); included in tier-1")
    # multichip tests run on the virtual 8-device CPU mesh this conftest
    # already forces (--xla_force_host_platform_device_count=8), so they are
    # tier-1-safe by construction and run in every PR; the marker exists so
    # `-m multichip` can run the sharded-serving suite focused (the verify
    # skill's forced-8-device job line)
    config.addinivalue_line(
        "markers", "multichip: exercises a multi-device mesh (virtual on "
        "CPU); tier-1-safe, selectable with -m multichip")
    # service-edge tests (tests/test_service.py) drive the thread-per-
    # replica fleet driver and the HTTP/SSE front-end on loopback; they
    # poll outcomes with generous deadlines (never assert on timing), so
    # they are tier-1-safe and run in every PR
    config.addinivalue_line(
        "markers", "service: thread-per-replica fleet driver + HTTP/SSE "
        "service-edge tests; included in tier-1, selectable with "
        "-m service")


@pytest.fixture(autouse=True)
def _reset_mesh():
    """Each test starts with a fresh (unset) global mesh."""
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    yield
    groups.reset_mesh()


@pytest.fixture
def mesh_8dp():
    from deepspeed_tpu.utils import groups
    return groups.set_mesh(groups.build_mesh(data=8))


@pytest.fixture
def mesh_2x4():
    """2-way data x 4-way tensor."""
    from deepspeed_tpu.utils import groups
    return groups.set_mesh(groups.build_mesh(data=2, tensor=4))


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def planned_against_whole(monkeypatch):
    """``run(eng, arrivals, **serve_kw)``: serve ``arrivals()`` twice on one
    engine, as the serve loop plans its frames (a frame runs the steps its
    rows have work for: ``InferenceEngineV2._plan_frame_steps``) and with
    every frame forced to the whole length (``n_steps = steps``, what every
    tree before PR 41 ran). Every request's tokens must be the same, the
    forced run must compile nothing (the length is an operand of the frame
    programs), and the planned run's ``frame_steps_hist`` is returned with
    its outputs for the caller to say which frames ended early."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    def run(eng, arrivals, **kw):
        planned = dict(eng.serve(arrivals(), **kw))
        hist = dict(eng.serve_stats["frame_steps_hist"])
        programs = eng.runner.compile_count_total()
        with monkeypatch.context() as m:
            m.setattr(InferenceEngineV2, "_plan_frame_steps",
                      staticmethod(lambda cur_steps, *rest: cur_steps))
            whole = dict(eng.serve(arrivals(), **kw))
        assert len(eng.serve_stats["frame_steps_hist"]) == 1   # all whole
        assert eng.runner.compile_count_total() == programs
        assert set(planned) == set(whole) and planned
        for uid in whole:
            np.testing.assert_array_equal(planned[uid], whole[uid],
                                          err_msg=f"uid={uid} diverged")
        return planned, hist

    return run
