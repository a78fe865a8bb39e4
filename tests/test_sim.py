"""Fleet-simulator suite (ISSUE 18): deterministic replay, snapshot /
resume, real-policy pinning, capacity answers, cost calibration.

Pins the tentpole contracts:

* twin runs of the same (config, trace) produce a BYTE-IDENTICAL event
  log (the determinism root — ``SimResult.checkpoint`` is its sha256);
* ``run(resume_checkpoint=...)`` re-derives the run and verifies the
  barrier digest; a tampered checkpoint raises instead of silently
  diverging;
* the sim drives the REAL policy objects — ``EngineRouter._place``,
  ``RequestScheduler.pick``, ``ServiceEdge.admission_check``,
  ``AutoscaleController.on_tick`` all execute (call-counted via
  monkeypatch) while ZERO device frames dispatch;
* a capacity question (smallest fleet meeting a TTFT SLO) answers in
  seconds of wall time;
* traces round-trip through ``save_trace``/``load_trace``;
* deliberate overload sheds at the EDGE (admission math, not engine
  starvation);
* ``tune`` emits a version-1 serve-config ``bin/dstpu_serve --config``
  can overlay;
* ``calibrate_from_boundaries`` fits per-ledger-program pairs and
  round-trips through JSON.
"""

import json
import time

import pytest

from deepspeed_tpu.inference.v2.engine_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.router import EngineRouter
from deepspeed_tpu.inference.v2.scheduler import RequestScheduler
from deepspeed_tpu.inference.v2.service.autoscale import (AutoscaleConfig,
                                                          AutoscaleController)
from deepspeed_tpu.inference.v2.service.edge import EdgeConfig, ServiceEdge
from deepspeed_tpu.inference.v2.sim import (CostCalibration, FleetSimulator,
                                            FrameCostModel, SimConfig,
                                            load_trace, save_trace,
                                            synth_trace)
from deepspeed_tpu.inference.v2.sim.cost import (calibrate_from_boundaries,
                                                 fit_calibration,
                                                 load_calibration,
                                                 save_calibration)
from deepspeed_tpu.inference.v2.sim.tune import sweep_capacity, tune


def small_cfg(**kw):
    engine = kw.pop("engine", None) or RaggedInferenceEngineConfig(
        max_ragged_batch_size=8, frame_steps=8, prefill_chunk_size=64)
    return SimConfig(replicas=kw.pop("replicas", 2), engine=engine, **kw)


def small_trace(seed=3, rate=8.0, duration_s=6.0, profile="poisson"):
    return synth_trace(profile, rate=rate, duration_s=duration_s,
                       seed=seed, sessions=2)


# ---------------------------------------------------------------------
# determinism + snapshot/resume
# ---------------------------------------------------------------------

def test_event_log_byte_identical_across_runs():
    trace = small_trace()
    r1 = FleetSimulator(small_cfg()).run(trace)
    r2 = FleetSimulator(small_cfg()).run(trace)
    assert r1.completed == len(trace)
    assert r1.event_lines() == r2.event_lines()
    assert r1.checkpoint == r2.checkpoint
    assert r1.checkpoint["events"] == len(r1.events)


def test_profiles_are_seed_deterministic_and_distinct():
    for profile in ("poisson", "diurnal", "bursty", "heavy_tail"):
        a = synth_trace(profile, rate=6.0, duration_s=5.0, seed=7)
        b = synth_trace(profile, rate=6.0, duration_s=5.0, seed=7)
        assert a == b, profile
        c = synth_trace(profile, rate=6.0, duration_s=5.0, seed=8)
        assert a != c, profile


def test_snapshot_resume_reproduces_the_run():
    trace = small_trace()
    full = FleetSimulator(small_cfg()).run(trace)
    half = FleetSimulator(small_cfg()).run(
        trace, stop_after_events=len(full.events) // 2)
    assert half.checkpoint["events"] <= len(full.events)
    resumed = FleetSimulator(small_cfg()).run(
        trace, resume_checkpoint=half.checkpoint)
    assert resumed.event_lines() == full.event_lines()


def test_resume_from_diverged_checkpoint_raises():
    trace = small_trace()
    half = FleetSimulator(small_cfg()).run(trace, stop_after_events=20)
    bad = dict(half.checkpoint, sha256="0" * 64)
    with pytest.raises(RuntimeError, match="sha|barrier|diverg"):
        FleetSimulator(small_cfg()).run(trace, resume_checkpoint=bad)


# ---------------------------------------------------------------------
# the REAL policy stack runs; zero real frames dispatch
# ---------------------------------------------------------------------

def test_real_policy_objects_execute_and_no_frames_dispatch(monkeypatch):
    calls = {"place": 0, "pick": 0, "edge": 0, "tick": 0}

    orig_place = EngineRouter._place
    orig_pick = RequestScheduler.pick
    orig_edge = ServiceEdge.admission_check
    orig_tick = AutoscaleController.on_tick

    def count(key, orig):
        def wrapper(self, *a, **kw):
            calls[key] += 1
            return orig(self, *a, **kw)
        return wrapper

    monkeypatch.setattr(EngineRouter, "_place", count("place", orig_place))
    monkeypatch.setattr(RequestScheduler, "pick", count("pick", orig_pick))
    monkeypatch.setattr(ServiceEdge, "admission_check",
                        count("edge", orig_edge))
    monkeypatch.setattr(AutoscaleController, "on_tick",
                        count("tick", orig_tick))

    from deepspeed_tpu.inference.v2 import ragged_manager

    def no_dispatch(self, *a, **kw):
        raise AssertionError("the simulator dispatched a REAL frame")

    monkeypatch.setattr(ragged_manager.DeviceSlotTable, "dispatch_frame",
                        no_dispatch)

    trace = small_trace()
    cfg = small_cfg(autoscale=AutoscaleConfig(),
                    edge=EdgeConfig(max_queued_tokens=100_000, trace=False))
    res = FleetSimulator(cfg).run(trace)
    assert res.completed == len(trace)
    assert res.virtual_frames > 0
    for key, n in calls.items():
        assert n > 0, f"policy hook {key} never executed"


# ---------------------------------------------------------------------
# capacity questions
# ---------------------------------------------------------------------

def test_capacity_sweep_answers_in_seconds():
    trace = small_trace(rate=12.0, duration_s=6.0)
    t0 = time.perf_counter()
    out = sweep_capacity(trace, small_cfg(), replica_counts=(1, 2, 4),
                         slo_ttft_p90_ms=10_000.0)
    wall = time.perf_counter() - t0
    assert wall < 30.0, f"capacity sweep took {wall:.1f}s"
    assert [r["replicas"] for r in out["rows"]] == [1, 2, 4]
    assert out["min_replicas_for_slo"] is not None
    for row in out["rows"]:
        assert row["completed"] == len(trace)


def test_trace_round_trip(tmp_path):
    trace = small_trace(profile="bursty")
    path = str(tmp_path / "workload.jsonl")
    save_trace(path, trace)
    assert load_trace(path) == trace


def test_edge_sheds_under_deliberate_pressure():
    # a one-replica fleet priced 100x slower than reality, fed 4x the
    # traffic, behind an edge allowing almost no queued prompt tokens:
    # the REAL admission math must shed at the EDGE
    cfg = small_cfg(
        replicas=1,
        engine=RaggedInferenceEngineConfig(
            max_ragged_batch_size=2, frame_steps=8, prefill_chunk_size=64),
        edge=EdgeConfig(max_queued_tokens=64, trace=False),
        calibration=CostCalibration(c0=0.25, k=1.0))
    trace = small_trace(rate=30.0, duration_s=4.0)
    res = FleetSimulator(cfg).run(trace)
    sheds = sum(1 for line in res.event_lines()
                if json.loads(line)["kind"] == "edge_shed")
    assert sheds > 0, "edge admission never shed under overload"


def test_tune_emits_loadable_serve_config(tmp_path):
    trace = small_trace(rate=6.0, duration_s=4.0)
    space = {"frame_steps": (4, 8), "prefill_chunk_size": (64,),
             "speculate_gamma": (0,), "max_ragged_batch_size": (8,)}
    best, rows = tune(trace, small_cfg(), space=space, mode="grid")
    assert best["version"] == 1
    assert rows and rows[0]["score"] == best["score"]
    # the exact gate bin/dstpu_serve --config applies before overlaying
    path = tmp_path / "serve.json"
    path.write_text(json.dumps(best))
    tuned = json.loads(path.read_text())
    assert tuned["version"] == 1
    for key in ("frame_steps", "prefill_chunk_size", "speculate_gamma",
                "max_ragged_batch_size"):
        assert key in tuned["engine"]
    assert "lookahead_reserve" in tuned["scheduler"]
    assert "max_queued_tokens" in tuned["edge"]


# ---------------------------------------------------------------------
# cost calibration
# ---------------------------------------------------------------------

def test_fit_calibration_recovers_affine_and_rejects_degenerate():
    fit = fit_calibration([(1.0, 0.011), (2.0, 0.021), (3.0, 0.031)])
    assert fit.c0 == pytest.approx(0.001, abs=1e-6)
    assert fit.k == pytest.approx(0.01, abs=1e-6)
    # one distinct work value -> no slope information -> defaults
    degenerate = fit_calibration([(1.0, 0.01), (1.0, 0.03)])
    assert (degenerate.c0, degenerate.k) == (CostCalibration().c0,
                                             CostCalibration().k)


def test_calibrate_from_boundaries_fits_per_program(tmp_path):
    model = FrameCostModel()
    # two frame shapes with dt far apart relative to their ledger work
    # gap — exactly the regime one global affine cannot represent
    samples = (
        [{"dt": 0.002, "steps": 4, "live": 1, "n_slots": 8, "width": 1}] * 8
        + [{"dt": 0.020, "steps": 4, "live": 1, "n_slots": 8,
            "width": 8}] * 8)
    cal = calibrate_from_boundaries(model, samples, warmup_factor=50.0)
    assert cal.per_program, "per-program refinement missing"
    narrow = model.frame_seconds(steps=4, live=1, n_slots=8, width=1)
    wide = model.frame_seconds(steps=4, live=1, n_slots=8, width=8)
    assert narrow == pytest.approx(0.002, rel=0.15)
    assert wide == pytest.approx(0.020, rel=0.15)
    # JSON round-trip preserves the refinement
    path = str(tmp_path / "cal.json")
    save_calibration(path, cal)
    loaded = load_calibration(path)
    assert loaded.per_program == cal.per_program
    assert loaded.for_program(next(iter(cal.per_program))) != (loaded.c0,
                                                               loaded.k) \
        or len(cal.per_program) == 1
    # a calibrated sim remains deterministic
    trace = small_trace(duration_s=4.0)
    r1 = FleetSimulator(small_cfg(calibration=loaded)).run(trace)
    r2 = FleetSimulator(small_cfg(calibration=loaded)).run(trace)
    assert r1.event_lines() == r2.event_lines()


def test_sim_plans_a_frames_steps_as_the_engine_does():
    """``SimEngine`` prices a frame by its steps: it asks the engine's own
    plan (``InferenceEngineV2._plan_frame_steps``) how many a frame runs,
    so a wide frame of its virtual serve ends with its last prefilling row
    (at half a frame at least) unless it takes a prompt over from the frame
    before, and a narrow one with a lone row's last token, and the counters
    say so as the server's do."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.scheduler import RequestScheduler
    from deepspeed_tpu.inference.v2.sim.engine import SimEngine
    eng = SimEngine(config=RaggedInferenceEngineConfig(
        max_ragged_batch_size=4, frame_steps=4, prefill_chunk_size=16),
        max_seq_len=256)
    arrivals = [[{"uid": 0, "tokens": list(range(20))},
                 {"uid": 1, "tokens": list(range(40))}],
                [{"uid": 2, "tokens": list(range(100))}]]
    got = dict(eng.serve(iter(arrivals), max_new_tokens=7,
                         scheduler=RequestScheduler()))
    assert {u: len(t) for u, t in got.items()} == {0: 7, 1: 7, 2: 7}
    trace = [r["steps"] for r in eng.telemetry.steps_trace]
    # 40 tokens: three steps; then 100 tokens: four steps and, carried over,
    # four for the three chunks left; a whole narrow frame; uid 2's last
    # token ends one at its first step
    assert trace == [3, 4, 4, 4, 1]
    c = eng.telemetry.counters
    assert (c["wide_steps"], c["frame_steps"]) == (3 + 4 + 4, sum(trace))
    assert eng.virtual_steps == sum(trace)
