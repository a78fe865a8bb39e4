"""Chaos suite: fault-tolerant serving under scripted fault schedules.

Every test drives ``serve(..., faults=FaultInjector(schedule))`` with a
DETERMINISTIC schedule (faults keyed by frame-boundary index and uid — no
randomness, no wall-clock triggers except the deadline tests' own
deadlines) and pins the acceptance contract of ISSUE 5:

* surviving requests complete with greedy outputs token-identical to a
  fault-free run (transient dispatch failure, poison row, KV-alloc
  failure, kill-and-resume);
* no KV blocks leak — the allocator's free count returns to baseline
  after every scenario;
* the in-graph finite-check adds zero device→host transfers inside a
  frame (transfer guard around ``dispatch_frame``);
* faults are visible: structured ``FaultReason`` records in
  ``engine.fault_log`` and ``ds_serving_*`` counters.

Engine tests share one module-scope engine/baseline (the compiled frame
programs are reused across serves — same budget discipline as the
speculative and scheduler suites).
"""

import numpy as np
import jax
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.faults import (FaultInjector, FaultSpec,
                                               FrameDispatchError,
                                               InjectedFault)
from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                  SchedulerConfig)
from deepspeed_tpu.models import build_model

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny")
    return model, model.init(jax.random.PRNGKey(0))


# One serve loop runs whatever the admission policy: what a de-forked
# helper does (expiry, quarantine, deferral, resume ingestion) is checked
# under both (None = scheduler.FifoPolicy). A factory, not an instance: a
# scheduler is bound to one serve at a time.
POLICIES = pytest.mark.parametrize(
    "policy", [lambda: None, RequestScheduler], ids=["fifo", "scheduler"])


def _engine(model, params, **over):
    kw = dict(kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
              dtype="float32", max_ragged_batch_size=8, frame_steps=4,
              frame_retry_backoff_s=0.0)    # chaos tests need no real backoff
    kw.update(over)
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                          max_seq_len=128)
    e.params = jax.device_put(params)
    return e


PROMPTS = {u: np.random.default_rng(5).integers(0, 200, (200,))
           .astype(np.int32)[o:o + n]
           for u, (o, n) in enumerate(((0, 7), (10, 24), (40, 33), (80, 5)))}
SCHEDULE = {0: [0, 1], 2: [2], 3: [3]}


def _arrivals(schedule=None):
    schedule = SCHEDULE if schedule is None else schedule
    for k in range(max(schedule) + 2):
        yield [(u, PROMPTS[u]) for u in schedule.get(k, [])]


@pytest.fixture(scope="module")
def served_engine(tiny_model_params):
    model, params = tiny_model_params
    return _engine(model, params)


@pytest.fixture(scope="module")
def fault_free_base(served_engine):
    """THE reference outputs every chaos scenario's survivors must match."""
    return dict(served_engine.serve(_arrivals(), max_new_tokens=8))


def _assert_clean(e):
    assert e.kv.free_blocks == e.kv.num_blocks - 1   # trash block only
    assert not e.state.seqs
    assert not e._ledger


# ---------------------------------------------------------------------------
# fault spec / injector units (no model)
# ---------------------------------------------------------------------------


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meteor_strike", frame=0)
    with pytest.raises(ValueError, match="needs a target uid"):
        FaultSpec(kind="poison_row", frame=0)
    with pytest.raises(ValueError, match="times >= 1"):
        FaultSpec(kind="dispatch_exception", frame=0, times=0)
    with pytest.raises(ValueError, match="seconds"):
        FaultSpec(kind="slow_frame", frame=0, seconds=-1.0)


def test_injector_is_deterministic_and_rearms():
    inj = FaultInjector([
        {"kind": "dispatch_exception", "frame": 1, "times": 2},
        {"kind": "poison_row", "frame": 2, "uid": 7},
        {"kind": "kv_alloc_fail", "frame": 0, "times": 2},
    ])

    def run():
        events = []
        for frame in range(4):
            if inj.kv_alloc_blocked(frame):
                events.append(("alloc", frame))
            events.append(("poison", frame, inj.poison_uids(frame)))
            attempt = 0
            while True:
                try:
                    inj.before_dispatch(frame, attempt)
                    break
                except InjectedFault:
                    events.append(("raise", frame, attempt))
                    attempt += 1
        return events

    first = run()
    inj.begin_serve()                       # rearm: identical second run
    assert run() == first
    assert ("raise", 1, 0) in first and ("raise", 1, 1) in first
    assert ("poison", 2, [7]) in first
    assert ("alloc", 0) in first and ("alloc", 1) in first
    assert ("alloc", 2) not in first


# ---------------------------------------------------------------------------
# transient dispatch failure: bounded retry, token-identical recovery
# ---------------------------------------------------------------------------


def test_transient_dispatch_failure_recovers_token_identical(
        served_engine, fault_free_base):
    """Two consecutive dispatch failures at one frame are absorbed by the
    retry loop (the donated carry was never consumed) — outputs are
    token-identical to the fault-free run and the retries are counted."""
    e = served_engine
    inj = FaultInjector([{"kind": "dispatch_exception", "frame": 2,
                          "times": 2}])
    got = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    assert set(got) == set(fault_free_base)
    for u in fault_free_base:
        np.testing.assert_array_equal(fault_free_base[u], got[u],
                                      err_msg=f"uid={u}")
    assert len(inj.fired) == 2
    assert e.telemetry.counters["frame_retries"] == 2
    assert e.telemetry.counters["faults"] == 2
    retries = [f for f in e.fault_log if f.kind == "dispatch_retry"]
    assert len(retries) >= 2 and retries[-1].frame == 2
    _assert_clean(e)


def test_watchdog_flags_slow_frame(served_engine, fault_free_base):
    """An injected slow frame trips the wall-clock watchdog: counted and
    logged, never killed — outputs stay token-identical."""
    e = served_engine
    # threshold far above a natural CPU frame (~8 ms), far below the
    # injected stall: only the scripted slow frame deterministically trips
    e._config.watchdog_frame_ms = 100.0
    try:
        inj = FaultInjector([{"kind": "slow_frame", "frame": 1,
                              "seconds": 0.25}])
        got = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    finally:
        e._config.watchdog_frame_ms = None
    for u in fault_free_base:
        np.testing.assert_array_equal(fault_free_base[u], got[u])
    assert e.telemetry.counters["slow_frames"] >= 1
    assert any(f.kind == "slow_frame" and f.frame == 1
               for f in e.fault_log)
    assert inj.fired and inj.fired[0]["kind"] == "slow_frame"
    _assert_clean(e)


# ---------------------------------------------------------------------------
# poison-row quarantine
# ---------------------------------------------------------------------------


@POLICIES
def test_poison_row_quarantined_siblings_unaffected(
        served_engine, fault_free_base, policy):
    """A row whose logits go non-finite mid-decode is quarantined at the
    frame boundary: evicted, retired with a structured FaultReason carrying
    its committed partial output, never yielded — and every sibling's
    output is byte-identical to the fault-free run. The batch never dies
    for one request."""
    e = served_engine
    inj = FaultInjector([{"kind": "poison_row", "frame": 1, "uid": 1}])
    got = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj,
                       scheduler=policy()))
    assert 1 not in got                      # quarantined, not yielded
    for u in (0, 2, 3):
        np.testing.assert_array_equal(fault_free_base[u], got[u],
                                      err_msg=f"uid={u}")
    fr = [f for f in e.fault_log if f.kind == "poison_row"][-1]
    assert fr.uid == 1 and fr.frame == 1
    # the partial output is the committed prefix of the healthy run: frames
    # BEFORE the poison emitted real tokens, the poisoned frame's tail was
    # suppressed by the in-graph emit mask
    assert fr.partial and fr.tokens_emitted == len(fr.partial)
    np.testing.assert_array_equal(
        np.asarray(fr.partial), fault_free_base[1][:len(fr.partial)])
    assert e.telemetry.counters["quarantined"] == 1
    prom = e.telemetry.render_prometheus()
    assert "ds_serving_quarantined_total 1" in prom
    assert 'ds_serving_faults_total{kind="poison_row"} 1' in prom
    _assert_clean(e)


def test_finite_check_adds_no_in_frame_transfers(served_engine,
                                                 frame_transfer_guard):
    """Acceptance guard: the finite-check/poison machinery rides the donated
    carry — frame dispatch performs ZERO device→host transfers even while a
    poison fault fires and a quarantine runs (conftest's shared guard)."""
    e = served_engine
    inj = FaultInjector([{"kind": "poison_row", "frame": 1, "uid": 1}])
    got = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    assert 1 not in got and set(got) == {0, 2, 3}
    assert [f.uid for f in e.fault_log
            if f.kind == "poison_row"][-1] == 1   # quarantine ran under guard
    _assert_clean(e)


# ---------------------------------------------------------------------------
# KV-allocation failure
# ---------------------------------------------------------------------------


@POLICIES
def test_kv_alloc_failure_defers_then_recovers(served_engine,
                                               fault_free_base, policy):
    """Injected allocation failures turn into admission deferrals (the
    graceful path), not crashes: arrivals wait out the fault window and
    complete token-identically."""
    e = served_engine
    inj = FaultInjector([{"kind": "kv_alloc_fail", "frame": 2, "times": 2}])
    got = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj,
                       scheduler=policy()))
    assert set(got) == set(fault_free_base)
    for u in fault_free_base:
        np.testing.assert_array_equal(fault_free_base[u], got[u],
                                      err_msg=f"uid={u}")
    assert any(f.kind == "kv_alloc_failed" for f in e.fault_log)
    assert e.telemetry.counters["admission_deferrals"] >= 1
    _assert_clean(e)


# ---------------------------------------------------------------------------
# per-request deadlines
# ---------------------------------------------------------------------------


def test_deadline_expiry_frees_blocks_and_counts(served_engine,
                                                 fault_free_base):
    """A live row whose deadline_ms elapses is cancelled at the next frame
    boundary: KV blocks freed, a deadline_expired timeout retirement
    recorded (with the committed partial), telemetry visible — and the
    surviving row's output is untouched."""
    e = served_engine
    blocks_baseline = e.kv.free_blocks

    def arr():
        yield [(0, PROMPTS[0]),
               {"uid": 9, "tokens": PROMPTS[1], "deadline_ms": 0.5}]
        for _ in range(3):
            yield []

    got = dict(e.serve(arr(), max_new_tokens=8))
    assert 9 not in got
    np.testing.assert_array_equal(got[0], fault_free_base[0])
    fr = [f for f in e.fault_log if f.kind == "deadline_expired"][-1]
    assert fr.uid == 9 and "live row" in fr.detail
    assert e.telemetry.counters["deadline_expired"] == 1
    assert "ds_serving_deadline_expired_total 1" in \
        e.telemetry.render_prometheus()
    assert e.kv.free_blocks == blocks_baseline     # expiry freed its blocks
    _assert_clean(e)


@POLICIES
def test_deadline_expiry_in_queue_before_admission(served_engine, policy):
    """A QUEUED request past its deadline is cancelled before a slot or any
    KV blocks are ever spent on it (zero tokens emitted)."""
    e = served_engine
    # 2 slots, 3 arrivals: uid 22 queues behind 20/21 and expires waiting
    def arr():
        yield [{"uid": 20, "tokens": PROMPTS[1]},
               {"uid": 21, "tokens": PROMPTS[2]},
               {"uid": 22, "tokens": PROMPTS[3], "deadline_ms": 0.5}]
        for _ in range(2):
            yield []

    got = dict(e.serve(arr(), max_new_tokens=8, frame_slots=2,
                       scheduler=policy()))
    assert set(got) == {20, 21}
    fr = [f for f in e.fault_log if f.kind == "deadline_expired"][-1]
    assert fr.uid == 22 and "queued" in fr.detail
    assert fr.tokens_emitted == 0 and fr.partial is None
    _assert_clean(e)


def test_deadline_cancelled_before_preemption_or_aging(served_engine):
    """Scheduler integration: an expired queued interactive request is
    cancelled BEFORE the boundary's preemption pass — no live best-effort
    row is evicted on behalf of dead work."""
    e = served_engine

    def arr():
        yield [{"uid": 30, "tokens": PROMPTS[1], "priority": "best_effort"},
               {"uid": 31, "tokens": PROMPTS[2], "priority": "best_effort"}]
        yield []
        # a deadline so tight it is already past at the arrival's own
        # boundary: the expiry pass must cancel it before the preemption
        # pass can evict a live row on its behalf
        yield [{"uid": 32, "tokens": PROMPTS[0], "priority": "interactive",
                "deadline_ms": 1e-6}]
        for _ in range(2):
            yield []

    s = RequestScheduler(SchedulerConfig())
    got = dict(e.serve(arr(), max_new_tokens=12, frame_slots=2, scheduler=s))
    assert set(got) == {30, 31}
    assert s.summary["preempted"] == 0       # dead work preempted nobody
    fr = [f for f in e.fault_log if f.kind == "deadline_expired"][-1]
    assert fr.uid == 32 and fr.priority == "interactive"
    _assert_clean(e)


# ---------------------------------------------------------------------------
# kill-and-resume crash recovery
# ---------------------------------------------------------------------------


@POLICIES
def test_kill_and_resume_token_identical(tiny_model_params, served_engine,
                                         fault_free_base, policy):
    """A fatal dispatch failure (retry budget exhausted) surfaces as
    FrameDispatchError AFTER the engine auto-snapshots its request ledger;
    a FRESH engine resuming from the snapshot re-admits the in-flight
    requests and the union of pre-crash and post-resume outputs is
    token-identical to the fault-free run. Recovery is visible in
    ds_serving_recoveries_total and the recovery-time gauge."""
    model, params = tiny_model_params
    e = served_engine
    inj = FaultInjector([{"kind": "dispatch_exception", "frame": 3,
                          "times": 10}])
    collected = {}
    with pytest.raises(FrameDispatchError, match="resume_from"):
        for uid, toks in e.serve(_arrivals(), max_new_tokens=8, faults=inj,
                                 scheduler=policy()):
            collected[uid] = toks
    assert any(f.kind == "dispatch_failed" for f in e.fault_log)
    _assert_clean(e)                          # crash cleanup left no leaks
    snap = e.last_crash_snapshot
    assert snap is not None and snap["version"] == 1
    in_flight = {r["uid"] for r in snap["requests"]}
    assert in_flight and in_flight.isdisjoint(collected)

    e2 = _engine(model, params)               # the restarted engine
    rest = dict(e2.serve(iter([[]]), max_new_tokens=8, resume_from=snap,
                         scheduler=policy()))
    collected.update(rest)
    assert set(collected) == set(fault_free_base)
    for u in fault_free_base:
        np.testing.assert_array_equal(fault_free_base[u], collected[u],
                                      err_msg=f"uid={u}")
    assert e2.telemetry.counters["recoveries"] == len(in_flight)
    assert e2.telemetry.gauges["last_recovery_ms"] > 0
    assert "ds_serving_recoveries_total" in e2.telemetry.render_prometheus()
    _assert_clean(e2)


def test_snapshot_restore_parity_without_crash(tiny_model_params,
                                               served_engine,
                                               fault_free_base):
    """snapshot_serving_state() works on a healthy engine too: abandon a
    serve mid-flight after snapshotting, resume the snapshot elsewhere, and
    the resumed outputs extend the committed prefixes token-identically."""
    model, params = tiny_model_params
    e = served_engine
    collected = {}
    gen = e.serve(_arrivals(), max_new_tokens=8)
    snap = None
    for uid, toks in gen:
        collected[uid] = toks
        snap = e.snapshot_serving_state()    # after the first retirement
        break
    gen.close()                              # abandon: cleanup must not
    _assert_clean(e)                         # invalidate the snapshot
    # the first retirement (uid 0, smallest budget) lands after the
    # generator polled uid 2 off the arrival schedule (the first wide frame
    # ends with its prompts' last chunk) and before it polls uid 3, so the
    # snapshot covers exactly the other two in-flight requests
    assert {r["uid"] for r in snap["requests"]} == {1, 2}
    e2 = _engine(model, params)
    rest = dict(e2.serve(iter([[]]), max_new_tokens=8, resume_from=snap))
    collected.update(rest)
    assert set(collected) == {0, 1, 2}
    for u in collected:
        np.testing.assert_array_equal(fault_free_base[u], collected[u],
                                      err_msg=f"uid={u}")
    _assert_clean(e2)


def test_resume_through_scheduler_preserves_metadata(tiny_model_params):
    """Resuming into a scheduled serve: snapshot tenant/priority ride the
    ledger, so resumed requests re-enter the policy queues in class order
    (and fault-free resumed outputs match the plain run)."""
    model, params = tiny_model_params
    e = _engine(model, params)
    base = dict(e.serve(_arrivals(), max_new_tokens=8))

    def arr():
        yield [{"uid": 0, "tokens": PROMPTS[0], "tenant": "acme",
                "priority": "interactive"},
               {"uid": 1, "tokens": PROMPTS[1], "tenant": "umbrella",
                "priority": "batch"}]

    inj = FaultInjector([{"kind": "dispatch_exception", "frame": 1,
                          "times": 10}])
    s = RequestScheduler(SchedulerConfig())
    with pytest.raises(FrameDispatchError):
        list(e.serve(arr(), max_new_tokens=8, scheduler=s, faults=inj))
    snap = e.last_crash_snapshot
    by_uid = {r["uid"]: r for r in snap["requests"]}
    assert by_uid[0]["tenant"] == "acme"
    assert by_uid[0]["priority"] == "interactive"
    assert by_uid[1]["priority"] == "batch"

    rest = dict(e.serve(iter([[]]), max_new_tokens=8,
                        scheduler=RequestScheduler(), resume_from=snap))
    for u in (0, 1):
        np.testing.assert_array_equal(base[u], rest[u], err_msg=f"uid={u}")
    _assert_clean(e)


def test_resume_bypasses_tenant_queue_quota(tiny_model_params):
    """Known issue (a): crash-recovery resume used to route previously-live
    requests through ``sched.submit()``, so ``tenant_max_queued`` could
    shed ACCEPTED mid-flight work and silently drop its committed tokens.
    Resume ingestion now bypasses the queue quota (the ``requeue_front``
    precedent for preempted work): every snapshot request completes,
    token-identical to the crash-free run, even when the tenant's quota is
    smaller than its in-flight count — and new (non-resume) arrivals still
    face the quota."""
    model, params = tiny_model_params
    e = _engine(model, params)
    base = dict(e.serve([[(0, PROMPTS[0]), (1, PROMPTS[1])]],
                        max_new_tokens=8))
    inj = FaultInjector([{"kind": "dispatch_exception", "frame": 1,
                          "times": 10}])

    def arr():
        yield [{"uid": 0, "tokens": PROMPTS[0], "tenant": "t"},
               {"uid": 1, "tokens": PROMPTS[1], "tenant": "t"}]

    with pytest.raises(FrameDispatchError):
        list(e.serve(arr(), max_new_tokens=8, scheduler=RequestScheduler(),
                     faults=inj))
    snap = e.last_crash_snapshot
    assert {r["uid"] for r in snap["requests"]} == {0, 1}
    # a quota of 1 would have shed uid 1 pre-fix; resume must not shed
    s = RequestScheduler(SchedulerConfig(tenant_max_queued=1))
    got = dict(e.serve(iter([[]]), max_new_tokens=8, scheduler=s,
                       resume_from=snap))
    assert set(got) == {0, 1}
    assert s.stats()["shed_total"] == 0
    for u in (0, 1):
        np.testing.assert_array_equal(base[u], got[u], err_msg=f"uid={u}")
    # the quota still applies to NEW submissions on the same scheduler
    from deepspeed_tpu.inference.v2.scheduler import Request
    s.submit(Request(uid=90, tokens=PROMPTS[0], limit=8, temp=0.0,
                     eos=None, tenant="t"))
    assert s.submit(Request(uid=91, tokens=PROMPTS[1], limit=8, temp=0.0,
                            eos=None, tenant="t")) is not None
    _assert_clean(e)
    # the shed uid stays reusable
    again = dict(e.serve(iter([[(1, PROMPTS[1])]]), max_new_tokens=4))
    assert len(again[1]) == 4
    _assert_clean(e)


# ---------------------------------------------------------------------------
# abandonment with faults mid-flight (satellite: preempted-row cleanup)
# ---------------------------------------------------------------------------


def test_abandonment_after_preemption_releases_everything(tiny_model_params):
    """Abandon a scheduled serve at the retirement right after a preemption
    (victim evicted, folded, re-queued — not yet re-admitted): the ledger
    sweep must release the preempted row's descriptor and folded tokens,
    and the engine stays reusable."""
    model, params = tiny_model_params
    e = _engine(model, params)

    def arr():
        yield [{"uid": 60, "tokens": PROMPTS[1], "priority": "best_effort"},
               {"uid": 61, "tokens": PROMPTS[2], "priority": "best_effort"}]
        yield []
        yield [{"uid": 62, "tokens": PROMPTS[0], "max_new_tokens": 4,
                "priority": "interactive"}]
        for _ in range(8):
            yield []

    s = RequestScheduler(SchedulerConfig())
    for _uid, _toks in e.serve(arr(), max_new_tokens=12, frame_slots=2,
                               scheduler=s):
        break          # the interactive retires first, victim still queued
    assert s.summary["preempted"] == 1
    _assert_clean(e)
    got = dict(e.serve(iter([[(60, PROMPTS[0])]]), max_new_tokens=4,
                       frame_slots=2))
    assert len(got[60]) == 4
    _assert_clean(e)


def test_fault_log_is_bounded(tiny_model_params):
    model, params = tiny_model_params
    e = _engine(model, params, fault_log_max=4)
    assert e.fault_log.maxlen == 4


# ---------------------------------------------------------------------------
# nonfinite_policy="repair": in-graph NaN repair (pre-fault-carry rollback)
# ---------------------------------------------------------------------------


def test_nonfinite_policy_validation(tiny_model_params):
    model, params = tiny_model_params
    with pytest.raises(ValueError, match="nonfinite_policy"):
        _engine(model, params, nonfinite_policy="hope")
    with pytest.raises(ValueError, match="nonfinite_repair_limit"):
        _engine(model, params, nonfinite_policy="repair",
                nonfinite_repair_limit=0)


@pytest.fixture(scope="module")
def repair_engine(tiny_model_params):
    model, params = tiny_model_params
    return _engine(model, params, nonfinite_policy="repair",
                   nonfinite_repair_limit=2)


def test_nonfinite_repair_transient_blip_parity(repair_engine,
                                                fault_free_base):
    """A one-frame poison blip under repair: the row rolls back to its
    pre-fault carry in-graph and CONTINUES — every request, including the
    poisoned one, finishes token-identical to the fault-free run (the
    quarantine policy retires the victim instead)."""
    e = repair_engine
    inj = FaultInjector([{"kind": "poison_row", "frame": 1, "uid": 1}])
    outs = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    assert inj.fired
    assert set(outs) == set(fault_free_base)
    for u, base in fault_free_base.items():
        assert np.array_equal(outs[u], base), f"uid={u}"
    kinds = [f.kind for f in e.fault_log]
    assert "nonfinite_repaired" in kinds
    assert "poison_row" not in kinds
    assert e.telemetry.counters["nonfinite_repaired"] >= 1
    assert e.telemetry.counters["quarantined"] == 0
    _assert_clean(e)


def test_nonfinite_repair_escalates_persistent_fault(repair_engine,
                                                     fault_free_base):
    """A fault that latches nonfinite_repair_limit consecutive boundaries
    is not a blip: the row escalates to the quarantine path, siblings
    stay token-identical."""
    e = repair_engine
    e.fault_log.clear()          # the log is engine-lifetime, not per-serve
    inj = FaultInjector([{"kind": "poison_row", "frame": f, "uid": 1}
                         for f in (1, 2, 3, 4, 5)])
    outs = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    assert 1 not in outs
    for u, base in fault_free_base.items():
        if u != 1:
            assert np.array_equal(outs[u], base), f"uid={u}"
    kinds = [f.kind for f in e.fault_log]
    assert kinds.count("nonfinite_repaired") == 2     # the repair budget
    assert kinds.count("poison_row") == 1             # then escalation
    assert kinds.index("poison_row") > kinds.index("nonfinite_repaired")
    _assert_clean(e)


def test_nonfinite_repair_speculative_parity(tiny_model_params,
                                             fault_free_base):
    """The rollback selects ride the SPECULATIVE frame carry too: a blip
    during draft/verify decode repairs token-identically (greedy spec
    output already equals plain greedy, so the plain baseline is the
    reference)."""
    model, params = tiny_model_params
    e = _engine(model, params, nonfinite_policy="repair")
    e.attach_draft(model, params)                     # self-draft
    inj = FaultInjector([{"kind": "poison_row", "frame": 2, "uid": 1}])
    outs = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    for u, base in fault_free_base.items():
        assert np.array_equal(outs[u], base), f"uid={u}"
    assert e.telemetry.counters["quarantined"] == 0
    _assert_clean(e)


def test_nonfinite_repair_inframe_transfer_guard(repair_engine,
                                                 fault_free_base,
                                                 frame_transfer_guard):
    """Repair adds only frame-BOUNDARY device traffic (latch read, batched
    clear, watermark resync): the in-frame transfer guard stays green."""
    e = repair_engine
    inj = FaultInjector([{"kind": "poison_row", "frame": 1, "uid": 1}])
    outs = dict(e.serve(_arrivals(), max_new_tokens=8, faults=inj))
    assert np.array_equal(outs[1], fault_free_base[1])
    _assert_clean(e)
