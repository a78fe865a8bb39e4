"""SLO-aware request scheduler tests.

Two layers, matching the subsystem's split:

* **Policy units** — ``RequestScheduler`` against a fake engine: strict
  priority dispatch, aging, weighted fair-share virtual time, tenant
  quotas, SLO pressure transitions, frame-steps caps. Pure host logic,
  no model, no jit.

* **Serving integration** — a shared tiny engine driving ``serve(...,
  scheduler=)`` on deterministic burst schedules: the overload acceptance
  behaviors ((a) interactive never waits behind best-effort, (b) aging
  eventually admits starved best-effort, (c) preempted rows are
  token-identical to an unpreempted greedy run, (d) the no-scheduler path
  is FIFO-identical), plus shedding/deferral under a scripted SLO breach,
  the zero-in-frame-transfer guard, and the telemetry satellites (HTTP
  /metrics endpoint, frame-steps decision trace, labeled counters).

Engine tests share one module-scope engine and a single slot-table shape
(``frame_slots=2``) so the compiled frame programs are reused across
serves — the same budget discipline as the speculative suite.
"""

import logging
import urllib.request

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduler import (BATCH, BEST_EFFORT,
                                                  INTERACTIVE, Request,
                                                  RequestScheduler,
                                                  SchedulerConfig,
                                                  normalize_priority)
from deepspeed_tpu.inference.v2.telemetry import ServingTelemetry
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils.logging import logger as ds_logger


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


# ---------------------------------------------------------------------------
# policy units (no model)
# ---------------------------------------------------------------------------


class _FakeKV:
    def blocks_for(self, n):
        return -(-n // 16)


class _FakeEngine:
    def __init__(self, enabled=True):
        self.kv = _FakeKV()
        self.telemetry = ServingTelemetry(enabled=enabled,
                                          clock=lambda: 0.0)


def _req(uid, tenant="default", prio=INTERACTIVE, n=8, limit=25, slo=None):
    return Request(uid=uid, tokens=np.zeros(n, np.int32), limit=limit,
                   temp=0.0, eos=None, tenant=tenant, priority=prio,
                   slo_ms=slo)


def _sched(**cfg):
    s = RequestScheduler(SchedulerConfig(**cfg))
    s.begin_serve(_FakeEngine())
    return s


def test_normalize_priority():
    assert normalize_priority(None) == INTERACTIVE
    assert normalize_priority("batch") == BATCH
    assert normalize_priority(2) == BEST_EFFORT
    with pytest.raises(ValueError, match="unknown priority"):
        normalize_priority("bulk")
    with pytest.raises(ValueError, match="out of range"):
        normalize_priority(3)


def test_config_validation():
    with pytest.raises(ValueError, match="aging_frames"):
        SchedulerConfig(aging_frames=0)
    with pytest.raises(ValueError, match="tenant_weights"):
        SchedulerConfig(tenant_weights={"a": 0.0})
    with pytest.raises(ValueError, match="tenant_max_live"):
        SchedulerConfig(tenant_max_live=0)
    with pytest.raises(ValueError, match="defer"):
        SchedulerConfig(slo_defer_threshold=1.5, slo_shed_threshold=1.0)


def test_strict_priority_dispatch():
    """All effective-interactive admissions precede any batch one, which
    precede any best-effort one — regardless of arrival order."""
    s = _sched()
    s.submit(_req(0, prio=BEST_EFFORT))
    s.submit(_req(1, prio=BATCH))
    s.submit(_req(2, prio=INTERACTIVE))
    s.on_boundary({}, live_count=1)
    order = [r.uid for r, _ in s.pick(3, lambda r: object(), live_count=1)]
    assert order == [2, 1, 0]


def test_weighted_fair_share_virtual_time():
    """Under one-admission-per-boundary starvation, tenants split service
    in proportion to their weights (the regime where per-visit-quantum DRR
    would collapse to 1:1)."""
    s = _sched(tenant_weights={"a": 2.0, "b": 1.0})
    uid = 0
    for _ in range(40):
        s.submit(_req(uid, "a")); uid += 1
        s.submit(_req(uid, "b")); uid += 1
    admitted = {"a": 0, "b": 0}
    for _ in range(30):
        s.on_boundary({}, live_count=1)
        for r, _seq in s.pick(1, lambda r: object(), live_count=1):
            admitted[r.tenant] += 1
            s.on_retire(r.uid)
    assert admitted["a"] == 20 and admitted["b"] == 10, admitted


def test_idle_tenant_returns_without_burst():
    """A tenant coming back from idle is synced to the active floor: it
    does not cash in virtual time 'saved' while absent."""
    s = _sched()
    uid = 0
    for _ in range(20):
        s.submit(_req(uid, "busy")); uid += 1
    for _ in range(10):               # busy tenant accumulates vtime
        s.on_boundary({}, live_count=1)
        for r, _seq in s.pick(1, lambda r: object(), live_count=1):
            s.on_retire(r.uid)
    s.submit(_req(100, "idler"))      # activation syncs to busy's clock
    s.submit(_req(101, "idler"))
    s.submit(_req(102, "idler"))
    s.on_boundary({}, live_count=1)
    got = [r.tenant for r, _ in s.pick(4, lambda r: object(), live_count=1)]
    # fair alternation, not an idler monopoly on its stale zero clock
    assert got.count("idler") <= 2, got


def test_tenant_quotas_shed_and_block():
    s = _sched(tenant_max_queued=2, tenant_max_live=1)
    assert s.submit(_req(0, "t")) is None
    assert s.submit(_req(1, "t")) is None
    shed = s.submit(_req(2, "t"))
    assert shed is not None and shed.reason == "tenant_queue_full"
    assert shed.uid == 2 and shed.tenant == "t"
    assert s.shed_log[-1] is shed
    s.on_boundary({}, live_count=1)
    admits = s.pick(4, lambda r: object(), live_count=1)
    assert [r.uid for r, _ in admits] == [0]   # max_live=1 blocks the second
    s.on_retire(0)
    s.on_boundary({}, live_count=1)
    assert [r.uid for r, _ in s.pick(4, lambda r: object(), live_count=1)] \
        == [1]


def test_aging_promotes_one_class_per_window():
    s = _sched(aging_frames=2)
    s.submit(_req(0, prio=BEST_EFFORT))
    r = next(iter(s._queues[(BEST_EFFORT, "default")]))
    assert s._eff(r) == BEST_EFFORT
    for _ in range(2):
        s.on_boundary({}, live_count=1)
    assert s._eff(r) == BATCH
    for _ in range(2):
        s.on_boundary({}, live_count=1)
    assert s._eff(r) == INTERACTIVE
    # a fresh interactive arrival loses the FIFO tie-break to the aged one
    s.submit(_req(1, prio=INTERACTIVE))
    got = [rq.uid for rq, _ in s.pick(1, lambda r: object(), live_count=1)]
    assert got == [0]


def test_slo_pressure_transitions_shed_and_defer():
    s = _sched(slo_ttft_ms=100.0)
    s.submit(_req(0, prio=INTERACTIVE))
    s.submit(_req(1, prio=BATCH))
    s.submit(_req(2, prio=BEST_EFFORT))
    # below defer threshold: everything admits
    sheds = s.on_boundary({"ttft_p90_ms": 50.0}, live_count=1)
    assert not sheds and s.pressure == 0 and s.risk == 0.5
    assert len(s.pick(3, lambda r: object(), live_count=1)) == 3
    for u in (0, 1, 2):
        s.on_retire(u)
    # at-risk: batch/best-effort deferred (stay queued), interactive flows
    s.submit(_req(3, prio=INTERACTIVE))
    s.submit(_req(4, prio=BATCH))
    s.submit(_req(5, prio=BEST_EFFORT))
    sheds = s.on_boundary({"ttft_p90_ms": 90.0}, live_count=1)
    assert not sheds and s.pressure == 1
    assert [r.uid for r, _ in s.pick(3, lambda r: object(), live_count=1)] \
        == [3]
    assert s.queued_count() == 2
    # critical: queued best-effort shed with a structured reason
    sheds = s.on_boundary({"ttft_p90_ms": 150.0}, live_count=1)
    assert s.pressure == 2
    assert [x.uid for x in sheds] == [5]
    assert sheds[0].reason == "slo_pressure" and sheds[0].risk == 1.5
    assert sheds[0].priority == "best_effort"
    assert not s.is_queued(5) and s.queued_count() == 1
    # an idle machine drains its queue instead of deferring it forever
    assert [r.uid for r, _ in s.pick(3, lambda r: object(), live_count=0)] \
        == [4]


def test_preempted_requests_never_shed():
    """A preempted request is mid-flight (accepted, tokens emitted): the
    pressure loop must never shed it, only fresh best-effort arrivals."""
    s = _sched(slo_ttft_ms=100.0)
    s.submit(_req(0, prio=BEST_EFFORT))
    s.on_boundary({}, live_count=1)
    [(rq, _seq)] = s.pick(1, lambda r: object(), live_count=1)
    s.requeue_front(s.on_evict(rq.uid))        # preempt it back to queue
    s.submit(_req(1, prio=BEST_EFFORT))        # fresh, sheddable
    sheds = s.on_boundary({"ttft_p90_ms": 500.0}, live_count=1)
    assert [x.uid for x in sheds] == [1]
    assert s.is_queued(0) and not s.is_queued(1)


def test_preemption_futility_guard():
    """No eviction when even the freed blocks could not fit the waiting
    interactive request — evicting would only buy a re-prefill thrash
    loop (victim recomputed every boundary, interactive still stuck)."""
    s = _sched()
    s.submit(_req(0, prio=BEST_EFFORT, n=8, limit=25))      # cost 3 blocks
    s.on_boundary({}, live_count=0)
    [(victim, _seq)] = s.pick(1, lambda r: object(), live_count=0)
    s.submit(_req(1, prio=INTERACTIVE, n=8, limit=500))     # cost 32 blocks
    s.on_boundary({}, live_count=1)
    assert s.preempt_wanted(free_slots=0)
    committed = {victim.uid: 4}
    # 3 victim blocks + 5 free < 32 needed: futile, no victims
    assert s.pick_victims(committed, free_blocks=5) == []
    # with enough free blocks the eviction goes ahead
    assert s.pick_victims(committed, free_blocks=30) == [victim.uid]
    # and with no capacity information the guard stays out of the way
    assert s.pick_victims(committed) == [victim.uid]


def test_per_request_slo_tightens_target():
    s = _sched(slo_ttft_ms=1000.0)
    s.submit(_req(0, prio=INTERACTIVE, slo=10.0))
    s.on_boundary({"ttft_p90_ms": 20.0}, live_count=1)
    assert s.risk == 2.0 and s.pressure == 2    # 20ms vs the 10ms request


def test_frame_steps_cap_buckets():
    s = _sched(slo_ttft_ms=100.0)
    assert s.frame_steps_cap(8) == 8
    s.submit(_req(0))
    s.on_boundary({"ttft_p90_ms": 90.0}, live_count=1)     # pressure 1
    assert s.frame_steps_cap(8) == 4
    s.on_boundary({"ttft_p90_ms": 200.0}, live_count=1)    # pressure 2
    assert s.frame_steps_cap(8) == 2
    assert s.frame_steps_cap(1) == 1


def test_pick_raises_on_impossible_fit_with_empty_table():
    s = _sched()
    s.submit(_req(0, n=500, limit=500))
    s.on_boundary({}, live_count=0)
    with pytest.raises(RuntimeError, match="can never fit"):
        s.pick(4, lambda r: None, live_count=0)


def test_defer_warning_includes_reserved_blocks():
    tel = ServingTelemetry(clock=lambda: 0.0)
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = Capture()
    ds_logger.addHandler(h)
    try:
        tel.on_defer(queue_depth=3, frame_steps=8, free_slots=2,
                     free_blocks=7, reserved_blocks=5)
    finally:
        ds_logger.removeHandler(h)
    (msg,) = [m for m in records if "admission deferred" in m]
    # free_blocks is net of this round's reservations; the warning carries
    # the reservation so standing pressure and a busy admission round are
    # distinguishable
    assert "free_kv_blocks=7" in msg
    assert "kv_blocks_reserved_this_round=5" in msg


def test_http_metrics_endpoint():
    tel = ServingTelemetry(clock=lambda: 0.0)
    tel.counters["tokens_emitted"] = 42
    srv = tel.serve_metrics_http(0)
    try:
        base = f"http://127.0.0.1:{srv.metrics_port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "ds_serving_tokens_emitted_total 42" in body
        tel.counters["tokens_emitted"] = 43      # scrapes render fresh
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as resp:
            assert "ds_serving_tokens_emitted_total 43" in resp.read().decode()
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/other", timeout=5)
        assert err.value.code == 404
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# serving integration (shared tiny engine, frame_slots=2 throughout)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny")
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **over):
    kw = dict(kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
              dtype="float32", max_ragged_batch_size=8, frame_steps=4)
    kw.update(over)
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                          max_seq_len=128)
    e.params = jax.device_put(params)
    return e


@pytest.fixture(scope="module")
def served_engine(tiny_model_params):
    """ONE engine for every integration test: serve() leaves the engine
    clean, and a single slot-table shape keeps the jit cache shared."""
    model, params = tiny_model_params
    e = _engine(model, params)
    e.telemetry.record_spans = True
    return e


PROMPTS = {u: np.random.default_rng(5).integers(0, 200, (120,))
           .astype(np.int32)[o:o + n]
           for u, (o, n) in enumerate(
               ((0, 7), (10, 14), (30, 9), (50, 5), (60, 11), (75, 13)))}


def _spans_by_uid(tel, uids):
    """Latest recorded span per uid (the deque persists across serves, so
    tests use disjoint uid ranges or read right after their serve)."""
    out = {}
    for s in tel.spans:
        if s["uid"] in uids:
            out[s["uid"]] = s
    return out


def test_no_scheduler_path_is_fifo_identical(served_engine):
    """(d) scheduler=None keeps the FIFO code path: outputs AND retirement
    order match a default-scheduler run (single tenant, one class, no SLO
    — the policy reduces to FIFO) and the telemetry counters agree."""
    e = served_engine

    def arrivals():
        sched = {0: [0, 1], 2: [2], 3: [3]}
        for k in range(5):
            yield [(u, PROMPTS[u]) for u in sched.get(k, [])]

    base = list(e.serve(arrivals(), max_new_tokens=8))
    base_counters = dict(e.telemetry.counters)
    got = list(e.serve(arrivals(), max_new_tokens=8,
                       scheduler=RequestScheduler()))
    assert [u for u, _ in base] == [u for u, _ in got]   # retirement order
    for (u1, t1), (u2, t2) in zip(base, got):
        np.testing.assert_array_equal(t1, t2, err_msg=f"uid={u1}")
    for k in ("tokens_emitted", "requests_admitted", "requests_retired"):
        assert e.telemetry.counters[k] == base_counters[k], k
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_interactive_never_waits_behind_best_effort(served_engine):
    """(a) burst of best-effort fills the table; interactive arrivals that
    show up later are admitted before every still-queued best-effort one
    (preemption off: this is pure queue ordering)."""
    e = served_engine
    be = {u: PROMPTS[u % 6] for u in (20, 21, 22, 23)}
    ia = {u: PROMPTS[u % 6] for u in (30, 31)}

    def arrivals():
        yield [{"uid": u, "tokens": be[u], "priority": "best_effort"}
               for u in be]
        yield []
        yield [{"uid": u, "tokens": ia[u], "priority": "interactive"}
               for u in ia]

    s = RequestScheduler(SchedulerConfig(preemption=False))
    got = dict(e.serve(arrivals(), max_new_tokens=6, frame_slots=2,
                       scheduler=s))
    assert set(got) == set(be) | set(ia)
    spans = _spans_by_uid(e.telemetry, set(be) | set(ia))
    # two best-effort admitted before the interactives even arrived; the
    # OTHER two queued best-effort must admit strictly after both
    # interactives
    be_admits = sorted(spans[u]["admit_t"] for u in be)
    ia_admits = [spans[u]["admit_t"] for u in ia]
    assert max(ia_admits) < be_admits[2], (be_admits, ia_admits)
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_aging_admits_starved_best_effort(served_engine):
    """(b) a steady interactive stream would starve best-effort under pure
    strict priority; aging promotes the starved request so it eventually
    wins the FIFO tie-break over fresher interactive arrivals."""
    e = served_engine
    n_ia = 6

    def arrivals():
        yield [{"uid": 40, "tokens": PROMPTS[3], "priority": "interactive"},
               {"uid": 41, "tokens": PROMPTS[4], "priority": "interactive"},
               {"uid": 50, "tokens": PROMPTS[5], "priority": "best_effort"}]
        for k in range(n_ia):
            yield [{"uid": 42 + k, "tokens": PROMPTS[k % 6],
                    "priority": "interactive"}]

    def run(aging_frames):
        s = RequestScheduler(SchedulerConfig(preemption=False,
                                             aging_frames=aging_frames))
        got = dict(e.serve(arrivals(), max_new_tokens=6, frame_slots=2,
                           scheduler=s))
        uids = {40, 41, 50} | {42 + k for k in range(n_ia)}
        assert set(got) == uids
        spans = _spans_by_uid(e.telemetry, uids)
        later_ia = max(spans[u]["admit_t"] for u in uids if u != 50)
        return spans[50]["admit_t"], later_ia

    be_admit, last_ia = run(aging_frames=2)
    assert be_admit < last_ia     # aged best-effort beat a fresh interactive
    be_admit, last_ia = run(aging_frames=1000)
    assert be_admit > last_ia     # without aging it drains dead last


def test_preemption_token_parity(served_engine):
    """(c) an interactive arrival preempts a live best-effort row; the
    preempted row re-prefills from its committed prefix and finishes with
    output token-identical to an unpreempted greedy run."""
    e = served_engine

    def arrivals():
        yield [{"uid": 60, "tokens": PROMPTS[1], "priority": "best_effort"},
               {"uid": 61, "tokens": PROMPTS[2], "priority": "best_effort"}]
        yield []
        yield [{"uid": 62, "tokens": PROMPTS[0], "max_new_tokens": 4,
                "priority": "interactive"}]

    s = RequestScheduler()
    got = dict(e.serve(arrivals(), max_new_tokens=12, frame_slots=2,
                       scheduler=s))
    assert s.summary["preempted"] == 1
    assert e.telemetry.counters["requests_preempted"] == 1
    assert len(got[62]) == 4
    preempt_counters = dict(e.telemetry.counters)
    prom = e.telemetry.render_prometheus()
    assert "ds_serving_requests_preempted_total 1" in prom
    assert 'class="best_effort"' in prom
    # solo (unpreempted) baselines on the same engine
    for uid in (60, 61):
        solo = dict(e.serve(iter([[(uid, dict(
            [(60, PROMPTS[1]), (61, PROMPTS[2])])[uid])]]),
            max_new_tokens=12, frame_slots=2))
        np.testing.assert_array_equal(solo[uid], got[uid],
                                      err_msg=f"uid={uid}")
    assert preempt_counters["requests_retired"] == 3
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    assert not e.state.seqs


def test_shed_and_defer_under_slo_pressure(served_engine):
    """An impossible TTFT target drives the control loop critical after the
    first interactive emission: a later best-effort arrival is shed with a
    structured reason, a batch arrival is deferred until the machine
    drains, and frames shrink to the pressure-capped bucket."""
    e = served_engine

    def arrivals():
        yield [{"uid": 70, "tokens": PROMPTS[0], "max_new_tokens": 16,
                "priority": "interactive"}]
        yield []
        yield [{"uid": 71, "tokens": PROMPTS[3], "priority": "best_effort"}]
        yield [{"uid": 72, "tokens": PROMPTS[4], "max_new_tokens": 4,
                "priority": "batch"}]

    s = RequestScheduler(SchedulerConfig(slo_ttft_ms=1e-4))
    got = dict(e.serve(arrivals(), max_new_tokens=16, frame_slots=2,
                       scheduler=s))
    assert set(got) == {70, 72}            # 71 shed, never yielded
    assert len(got[72]) == 4               # deferred batch still completed
    shed = [x for x in s.shed_log if x.uid == 71]
    assert len(shed) == 1
    assert shed[0].reason == "slo_pressure"
    assert shed[0].priority == "best_effort" and shed[0].risk > 1.0
    assert e.telemetry.counters["requests_shed"] == 1
    assert e.telemetry.gauges["slo_risk"] > 1.0
    prom = e.telemetry.render_prometheus()
    assert "ds_serving_requests_shed_total 1" in prom
    # the batch row waited for the drain: admitted only after the
    # interactive retired
    spans = _spans_by_uid(e.telemetry, {70, 72})
    assert spans[72]["admit_t"] >= spans[70]["retire_t"]
    # pressure capped the frame length below the configured 4
    hist = e.serve_stats["frame_steps_hist"]
    assert any(k < 4 for k in hist), hist
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    # the shed request left no stale descriptor behind (uid stays reusable)
    assert not e.state.seqs


def test_scheduler_adds_no_in_frame_transfers(served_engine,
                                              frame_transfer_guard):
    """Acceptance guard: the whole policy layer (including a preemption)
    runs at frame boundaries — frame dispatch stays free of device→host
    transfers (conftest's shared guard; graft-lint GL001 is the static
    twin)."""
    e = served_engine

    def arrivals():
        yield [{"uid": 80, "tokens": PROMPTS[1], "priority": "best_effort"},
               {"uid": 81, "tokens": PROMPTS[2], "priority": "best_effort"}]
        yield []
        yield [{"uid": 82, "tokens": PROMPTS[0], "max_new_tokens": 4,
                "priority": "interactive"}]

    s = RequestScheduler()
    got = dict(e.serve(arrivals(), max_new_tokens=12, frame_slots=2,
                       scheduler=s))
    assert set(got) == {80, 81, 82}
    assert s.summary["preempted"] == 1     # the eviction ran under the guard
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_frame_steps_decision_trace(served_engine):
    """Satellite (d): every frame's sizing decision lands in the bounded
    ring surfaced via serve_stats and the Prometheus gauge."""
    e = served_engine
    got = dict(e.serve(iter([[(90, PROMPTS[0])]]), max_new_tokens=6,
                       frame_slots=2))
    assert len(got[90]) == 6
    trace = list(e.serve_stats["frame_steps_trace"])
    assert len(trace) == e.serve_stats["frames"]
    for rec in trace:
        assert set(rec) == {"frame", "ewma", "saturated", "steps"}
    # fixed frame_steps, no pressure: the wide frame ends with its prompt's
    # one chunk, at half the length at least (the request's second token
    # leaves with its first), and the narrow frame behind it runs the whole
    # length to the sixth
    assert [rec["steps"] for rec in trace] == [2, 4]
    assert [rec["frame"] for rec in trace] == list(range(len(trace)))
    prom = e.telemetry.render_prometheus()
    assert "ds_serving_frame_steps_chosen 4" in prom
    snap = e.telemetry.snapshot()
    assert snap["frame_steps_trace"] == trace


def test_dict_arrivals_without_scheduler(served_engine):
    """Dict arrivals are valid on the FIFO path too — the scheduling fields
    are simply inert — and produce identical output to tuple arrivals."""
    e = served_engine
    base = dict(e.serve(iter([[(95, PROMPTS[2])]]), max_new_tokens=6,
                        frame_slots=2))
    got = dict(e.serve(iter([[{"uid": 96, "tokens": PROMPTS[2],
                               "tenant": "t", "priority": "batch",
                               "slo_ms": 5.0}]]),
                       max_new_tokens=6, frame_slots=2))
    np.testing.assert_array_equal(base[95], got[96])


def test_tenant_labels_exported(served_engine):
    """Scheduler runs label the ds_serving_* counters per class/tenant and
    feed the per-class TTFT histogram."""
    e = served_engine

    def arrivals():
        yield [{"uid": 97, "tokens": PROMPTS[0], "tenant": "acme",
                "priority": "interactive"},
               {"uid": 98, "tokens": PROMPTS[3], "tenant": "umbrella",
                "priority": "batch"}]

    got = dict(e.serve(arrivals(), max_new_tokens=6, frame_slots=2,
                       scheduler=RequestScheduler()))
    assert set(got) == {97, 98}
    prom = e.telemetry.render_prometheus()
    assert 'ds_serving_requests_retired_total{class="interactive",' \
        'tenant="acme"} 1' in prom
    assert 'ds_serving_requests_retired_total{class="batch",' \
        'tenant="umbrella"} 1' in prom
    assert 'ds_serving_tokens_emitted_total{class="interactive",' \
        'tenant="acme"} 6' in prom
    assert 'ds_serving_class_ttft_p90_seconds{class="interactive"}' in prom
    snap = e.telemetry.snapshot()
    assert snap["class_ttft_p90_ms"]["interactive"] > 0
    assert snap["labeled"]["requests_admitted"][
        "class=batch,tenant=umbrella"] == 1


def test_abandonment_releases_scheduler_state(served_engine):
    """Breaking out of a scheduled serve with queued + live + preempted
    requests must strand nothing: descriptors flushed, KV drained, engine
    reusable."""
    e = served_engine

    def arrivals():
        yield [{"uid": 110 + i, "tokens": PROMPTS[i % 6],
                "priority": "best_effort"} for i in range(5)]
        yield []
        yield [{"uid": 120, "tokens": PROMPTS[0],
                "priority": "interactive"}]
        yield []

    s = RequestScheduler()
    for _uid, _toks in e.serve(arrivals(), max_new_tokens=12, frame_slots=2,
                               scheduler=s):
        break                              # abandon mid-flight
    assert not e.state.seqs
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    got = dict(e.serve(iter([[(110, PROMPTS[0])]]), max_new_tokens=4,
                       frame_slots=2))
    assert len(got[110]) == 4


# ---------------------------------------------------------------------------
# admission lookahead (ISSUE 14 satellite): slots reserved for
# EWMA-predicted interactive arrivals
# ---------------------------------------------------------------------------


def test_lookahead_reserves_slots_for_predicted_interactive():
    """Scripted schedule: one fresh interactive submission per boundary
    establishes the EWMA; a batch burst then cannot fill the last
    (reserved) slot, and the interactive arrival that lands one boundary
    later admits immediately — no wait, no preemption."""
    s = _sched(lookahead_reserve=True, lookahead_ewma_alpha=1.0,
               lookahead_max_reserve=2)
    # boundaries 1..3: one interactive arrival each -> ewma == 1.0
    for b in range(3):
        s.submit(_req(100 + b, prio=INTERACTIVE))
        s.on_boundary({}, live_count=1)
        picked = s.pick(4, lambda r: object(), live_count=1)
        assert [r.uid for r, _ in picked] == [100 + b]
    assert s._ia_ewma == 1.0
    assert s.lookahead_reserved(4) == 1
    # batch burst an instant before the predicted chat arrival: with 2
    # free slots it may take only ONE (the other is reserved)
    for u in range(4):
        s.submit(_req(200 + u, prio=BATCH))
    admitted = s.pick(2, lambda r: object(), live_count=2)
    assert [r.uid for r, _ in admitted] == [200]
    # ...and the predicted interactive arrival admits into the held slot
    s.submit(_req(300, prio=INTERACTIVE))
    s.on_boundary({}, live_count=3)
    admitted = s.pick(1, lambda r: object(), live_count=3)
    assert [r.uid for r, _ in admitted] == [300]


def test_lookahead_off_burst_fills_every_slot():
    """Control: without the reserve, the same burst takes both slots and
    the chat arrival must wait for a retirement (or a preemption)."""
    s = _sched()                      # lookahead_reserve defaults False
    for b in range(3):
        s.submit(_req(100 + b, prio=INTERACTIVE))
        s.on_boundary({}, live_count=1)
        s.pick(4, lambda r: object(), live_count=1)
    for u in range(4):
        s.submit(_req(200 + u, prio=BATCH))
    admitted = s.pick(2, lambda r: object(), live_count=2)
    assert [r.uid for r, _ in admitted] == [200, 201]
    s.submit(_req(300, prio=INTERACTIVE))
    s.on_boundary({}, live_count=4)
    assert s.pick(0, lambda r: object(), live_count=4) == []
    assert s.is_queued(300)


def test_lookahead_reserve_decays_and_never_starves_batch():
    """The reserve decays with the EWMA once interactive traffic stops,
    and it never blocks the LAST admissible slot (a pure-batch workload
    still makes progress at free_slots=1)."""
    s = _sched(lookahead_reserve=True, lookahead_ewma_alpha=0.5,
               lookahead_max_reserve=4)
    for b in range(4):
        s.submit(_req(100 + b, prio=INTERACTIVE))
        s.on_boundary({}, live_count=1)
        s.pick(8, lambda r: object(), live_count=1)
    assert s.lookahead_reserved(8) >= 1
    # free_slots=1: the reserve must never eat the last slot
    assert s.lookahead_reserved(1) == 0
    s.submit(_req(500, prio=BATCH))
    assert [r.uid for r, _ in s.pick(1, lambda r: object(),
                                     live_count=1)] == [500]
    # interactive traffic stops: the EWMA (and the reserve) decay to zero
    for _ in range(12):
        s.on_boundary({}, live_count=1)
    assert s.lookahead_reserved(8) == 0
    # aged batch/BE work ignores the reserve (anti-starvation outranks
    # lookahead, like deferral)
    s2 = _sched(lookahead_reserve=True, lookahead_ewma_alpha=1.0,
                aging_frames=1)
    s2.submit(_req(0, prio=INTERACTIVE))
    s2.on_boundary({}, live_count=1)
    s2.pick(4, lambda r: object(), live_count=1)     # ewma == 1
    s2.submit(_req(1, prio=BATCH))
    s2.on_boundary({}, live_count=1)                 # ages 1 -> eff O(1)
    s2.on_boundary({}, live_count=1)
    admitted = s2.pick(1, lambda r: object(), live_count=1)
    assert [r.uid for r, _ in admitted] == [1], \
        "an aged-to-interactive request must ignore the reserve"


def test_lookahead_config_validation():
    with pytest.raises(ValueError, match="lookahead_ewma_alpha"):
        SchedulerConfig(lookahead_ewma_alpha=0.0)
    with pytest.raises(ValueError, match="lookahead_max_reserve"):
        SchedulerConfig(lookahead_max_reserve=-1)
