"""Time to first token, stage by stage (ISSUE 37).

A request is stamped at every hand-over from the edge's handler to its
first SSE write; ``TraceCollector.note_first_token`` cuts that time into
``telemetry.TTFT_STAGES`` (ingress, feed, queue, prefill, egress) and the
replica's serve loop mirrors the sums into ``telemetry.counters``
(``TTFT_COUNTERS``), which ``/metrics`` and the benchmark's window deltas
read. Pinned here: the stages tile the request's time to the nanosecond;
the wait behind a frame in flight is ``feed``, not ``queue``; a request
that never had a first token adds nothing; sampling changes nothing; a bare
engine has no ingress, feed or egress; the names on ``/metrics``; the seven
readers of ``perfbench/layer_metrics``.
"""

import importlib.util
import itertools
import os
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.router import EngineRouter
from deepspeed_tpu.inference.v2.telemetry import (TTFT_COUNTERS, TTFT_STAGES,
                                                  ServingTelemetry)
from deepspeed_tpu.inference.v2.tracing import TraceCollector, validate_trace
from deepspeed_tpu.models import build_model

BS, CHUNK, MAX_NEW, FRAME_STEPS = 16, 8, 8, 2
RNG = np.random.default_rng(37)
PROMPTS = {u: RNG.integers(0, 200, (12,)).astype(np.int32)
           for u in range(4)}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny", num_heads=8)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **over):
    kw = dict(kv_block_size=BS, prefill_chunk_size=CHUNK,
              max_tokens_per_step=512, dtype="float32",
              max_ragged_batch_size=4, frame_steps=FRAME_STEPS,
              frame_retry_backoff_s=0.0)
    kw.update(over)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                             params=params, max_seq_len=160)


class TickClock:
    """Every reading is a millisecond after the last, from whatever
    thread: the order of the stamps is the order they were taken in."""

    def __init__(self):
        self._ticks = itertools.count(1)
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return next(self._ticks) * 1e-3


class HandClock:
    """Stands still until the test moves it."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _ns(t):
    return int(round(t * 1e9))


def _stages(counters):
    return [counters[f"ttft_{s}_ns"] for s in TTFT_STAGES]


def _wait(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


# ---------------------------------------------------------------------------
# (a), (d), (h): through the edge, the driver and an engine, on one clock
# ---------------------------------------------------------------------------


@pytest.mark.service
@pytest.mark.parametrize("sample_rate", [1.0, 0.0])
def test_edge_to_first_write_tiles_to_the_nanosecond(tiny_model_params,
                                                     sample_rate):
    from deepspeed_tpu.inference.v2.service import (EdgeConfig, FleetDriver,
                                                    ServiceEdge)
    model, params = tiny_model_params
    clock = TickClock()
    eng = _engine(model, params)
    eng.telemetry.clock = clock
    router = EngineRouter({"a": eng}, clock=clock)
    col = TraceCollector(sample_rate=sample_rate, clock=clock)
    driver = FleetDriver(router)
    edge = ServiceEdge(driver, EdgeConfig(), tracer=col)
    driver.start(max_new_tokens=MAX_NEW)
    counted = 0
    try:
        for n, u in enumerate(range(3), start=1):
            kind, uid, events = edge.handle_generate(
                {"prompt": [int(t) for t in PROMPTS[u]]})
            assert kind == "stream"
            # what the handler thread does with its queue: write, then say so
            while True:
                ev = events.get(timeout=60)
                if ev["type"] == "tokens":
                    edge._note_sse_write(uid, len(ev["tokens"]))
                else:
                    assert ev["type"] == "done", ev
                    break
            # the serve loop mirrors at its next boundary (idle: 5 ms);
            # ``counters`` is a new dict from the run's begin_serve on
            assert _wait(
                lambda: eng.telemetry.counters["ttft_requests"] == n), \
                col.ttft_totals("a")
            counters = eng.telemetry.counters
            if sample_rate:
                spans = col.get(uid=uid)["spans"]
                assert not validate_trace(spans)
                names = [s["name"] for s in spans]
                assert "engine.feed" in names
                assert names.index("engine.feed") < names.index("engine.queue")
                by = {s["name"]: s for s in reversed(spans)}   # the FIRST of each
                total = _ns(by["sse.write"]["t0"]) - _ns(by["edge.recv"]["t0"])
                assert counters["ttft_total_ns"] - counted == total
                # each stage is the span the README names for it
                assert by["engine.feed"]["t0"] == by["router.place"]["t0"]
                assert by["engine.feed"]["t1"] == by["engine.queue"]["t0"]
                assert by["engine.prefill"]["attrs"]["frames"] >= 1
                assert by["engine.prefill"]["attrs"]["steps"] >= FRAME_STEPS
            counted = counters["ttft_total_ns"]
            edge._trace_close(uid, "done")
        stages = _stages(counters)
        assert all(s >= 0 for s in stages), stages
        assert sum(stages) == counters["ttft_total_ns"]
        # the tick clock moves a millisecond a reading, and every stage
        # holds at least the reading that ends it
        assert all(s >= 1_000_000 * 3 for s in stages), stages
        assert counters["ttft_prefill_frames"] >= 3
        text = router.render_prometheus()
        assert 'ds_serving_ttft_requests_total{engine="a"' in text
    finally:
        driver.stop()


# ---------------------------------------------------------------------------
# (b): a frame in flight is ``feed``, not ``queue``
# ---------------------------------------------------------------------------


def test_a_frame_in_flight_is_feed_not_queue(tiny_model_params):
    """The router's part played by hand: request 1 is placed 30 ms into a
    frame of 100 ms, so it waits 70 ms in the feed; the serve loop's poll
    takes it at the boundary and admits it at once."""
    model, params = tiny_model_params
    clock = HandClock()
    eng = _engine(model, params)
    eng.telemetry.clock = clock
    col = TraceCollector(clock=clock)
    eng.telemetry.set_tracer(col, replica="solo")
    frame_t0 = []
    run_frame = eng._run_frame_resilient

    def timed_frame(*a, **kw):
        frame_t0.append(clock.now)
        clock.now += 0.1
        return run_frame(*a, **kw)

    eng._run_frame_resilient = timed_frame

    def request(uid, placed):
        tid, root = col.mint("edge.recv", t=placed - 0.002,
                             attrs={"uid": uid})
        col.note_placed(tid, placed)
        return {"uid": uid, "tokens": PROMPTS[uid],
                "trace": {"id": tid, "parent": root}}

    def arrivals():
        yield [request(0, clock.now)]
        # polled after frame 0: placed while it ran
        yield [request(1, frame_t0[0] + 0.03)]

    out = dict(eng.serve(arrivals(), max_new_tokens=MAX_NEW))
    assert set(out) == {0, 1}
    spans = {s["name"]: s for s in col.get(uid=1)["spans"]}
    feed, queue = spans["engine.feed"], spans["engine.queue"]
    assert feed["t1"] - feed["t0"] == pytest.approx(0.07)
    assert feed["t0"] == pytest.approx(frame_t0[0] + 0.03)
    # the frame it waited out: request 0 was prefilling, so it was wide
    assert feed["attrs"]["after_width"] == CHUNK
    assert feed["attrs"]["after_steps"] == FRAME_STEPS
    assert queue["t1"] - queue["t0"] == 0.0
    # request 0 met an idle server
    first = {s["name"]: s for s in col.get(uid=0)["spans"]}["engine.feed"]
    assert first["attrs"] == {"uid": 0, "after_width": 0, "after_steps": 0}
    c = eng.telemetry.counters
    assert c["ttft_requests"] == 2
    assert c["ttft_feed_ns"] == 70_000_000
    assert c["ttft_ingress_ns"] == 2 * 2_000_000
    assert c["ttft_queue_ns"] == 0
    assert c["ttft_egress_ns"] == 0                # no edge wrote anything
    assert sum(_stages(c)) == c["ttft_total_ns"]


# ---------------------------------------------------------------------------
# (d), (e): a bare engine, sampled or not
# ---------------------------------------------------------------------------


def _bare_serve(model, params, sample_rate):
    clock = HandClock()
    eng = _engine(model, params)
    eng.telemetry.clock = clock
    eng.telemetry.set_tracer(TraceCollector(sample_rate=sample_rate,
                                            clock=clock), replica="solo")
    run_frame = eng._run_frame_resilient

    def timed_frame(*a, **kw):
        clock.now += 0.05
        return run_frame(*a, **kw)

    eng._run_frame_resilient = timed_frame

    def arrivals():
        yield [(0, PROMPTS[0]), (1, PROMPTS[1])]
        yield [(2, PROMPTS[2])]

    out = dict(eng.serve(arrivals(), max_new_tokens=MAX_NEW))
    assert set(out) == {0, 1, 2}
    return {n: eng.telemetry.counters[n] for n in TTFT_COUNTERS}


def test_bare_engine_has_no_ingress_feed_or_egress(tiny_model_params):
    c = _bare_serve(*tiny_model_params, sample_rate=1.0)
    assert c["ttft_requests"] == 3
    assert c["ttft_ingress_ns"] == c["ttft_feed_ns"] == 0
    assert c["ttft_egress_ns"] == 0
    assert c["ttft_queue_ns"] == 0                 # a free slot each
    # 12 tokens of prompt at 8 a step: the first token ends frame one
    assert c["ttft_prefill_ns"] == 3 * 50_000_000 == c["ttft_total_ns"]
    assert c["ttft_prefill_frames"] == 3


def test_sampling_changes_none_of_the_counters(tiny_model_params):
    assert _bare_serve(*tiny_model_params, sample_rate=0.0) == \
        _bare_serve(*tiny_model_params, sample_rate=1.0)


def test_disabled_telemetry_counts_nothing(tiny_model_params):
    model, params = tiny_model_params
    eng = _engine(model, params)
    eng.telemetry.enabled = False
    col = TraceCollector()
    eng.telemetry.set_tracer(col, replica="solo")
    out = dict(eng.serve(iter([[(0, PROMPTS[0])]]), max_new_tokens=MAX_NEW))
    assert set(out) == {0}
    assert not any(eng.telemetry.counters[n] for n in TTFT_COUNTERS)
    assert not col.traces() and not col.ttft_totals("solo")["ttft_requests"]


# ---------------------------------------------------------------------------
# the collector alone: stamps nobody gave, the write that never comes
# ---------------------------------------------------------------------------


def test_collector_fold_units():
    col = TraceCollector(sample_rate=0.0, max_traces=2)
    # every stamp given, a stream: nothing is counted before the write
    tid, _ = col.mint("edge.recv", t=1.0, awaits_write=True)
    col.note_placed(tid, 1.004)
    got = col.note_first_token(tid, 1.5, replica="a", poll_t=1.1,
                               admit_t=1.1005, frames=2)
    assert got == {"ingress": 4_000_000, "feed": 96_000_000,
                   "queue": 500_000, "prefill": 399_500_000}
    assert col.ttft_totals("a")["ttft_requests"] == 0
    # a second replica's first emission of the same trace is no sample
    assert col.note_first_token(tid, 1.7, replica="b") is None
    # the engine retires it and the unsampled trace goes; the write counts
    col.finish(tid, 1.6, status="ok")
    assert col.get(trace_id=tid) is None
    col.note_first_write(tid, 1.503)
    col.note_first_write(tid, 1.9)                 # only the first
    a = col.ttft_totals("a")
    assert a["ttft_requests"] == 1 and a["ttft_egress_ns"] == 3_000_000
    assert a["ttft_total_ns"] == 503_000_000 and a["ttft_prefill_frames"] == 2
    assert not col.ttft_totals("b")["ttft_requests"]
    # stamps nobody gave fall on the one before: those stages are 0
    tid2, _ = col.mint("engine.recv", t=2.0)
    assert col.note_first_token(tid2, 2.25) == {
        "ingress": 0, "feed": 0, "queue": 0, "prefill": 250_000_000}
    assert col.ttft_totals(None)["ttft_total_ns"] == 250_000_000
    # streams that never write are bounded
    for i in range(20):
        t, _ = col.mint("edge.recv", awaits_write=True)
        col.note_first_token(t, 3.0)
    assert len(col._ttft_pending) <= 4 * col.max_traces


def test_collector_fold_under_threads():
    """Serve loops fold first tokens and read totals while edge handlers
    fold first writes: no sample may be lost, and a total a reader takes
    always tiles."""
    import sys
    col = TraceCollector(sample_rate=0.0, max_traces=64)
    per_thread, n_threads, torn = 200, 16, []

    def requests(replica):
        for i in range(per_thread):
            tid, _ = col.mint("edge.recv", t=float(i), awaits_write=True)
            col.note_placed(tid, i + 0.001)
            col.note_first_token(tid, i + 0.5, replica=replica,
                                 poll_t=i + 0.1, admit_t=i + 0.2, frames=1)
            col.finish(tid, i + 0.6, status="ok")
            col.note_first_write(tid, i + 0.502)
            tot = col.ttft_totals(replica)
            if sum(tot[f"ttft_{s}_ns"] for s in TTFT_STAGES) \
                    != tot["ttft_total_ns"]:
                torn.append(tot)

    threads = [threading.Thread(target=requests, args=(f"r{k % 2}",))
               for k in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads) and not torn
    for replica in ("r0", "r1"):
        tot = col.ttft_totals(replica)
        assert tot["ttft_requests"] == per_thread * n_threads // 2
        assert tot["ttft_total_ns"] == tot["ttft_requests"] * 502_000_000
    assert not col._ttft_pending


# ---------------------------------------------------------------------------
# (f): /metrics
# ---------------------------------------------------------------------------


def test_metrics_carry_the_eight_counters():
    text = ServingTelemetry().render_prometheus()
    for name in TTFT_COUNTERS:
        assert f"# TYPE ds_serving_{name}_total counter" in text
    assert len(TTFT_COUNTERS) == 8


def test_first_token_annotation_only_when_traced(tiny_model_params,
                                                 monkeypatch):
    model, params = tiny_model_params
    written = []

    class Annotation:
        def __init__(self, name, **stats):
            written.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    for trace in (False, True):
        eng = _engine(model, params)
        eng.telemetry.set_tracer(TraceCollector(), replica="solo")
        eng.telemetry.trace = trace
        dict(eng.serve(iter([[(0, PROMPTS[0])]]), max_new_tokens=MAX_NEW))
        firsts = [s for n, s in written if n == "serve/first_token"]
        if not trace:
            assert not written
            continue
        assert len(firsts) == 1
        assert firsts[0]["uid"] == 0 and firsts[0]["prompt_tokens"] == 12
        assert firsts[0]["frames"] == 1 and firsts[0]["steps"] == FRAME_STEPS
        assert {"ingress_ns", "feed_ns", "queue_ns", "prefill_ns",
                "mono_ns"} <= set(firsts[0])
        assert abs(firsts[0]["mono_ns"] - time.monotonic_ns()) < 600e9


# ---------------------------------------------------------------------------
# (g): the benchmark's readers
# ---------------------------------------------------------------------------


def _reader(name):
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "perfbench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


CTX = {
    "kind": "serve", "t0": 15.0, "t1": 66.0,
    "counters": {"ttft_requests": 4, "ttft_total_ns": 1_000_000_000,
                 "ttft_ingress_ns": 12_000_000, "ttft_feed_ns": 180_000_000,
                 "ttft_queue_ns": 400_000, "ttft_prefill_ns": 800_000_000,
                 "ttft_egress_ns": 7_600_000, "ttft_prefill_frames": 6},
    # first tokens at the client: two in the window (means 260 ms), one
    # before it, one request that never got one
    "records": [
        {"sched_t": 20.0, "first_t": 20.25}, {"sched_t": 30.0, "first_t": 30.27},
        {"sched_t": 14.0, "first_t": 14.5}, {"sched_t": 65.9, "first_t": None}],
}


@pytest.mark.parametrize("name, want", [
    ("ttft_ingress_ms", 3.0), ("ttft_feed_wait_ms", 45.0),
    ("ttft_queue_ms", 0.1), ("ttft_prefill_ms", 200.0),
    ("ttft_egress_ms", 1.9), ("ttft_outside_program_ms", 10.0),
    ("prefill_frames_per_request", 1.5)])
def test_layer_metric_readers(name, want):
    read = _reader(name)
    assert read(CTX) == pytest.approx(want)
    # a window with no first token, and a program without the counters
    idle = dict(CTX, counters=dict(CTX["counters"], ttft_requests=0))
    assert read(idle) is None
    assert read(dict(CTX, counters={"frames": 3})) is None
    assert read({"kind": "train"}) is None


def test_the_five_stage_readers_tile_the_total():
    parts = sum(_reader(n)(CTX) for n in (
        "ttft_ingress_ms", "ttft_feed_wait_ms", "ttft_queue_ms",
        "ttft_prefill_ms", "ttft_egress_ms"))
    c = CTX["counters"]
    assert parts == pytest.approx(c["ttft_total_ns"] / c["ttft_requests"] / 1e6,
                                  abs=1e-9)
