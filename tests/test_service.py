"""Service-edge suite (ISSUE 14): threaded fleet driver, HTTP/SSE
front-end, edge admission, autoscaling.

Pins the tentpole contracts:

* the thread-per-replica ``FleetDriver`` is TOKEN-IDENTICAL to the
  serial cooperative router on the same schedule — plain, and through a
  scripted kill/failover (timing differs; token identity is
  timing-independent by the resume-arrival construction);
* ``ServeBoundary.emissions`` streams exactly the tokens the final
  ``(uid, tokens)`` yield reports (the SSE feed's correctness root);
* an SSE stream over the real HTTP endpoint is byte-identical to a
  direct ``serve()`` of the same request;
* a client disconnect cancels through the engine's deadline/cancel path:
  the ledger empties and every KV block returns to the allocator;
* scripted overload sheds at the EDGE with a numeric ``Retry-After``
  while every replica's local scheduler sheds nothing;
* the autoscaler's prefill<->decode flip round-trips (flip under
  queued-prompt-token pressure, flip back when it drains) with outputs
  token-identical throughout.

Wall-clock waits use generous poll-until deadlines, never timing
asserts, so the suite stays deterministic-in-outcome on slow boxes.
"""

import http.client
import json
import socket
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig,
                                                  ServeBoundary)
from deepspeed_tpu.inference.v2.faults import RouterFaultInjector
from deepspeed_tpu.inference.v2.kv_hierarchy import KVSwapTier
from deepspeed_tpu.inference.v2.router import EngineRouter, RouterConfig
from deepspeed_tpu.inference.v2.service import (AutoscaleConfig,
                                                AutoscaleController,
                                                EdgeConfig, FleetDriver,
                                                ServiceEdge)
from deepspeed_tpu.models import build_model

pytestmark = pytest.mark.service

BS, CHUNK, MAX_NEW = 16, 8, 8
RNG = np.random.default_rng(14)
PROMPTS = {u: RNG.integers(0, 200, (12,)).astype(np.int32)
           for u in range(8)}


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny", num_heads=8)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **over):
    kw = dict(kv_block_size=BS, prefill_chunk_size=CHUNK,
              max_tokens_per_step=512, dtype="float32",
              max_ragged_batch_size=4, frame_steps=2,
              frame_retry_backoff_s=0.0)
    kw.update(over)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                             params=params, max_seq_len=160)


def _wait(cond, timeout=60.0, interval=0.05):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _assert_clean(eng):
    assert not eng._ledger
    assert not eng.state.seqs
    assert eng.kv.free_blocks == eng.kv.num_blocks - 1


# ----------------------------------------------------------------------
# boundary emissions: the streaming contract at the engine level
# ----------------------------------------------------------------------

def test_boundary_emissions_match_final_output(tiny_model_params):
    model, params = tiny_model_params
    eng = _engine(model, params)

    def arrivals():
        yield [(0, PROMPTS[0]), (1, PROMPTS[1])]
        yield [(2, PROMPTS[2])]

    streamed = {0: [], 1: [], 2: []}
    finals = {}
    for ev in eng.serve(arrivals(), max_new_tokens=MAX_NEW,
                        yield_boundaries=True):
        if isinstance(ev, ServeBoundary):
            if ev.dispatched:
                assert ev.emissions is not None
                for uid, toks in ev.emissions.items():
                    streamed[uid].extend(int(t) for t in toks)
            else:
                assert ev.emissions is None
        else:
            finals[ev[0]] = [int(t) for t in ev[1]]
    assert set(finals) == {0, 1, 2}
    for uid, toks in finals.items():
        assert streamed[uid] == toks, \
            f"uid={uid}: boundary emissions {streamed[uid]} != final {toks}"
    _assert_clean(eng)


# ----------------------------------------------------------------------
# threaded driver vs serial driver
# ----------------------------------------------------------------------

def _burst():
    yield [(u, PROMPTS[u]) for u in range(4)]
    yield []
    yield [(u, PROMPTS[u]) for u in range(4, 8)]


def test_threaded_driver_parity_with_serial(tiny_model_params):
    model, params = tiny_model_params
    ref = dict(EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)}
    ).serve(_burst(), max_new_tokens=MAX_NEW))
    router = EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)},
        RouterConfig(driver="threaded"))
    out = dict(router.serve(_burst(), max_new_tokens=MAX_NEW))
    assert set(out) == set(ref)
    for u in ref:
        assert np.array_equal(out[u], ref[u]), f"uid={u}"
    assert router.counters["completions"] == len(ref)
    for r in router._replicas.values():
        _assert_clean(r.engine)


def test_threaded_driver_kill_failover_parity(tiny_model_params):
    """A scripted engine_kill mid-run: in-flight requests fail over as
    resume arrivals and the fleet's outputs stay token-identical to a
    serial NO-failure run (the serial driver is the reference, per the
    ISSUE: threaded-driver kill parity vs the serial driver)."""
    model, params = tiny_model_params

    def arrivals():
        yield [(u, PROMPTS[u]) for u in range(6)]

    ref = dict(EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)}
    ).serve(arrivals(), max_new_tokens=48))
    router = EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)},
        RouterConfig(driver="threaded", quarantine_backoff_ticks=10 ** 9))
    faults = RouterFaultInjector(
        [{"kind": "engine_kill", "tick": 6, "engine": "a"}])
    out = dict(router.serve(arrivals(), max_new_tokens=48, faults=faults))
    assert faults.fired, "scripted kill never fired"
    assert router.counters["engine_kills"] == 1
    assert router.counters["failovers"] == 1
    assert router.replica_status()["a"] == "quarantined"
    assert set(out) == set(ref)
    for u in ref:
        assert np.array_equal(out[u], ref[u]), f"uid={u} diverged"


def test_threaded_driver_scheduler_path(tiny_model_params):
    """Scheduler-driven replicas under the threaded driver: metadata
    arrivals flow, outputs match the serial scheduler run."""
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    model, params = tiny_model_params

    def arrivals():
        yield [{"uid": u, "tokens": PROMPTS[u], "tenant": f"t{u % 2}",
                "priority": "interactive" if u % 2 else "batch"}
               for u in range(6)]

    mk_sched = lambda: RequestScheduler(SchedulerConfig())   # noqa: E731
    ref = dict(EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)}
    ).serve(arrivals(), max_new_tokens=MAX_NEW,
            scheduler_factory=mk_sched))
    out = dict(EngineRouter(
        {"a": _engine(model, params), "b": _engine(model, params)},
        RouterConfig(driver="threaded")
    ).serve(arrivals(), max_new_tokens=MAX_NEW,
            scheduler_factory=mk_sched))
    assert set(out) == set(ref)
    for u in ref:
        assert np.array_equal(out[u], ref[u])


# ----------------------------------------------------------------------
# HTTP/SSE edge
# ----------------------------------------------------------------------

def _sse_collect(host, port, body, timeout=120):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, resp.read().decode(), \
                dict(resp.getheaders())
        streamed, done, buf = [], None, b""
        while True:
            line = resp.readline()
            if not line:
                break
            buf += line
            if line != b"\n":
                continue
            ev, data = None, None
            for ln in buf.decode().strip().splitlines():
                if ln.startswith("event: "):
                    ev = ln[7:]
                elif ln.startswith("data: "):
                    data = json.loads(ln[6:])
            buf = b""
            if ev == "token":
                streamed.extend(data["tokens"])
            elif ev in ("done", "error"):
                done = (ev, data)
                break
        return 200, (streamed, done), {}
    finally:
        conn.close()


@pytest.fixture
def served_fleet(tiny_model_params):
    """A started 2-replica threaded fleet + edge; torn down after."""
    model, params = tiny_model_params
    router = EngineRouter({"a": _engine(model, params),
                           "b": _engine(model, params)})
    driver = FleetDriver(router)
    driver.start(max_new_tokens=MAX_NEW)
    edge = ServiceEdge(driver, EdgeConfig(keepalive_s=0.5)).start()
    yield router, driver, edge
    edge.shutdown()
    driver.stop()


def test_sse_stream_token_identical_to_direct_serve(tiny_model_params,
                                                    served_fleet):
    model, params = tiny_model_params
    _, _, edge = served_fleet
    eng = _engine(model, params)
    ref = {}
    for uid, toks in eng.serve(
            iter([[(u, PROMPTS[u]) for u in range(4)]]),
            max_new_tokens=MAX_NEW):
        ref[uid] = [int(t) for t in toks]

    outs = {}
    errs = []

    def client(u):
        status, payload, _ = _sse_collect(
            "127.0.0.1", edge.edge_port,
            {"prompt": [int(t) for t in PROMPTS[u]],
             "max_new_tokens": MAX_NEW, "session": f"s{u}"})
        if status != 200:
            errs.append((u, status, payload))
            return
        streamed, (kind, data) = payload
        if kind != "done":
            errs.append((u, kind, data))
            return
        outs[u] = (streamed, data["tokens"])

    threads = [threading.Thread(target=client, args=(u,))
               for u in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    for u in range(4):
        streamed, done = outs[u]
        assert streamed == done == ref[u], \
            f"uid={u}: streamed {streamed} vs direct {ref[u]}"
    # the handler thread increments AFTER writing the done event the
    # client just read — poll, don't race it
    assert _wait(lambda: edge.counters["completed"] == 4, timeout=10)


def test_client_disconnect_frees_slots_and_kv(served_fleet):
    """Drop the socket mid-stream: the cancel must travel
    edge -> driver -> engine.cancel_request -> deadline machinery, and
    every slot, ledger row, and KV block must come back (allocator
    refcount assert: free == total)."""
    router, driver, edge = served_fleet
    body = json.dumps({"prompt": [int(t) for t in PROMPTS[0]],
                       "max_new_tokens": 120}).encode()
    s = socket.create_connection(("127.0.0.1", edge.edge_port))
    s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
              b"Content-Type: application/json\r\n"
              + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    buf = b""
    while b"event: token" not in buf:
        chunk = s.recv(4096)
        assert chunk, f"stream ended early: {buf!r}"
        buf += chunk
    s.close()                        # client vanishes mid-stream

    engines = [r.engine for r in router._replicas.values()]
    assert _wait(lambda: all(not e._ledger for e in engines)
                 and all(e.kv.free_blocks == e.kv.num_blocks - 1
                         for e in engines)), \
        ("disconnect did not free serving state: "
         + str([(list(e._ledger),
                 e.kv.free_blocks, e.kv.num_blocks - 1) for e in engines]))
    assert _wait(lambda: driver.in_flight() == 0)
    assert edge.counters["disconnects"] == 1
    kinds = [f.kind for e in engines for f in e.fault_log]
    assert "cancelled" in kinds
    assert sum(e.telemetry.counters["cancelled"] for e in engines) == 1


def test_edge_sheds_429_with_retry_after(tiny_model_params):
    """Scripted overload against a one-slot edge budget: excess requests
    get 429 + a numeric Retry-After BEFORE any replica's scheduler sheds
    locally; a retry after the fleet drains succeeds."""
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    model, params = tiny_model_params
    router = EngineRouter({"a": _engine(model, params)})
    driver = FleetDriver(router)
    driver.start(max_new_tokens=MAX_NEW,
                 scheduler_factory=lambda: RequestScheduler(
                     SchedulerConfig(tenant_max_queued=16)))
    edge = ServiceEdge(driver, EdgeConfig(
        max_queued_tokens=24, retry_after_min_s=1.0)).start()
    try:
        # hold the fleet busy with slow work so pressure sustains: 16
        # requests of 128 tokens against 4 slots keep 3 or more queued (36
        # tokens > 24; never more than the 16 a tenant may queue) until the
        # third wave is admitted, 128 frames on. The estimate is blind to
        # what the engine has taken from its feed until its next boundary
        # report, and the first frame compiles: POST once a held request
        # has streamed a token, when that report is in. (With 6 requests of
        # 64 tokens the 2 left queued were exactly 24, and the POST raced
        # the first report either way; it lost under load.)
        streaming = threading.Event()
        for i in range(16):
            driver.submit({"uid": 10_000 + i, "tokens": PROMPTS[i % 8],
                           "max_new_tokens": 128},
                          subscriber=lambda ev: (
                              streaming.set()
                              if ev["type"] == "tokens" else None))
        assert streaming.wait(120)
        assert _wait(lambda: driver.queued_tokens_estimate() > 24)
        status, bodytext, headers = _sse_collect(
            "127.0.0.1", edge.edge_port,
            {"prompt": [int(t) for t in PROMPTS[7]],
             "max_new_tokens": 4})
        assert status == 429, (status, bodytext)
        retry_after = headers.get("Retry-After")
        assert retry_after is not None and float(retry_after) >= 1
        payload = json.loads(bodytext)
        assert payload["error"] == "overloaded"
        assert payload["retry_after_s"] >= 1.0
        assert edge.counters["sheds"] == 1
        # the edge shed BEFORE any local scheduler shed
        assert all(r.engine.telemetry.counters["requests_shed"] == 0
                   for r in router._replicas.values())
        # capacity returns -> the retry is admitted and completes
        assert _wait(lambda: driver.in_flight() == 0, timeout=180)
        status, payload, _ = _sse_collect(
            "127.0.0.1", edge.edge_port,
            {"prompt": [int(t) for t in PROMPTS[7]],
             "max_new_tokens": 4})
        assert status == 200 and payload[1][0] == "done"
    finally:
        edge.shutdown()
        driver.stop()


def test_edge_rejects_malformed_requests(served_fleet):
    _, _, edge = served_fleet
    for bad in ({"prompt": []}, {"prompt": "text"}, {},
                {"prompt": [1, 2], "max_new_tokens": 0}):
        status, body, _ = _sse_collect("127.0.0.1", edge.edge_port, bad)
        assert status == 400, (bad, status, body)
    # unknown path
    conn = http.client.HTTPConnection("127.0.0.1", edge.edge_port,
                                      timeout=10)
    conn.request("GET", "/nope")
    assert conn.getresponse().status == 404
    conn.close()


def test_edge_metrics_and_health(served_fleet):
    _, _, edge = served_fleet
    conn = http.client.HTTPConnection("127.0.0.1", edge.edge_port,
                                      timeout=10)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    assert resp.status == 200
    assert "ds_edge_requests_total" in text
    assert "ds_edge_sheds_total" in text
    assert "ds_edge_streams_active" in text
    assert "ds_router_placements_total" in text
    assert "ds_router_scale_up_total" in text
    conn.request("GET", "/healthz")
    health = json.loads(conn.getresponse().read())
    assert set(health["replicas"]) == {"a", "b"}
    conn.close()


# ----------------------------------------------------------------------
# autoscaling
# ----------------------------------------------------------------------

def test_autoscale_flip_round_trip(tiny_model_params, tmp_path):
    """Prefill<->decode flip round trip: queued-prompt-token pressure
    flips a unified replica to prefill; once the backlog drains, the
    controller flips it back to its original role. Outputs stay
    token-identical to a direct serve throughout."""
    model, params = tiny_model_params
    tier = KVSwapTier(str(tmp_path / "tier"), shared=True)
    engines = {}
    for n in ("r0", "r1"):
        e = _engine(model, params, max_tokens_per_step=2048)
        e.attach_kv_tier(tier, tag=n)
        engines[n] = e
    router = EngineRouter(engines)
    # Two races with the machine's speed, both lost under load. A fleet
    # that goes idle inside the flip's dwell would park the flipped replica
    # (scale-down sustains in 0.2 s), and a parked replica is never flipped
    # back: both replicas stay live. And a flip is applied at the replica's
    # next boundary: where that came later than the dwell, the controller
    # asked for the same flip again, was refused, and on refusal forgot
    # that it had flipped the replica at all (``_flipped.pop``): the dwell
    # outlasts a slow boundary.
    ctl = AutoscaleController(AutoscaleConfig(
        evaluate_every_s=0.1, sustain=2, min_live_replicas=2,
        flip_prefill_high=64, flip_dwell_s=5.0,
        scale_up_queued_tokens=10 ** 9))
    driver = FleetDriver(router, autoscaler=ctl)
    driver.start(max_new_tokens=4)
    results = {}
    lock = threading.Lock()

    def sub_for(uid):
        def sub(ev):
            if ev["type"] == "done":
                with lock:
                    results[uid] = ev["tokens"]
        return sub

    rng = np.random.default_rng(21)
    longs = {100 + i: [int(t) for t in rng.integers(0, 200, (96,))]
             for i in range(12)}
    try:
        for u, p in longs.items():
            driver.submit({"uid": u, "tokens": p, "max_new_tokens": 4},
                          sub_for(u))
        assert _wait(lambda: router.counters["scale_role_flips"] >= 1,
                     timeout=120), \
            f"no flip: events={ctl.events} " \
            f"queued={driver.queued_tokens_estimate()}"
        flipped = next(e["replica"] for e in ctl.events
                       if e["action"] == "role_flip")
        assert _wait(lambda: len(results) == len(longs), timeout=180), \
            f"only {len(results)}/{len(longs)} completed"
        # backlog drained -> the controller flips it back
        assert _wait(lambda: router._roles[flipped] == "unified",
                     timeout=60), \
            f"never flipped back: roles={dict(router._roles)} " \
            f"events={ctl.events}"
        assert router.counters["scale_role_flips"] >= 2
    finally:
        driver.stop()
    eng = _engine(model, params, max_tokens_per_step=2048)
    ref = {}
    for uid, toks in eng.serve(
            iter([[{"uid": u, "tokens": p, "max_new_tokens": 4}
                   for u, p in sorted(longs.items())]]),
            max_new_tokens=4):
        ref[uid] = [int(t) for t in toks]
    for u in longs:
        assert results[u] == ref[u], f"uid={u} diverged after flips"


def test_autoscale_scale_down_and_up(tiny_model_params):
    """Idle fleet drains a replica; a later backlog rejoins it."""
    model, params = tiny_model_params
    router = EngineRouter({"r0": _engine(model, params),
                           "r1": _engine(model, params)})
    ctl = AutoscaleController(AutoscaleConfig(
        evaluate_every_s=0.1, sustain=2, min_live_replicas=1,
        scale_up_queued_tokens=32, role_flip=False))
    driver = FleetDriver(router, autoscaler=ctl)
    driver.start(max_new_tokens=MAX_NEW)
    done = []
    try:
        driver.submit({"uid": 0, "tokens": [int(t) for t in PROMPTS[0]]},
                      subscriber=lambda ev: done.append(ev)
                      if ev["type"] == "done" else None)
        assert _wait(lambda: len(done) == 1, timeout=120)
        assert _wait(lambda: router.counters["scale_down"] >= 1,
                     timeout=60), f"no scale_down: {ctl.events}"
        # the counter moves when the drain is asked for; the replica is
        # ``draining`` until a later tick finds it empty
        assert _wait(lambda: "drained" in router.replica_status().values(),
                     timeout=60), router.replica_status()
        # burst: oversubscribe the surviving replica so queued tokens
        # sustain past the watermark
        n_done = []
        for i in range(12):
            driver.submit(
                {"uid": 50 + i, "tokens": [int(t) for t in PROMPTS[i % 8]],
                 "max_new_tokens": 32},
                subscriber=lambda ev: n_done.append(ev)
                if ev["type"] == "done" else None)
        assert _wait(lambda: router.counters["scale_up"] >= 1,
                     timeout=120), \
            f"no scale_up: {ctl.events} " \
            f"queued={driver.queued_tokens_estimate()}"
        assert _wait(lambda: len(n_done) == 12, timeout=180)
    finally:
        driver.stop()
