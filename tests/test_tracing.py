"""Distributed tracing + crash flight recorder suite (ISSUE 15).

Pins the tentpole contracts:

* ONE request = ONE connected span tree — shared trace id, exactly one
  root, intact parent chain (``tracing.validate_trace``) — across a
  scripted mid-stream kill/failover AND a prefill→decode handoff, with
  spans from BOTH replicas in the same tree;
* fleet-merged TTFT/E2E attribution: ``ds_fleet_ttft_ms`` records
  exactly ONE first-token sample per trace id, spanning handoff and
  failover (the PR-11 "record nothing on resumed spans" workaround is
  replaced; per-replica series stay resumed-blind);
* sampling: ``trace_sample_rate`` drops completed traces but faulted /
  shed / handed-off / failed-over / cancelled requests are ALWAYS kept;
* the flight recorder's bounded event ring, the postmortem bundle
  written on replica DEAD (killed replica's last-N events + every
  in-flight request's trace), and the Chrome-trace export shape;
* the ``dstpu_trace`` CLI renders an export and exits nonzero on a
  disconnected trace (the CI gate);
* cancel (client disconnect) and scheduler-shed requests still yield
  closed, connected, always-sampled traces.

Everything host-side at frame boundaries: under GRAFT_SANITIZE the
in-frame transfer guard runs over this whole suite (conftest lists it in
SERVING_SUITES) and must stay green — tracing adds zero device reads.
"""

import json
import socket
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.faults import (RouterFaultInjector,
                                               snapshot_split)
from deepspeed_tpu.inference.v2.kv_hierarchy import KVSwapTier
from deepspeed_tpu.inference.v2.router import EngineRouter, RouterConfig
from deepspeed_tpu.inference.v2.tracing import (FlightRecorder,
                                                TraceCollector,
                                                validate_trace)
from deepspeed_tpu.models import build_model

BS, CHUNK, MAX_NEW = 16, 8, 8
RNG = np.random.default_rng(15)
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


def _assert_connected(trace):
    problems = validate_trace(trace["spans"])
    assert not problems, f"trace {trace['id']}: {problems}"


def _names(trace):
    return [s["name"] for s in trace["spans"]]


def _replicas_of(trace):
    return {s["replica"] for s in trace["spans"]} - {"router", "edge"}


def _ttft_samples(col, *replicas):
    """First tokens folded into the TTFT stage counters, by replica."""
    return [col.ttft_totals(r)["ttft_requests"] for r in replicas]


# ---------------------------------------------------------------------------
# collector units (no engines)
# ---------------------------------------------------------------------------


def test_collector_bounds_sampling_and_validation():
    col = TraceCollector(sample_rate=0.0, max_traces=4,
                         max_spans_per_trace=3)
    # sample_rate=0: a plain completed trace is dropped...
    tid, root = col.mint("edge.recv", attrs={"uid": 1})
    col.note_first_token(tid, 0.5)
    col.note_done(tid, 1.0)
    col.finish(tid, status="ok")
    assert col.get(trace_id=tid) is None
    assert col.counters["traces_dropped"] == 1
    # ...but the fleet histograms recorded it anyway (attribution is
    # independent of span retention)
    assert col.fleet_ttft.total == 1
    assert col.fleet_e2e.total == 1
    # a MARKED trace survives sample_rate=0
    tid2, _ = col.mint("edge.recv", attrs={"uid": 2})
    col.mark(tid2, "fault")
    col.finish(tid2, status="poison_row")
    kept = col.get(trace_id=tid2)
    assert kept is not None and kept["status"] == "poison_row"
    # span budget: the 4th span of a 3-span-budget trace is refused
    tid3, r3 = col.mint("edge.recv")
    assert col.span(tid3, "a", 0.0, 1.0, parent=r3) is not None
    assert col.span(tid3, "b", 0.0, 1.0, parent=r3) is not None
    assert col.span(tid3, "c", 0.0, 1.0, parent=r3) is None
    assert col.counters["spans_truncated"] == 1
    # retention ring is bounded at max_traces
    for i in range(10):
        t, _ = col.mint("edge.recv")
        col.mark(t, "fault")
        col.finish(t, status="x")
    assert len(col.traces(include_open=False)) <= 4
    # validate_trace: orphan parents and double roots are named
    spans = [{"trace": "t", "sid": "s0", "parent": None, "name": "root"},
             {"trace": "t", "sid": "s1", "parent": "s9", "name": "leaf"}]
    assert any("orphan" in p for p in validate_trace(spans))
    spans[1]["parent"] = None
    assert any("root" in p for p in validate_trace(spans))
    assert validate_trace([]) == ["trace has no spans"]


def test_flight_recorder_ring_and_postmortem(tmp_path):
    col = TraceCollector()
    tid, _ = col.mint("edge.recv", attrs={"uid": 7})   # stays in flight
    fr = FlightRecorder(collector=col, max_events=4,
                        dump_dir=str(tmp_path))
    for i in range(8):
        fr.record("placement", replica="a", uid=i)
    assert len(fr.events) == 4                         # bounded ring
    assert fr.counters["events"] == 8
    assert not fr.dumps                                # nothing auto-dumped
    fr.record("replica_dead", replica="a", detail="strike budget")
    assert len(fr.dumps) == 1                          # auto-dump kind
    bundle = json.load(open(fr.dumps[0]))
    assert bundle["format"] == "dstpu-flight-bundle/1"
    assert bundle["reason"].startswith("replica_dead")
    assert any(e["kind"] == "replica_dead" for e in bundle["events"])
    # the in-flight request's trace rides the bundle
    assert [t["id"] for t in bundle["in_flight_traces"]] == [tid]
    assert "fleet_latency" in bundle


def test_chrome_export_shape():
    col = TraceCollector()
    tid, root = col.mint("edge.recv", replica="edge", t=1.0,
                         attrs={"uid": 3})
    col.span(tid, "engine.prefill", 1.1, 1.5, parent=root, replica="a")
    col.instant(tid, "emit", t=1.5, parent=root, replica="a")
    col.finish(tid, t=2.0, status="ok")
    doc = col.export_chrome()
    evs = doc["traceEvents"]
    procs = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert procs == {"edge", "a"}
    xs = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert {e["name"] for e in xs} == {"edge.recv", "engine.prefill"}
    assert [e["name"] for e in instants] == ["emit"]
    # µs relative to the earliest root
    pre = next(e for e in xs if e["name"] == "engine.prefill")
    assert pre["ts"] == pytest.approx(0.1e6)
    assert pre["dur"] == pytest.approx(0.4e6)
    # JSONL round-trips through validate_trace
    lines = [json.loads(ln) for ln in col.export_jsonl().splitlines()]
    assert not validate_trace(lines)


# ---------------------------------------------------------------------------
# single engine: tree shape, sampling of faulted/shed/cancelled requests
# ---------------------------------------------------------------------------


def test_single_engine_connected_trace(tiny_model_params):
    model, params = tiny_model_params
    eng = _engine(model, params)
    col = TraceCollector()
    eng.telemetry.set_tracer(col, replica="solo")
    out = dict(eng.serve(iter([[(u, PROMPTS[u]) for u in range(3)]]),
                         max_new_tokens=MAX_NEW))
    assert set(out) == {0, 1, 2}
    traces = col.traces()
    assert len(traces) == 3
    for t in traces:
        _assert_connected(t)
        assert not t["open"]
        assert t["status"] == "ok"
        names = _names(t)
        # tuple arrivals mint at the engine: root is engine.recv
        assert names[0] == "engine.recv"
        for want in ("engine.queue", "engine.prefill", "emit",
                     "engine.decode"):
            assert want in names, (want, names)
    snap = col.snapshot()
    assert snap["counters"]["ttft_samples"] == 3
    assert snap["counters"]["e2e_samples"] == 3
    assert snap["fleet_ttft_ms"]["count"] == 3
    # prometheus: the fleet-merged summaries + trace counters render
    text = col.render_prometheus()
    assert "ds_fleet_ttft_ms_count 3" in text
    assert "ds_fleet_e2e_ms_count 3" in text
    assert "ds_trace_traces_minted_total 3" in text


def test_cancel_and_shed_traces_always_sampled(tiny_model_params):
    """sample_rate=0 still keeps the traces worth debugging: a scheduler
    shed and a cancelled (deadline/disconnect path) request, each with a
    closed, connected trace carrying the terminal status."""
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    model, params = tiny_model_params
    eng = _engine(model, params)
    col = TraceCollector(sample_rate=0.0)
    eng.telemetry.set_tracer(col, replica="solo")
    sched = RequestScheduler(SchedulerConfig(tenant_max_queued=1))

    def arrivals():
        # same tenant, queue quota 1: the second submit sheds; the third
        # request expires by deadline before its first boundary admits it
        yield [{"uid": 0, "tokens": PROMPTS[0], "tenant": "t0"},
               {"uid": 1, "tokens": PROMPTS[1], "tenant": "t0"},
               {"uid": 2, "tokens": PROMPTS[2], "tenant": "t1",
                "deadline_ms": 1e-6}]

    out = dict(eng.serve(arrivals(), max_new_tokens=MAX_NEW,
                         scheduler=sched))
    assert set(out) == {0}
    traces = {t["uid"]: t for t in col.traces()}
    # uid 0 completed normally -> dropped at sample_rate=0
    assert 0 not in traces
    assert traces[1]["status"].startswith("shed:")
    assert "shed" in traces[1]["marks"]
    assert traces[2]["status"] in ("deadline_expired", "cancelled")
    for t in (traces[1], traces[2]):
        _assert_connected(t)
        assert not t["open"]
    # faulted/shed requests record no fleet E2E sample (mirrors the
    # per-replica histogram semantics)
    assert col.snapshot()["counters"]["e2e_samples"] == 1
    # and no stage sample: only uid 0 ever had a first token
    assert _ttft_samples(col, "solo") == [1]
    assert eng.telemetry.counters["ttft_requests"] == 1


# ---------------------------------------------------------------------------
# the tentpole: one connected trace across kill/failover and handoff
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_failover_one_connected_trace(tiny_model_params, tmp_path):
    """Scripted mid-stream kill (rejoin disabled => replica DEAD): the
    failed-over request's spans land on BOTH replicas under ONE trace id
    with an intact parent chain; fleet TTFT/E2E record exactly one
    sample per trace id; the postmortem bundle written on death holds
    the killed replica's events and the orphaned requests' traces."""
    model, params = tiny_model_params
    router = EngineRouter({"a": _engine(model, params),
                           "b": _engine(model, params)},
                          RouterConfig(rejoin=False))
    col, fr = router.attach_tracing(
        TraceCollector(), FlightRecorder(dump_dir=str(tmp_path)))
    faults = RouterFaultInjector(
        [{"kind": "engine_kill", "tick": 6, "engine": "a"}])
    out = dict(router.serve(iter([[(u, PROMPTS[u]) for u in range(6)]]),
                            max_new_tokens=48, faults=faults))
    assert faults.fired and len(out) == 6
    assert router.replica_status()["a"] == "dead"

    traces = col.traces()
    assert len(traces) == 6                 # ONE trace per request
    for t in traces:
        _assert_connected(t)
        assert not t["open"], f"trace {t['id']} never finished"
    crossed = [t for t in traces if len(_replicas_of(t)) > 1]
    assert crossed, "no trace spans both replicas after the failover"
    for t in crossed:
        assert "failover" in t["marks"]
        names = _names(t)
        assert "router.failover" in names
        # the continuation is a restore span on the peer, and the peer's
        # spans parent into the SAME tree (validated above)
        assert "engine.restore" in names
    # fleet-merged attribution: exactly one TTFT and one E2E per trace id
    snap = col.snapshot()
    assert snap["counters"]["ttft_samples"] == 6
    assert snap["counters"]["e2e_samples"] == 6
    # and exactly one stage sample, on the replica that emitted the first
    # token: a failed-over request is not counted again by its second
    by_replica = _ttft_samples(col, "a", "b")
    assert sum(by_replica) == 6 and all(by_replica), by_replica
    for name in "ab":
        tot = col.ttft_totals(name)
        assert sum(tot[f"ttft_{s}_ns"] for s in (
            "ingress", "feed", "queue", "prefill", "egress")) \
            == tot["ttft_total_ns"]
    # the survivor's serve loop mirrored every one of its own
    assert router._replicas["b"].engine.telemetry.counters[
        "ttft_requests"] == by_replica[1]
    # per-replica TTFT stays resumed-blind: total per-replica samples
    # equal fresh enqueues only (the failed-over request sampled once,
    # on its FIRST replica)
    per_replica = sum(
        r.engine.telemetry.hists["ttft"].total
        for r in router._replicas.values())
    assert per_replica == 6
    # postmortem bundle: written at death, carries the killed replica's
    # ring events and the then-in-flight requests' traces
    assert fr.dumps, "replica death wrote no bundle"
    bundle = json.load(open(fr.dumps[-1]))
    kinds = {e["kind"] for e in bundle["events"]}
    assert "engine_kill" in kinds and "replica_dead" in kinds
    assert any(e.get("replica") == "a" for e in bundle["events"])
    assert bundle["in_flight_traces"], "bundle lost the orphans' traces"
    for t in bundle["in_flight_traces"]:
        assert t["spans"], t


@pytest.mark.chaos
def test_handoff_one_connected_trace(tiny_model_params, tmp_path):
    """Disaggregated prefill→decode handoff: one connected trace across
    both roles, with the tier publish (prefill side) and the page
    restore (decode side) visible as spans, handoff always-sampled, and
    exactly one fleet TTFT sample (the prefill replica's first token)."""
    model, params = tiny_model_params
    tier = KVSwapTier(str(tmp_path / "tier"), shared=True)
    pe = _engine(model, params, role="prefill", max_tokens_per_step=256)
    pe.attach_kv_tier(tier, tag="p")
    de = _engine(model, params, role="decode", max_tokens_per_step=256)
    de.attach_kv_tier(tier, tag="d")
    router = EngineRouter({"prefill0": pe, "decode0": de})
    col, fr = router.attach_tracing()
    long_p = RNG.integers(0, 200, (48,)).astype(np.int32)

    def arrivals():
        yield [{"uid": 0, "tokens": long_p, "max_new_tokens": 4},
               {"uid": 2, "tokens": PROMPTS[2], "max_new_tokens": MAX_NEW}]

    out = dict(router.serve(arrivals(), max_new_tokens=MAX_NEW))
    assert set(out) == {0, 2}
    assert router.counters["handoffs"] == 1
    traces = {t["uid"]: t for t in col.traces()}
    assert len(traces) == 2
    for t in traces.values():
        _assert_connected(t)
        assert not t["open"]
        assert t["status"] == "ok"
    ho = traces[0]
    assert "handoff" in ho["marks"]
    assert _replicas_of(ho) == {"prefill0", "decode0"}
    names = _names(ho)
    for want in ("router.ingest", "router.place", "engine.prefill",
                 "engine.handoff", "tier.publish", "kv.restore",
                 "engine.restore", "engine.decode"):
        assert want in names, (want, names)
    # one TTFT per TRACE: the prefill replica recorded it; the decode
    # replica's resumed first emission did not double-count
    snap = col.snapshot()
    assert snap["counters"]["ttft_samples"] == 2
    assert snap["counters"]["e2e_samples"] == 2
    # one stage sample a trace too, the handed-off one on the PREFILL
    # replica; its trace shows the wait in each replica's feed
    assert sum(_ttft_samples(col, "prefill0", "decode0")) == 2
    assert _ttft_samples(col, "prefill0")[0] >= 1
    assert names.count("engine.feed") == 2
    # tier commits reached the flight ring
    assert any(e["kind"] == "tier_commit" for e in fr.events)
    assert any(e["kind"] == "handoff" for e in fr.events)


@pytest.mark.chaos
def test_disagg_handoff_plus_kill_chrome_export(tiny_model_params,
                                                tmp_path):
    """The acceptance scenario end to end: a disaggregated handoff AND a
    mid-stream kill/failover in ONE run — the handed-off request hops
    prefill0 → decode0 (handoff) → decode1 (failover), and the exported
    Chrome-trace JSON round-trips through the ``dstpu_trace`` loader
    with every request's spans sharing one trace id across ≥2 replicas
    and an intact parent chain."""
    model, params = tiny_model_params
    tier = KVSwapTier(str(tmp_path / "tier"), shared=True)
    engines = {}
    for name, role in (("prefill0", "prefill"), ("decode0", "decode"),
                       ("decode1", "decode")):
        e = _engine(model, params, role=role, max_tokens_per_step=256)
        e.attach_kv_tier(tier, tag=name)
        engines[name] = e
    router = EngineRouter(engines, RouterConfig(rejoin=False))
    col, fr = router.attach_tracing(
        TraceCollector(), FlightRecorder(dump_dir=str(tmp_path)))
    long_p = RNG.integers(0, 200, (48,)).astype(np.int32)

    def arrivals():
        # 48-token prompt, 12-token budget: prefill-heavy at the default
        # route ratio (48 >= 4 * 12), so the request handoffs first
        yield [{"uid": 0, "tokens": long_p, "max_new_tokens": 12,
                "session": "s0"}]

    # kill WHICHEVER decode replica the handoff lands on, a few ticks
    # into its decode: wrap the serial driver's _step so the kill keys
    # off the router's own assignment table (deterministic — the serial
    # tick clock and placement are), then let failover re-route
    killed = []
    state = {"owner": None, "owner_tick": None}
    orig_step = router._step

    def step_spy(r, tk, *a, **kw):
        owner = router._assignment.get(0)
        if state["owner"] is None and owner is not None \
                and router._roles[owner] != "prefill":
            state["owner"], state["owner_tick"] = owner, tk
        if state["owner"] is not None and not killed \
                and tk >= state["owner_tick"] + 3:
            if router._kill(state["owner"], tk, "scripted decode kill"):
                killed.append(state["owner"])
        return orig_step(r, tk, *a, **kw)

    router._step = step_spy
    out = dict(router.serve(arrivals(), max_new_tokens=12))
    assert set(out) == {0}
    assert killed, "the decode-side kill never fired"
    assert router.counters["handoffs"] >= 1
    assert router.counters["engine_kills"] == 1

    traces = col.traces()
    assert len(traces) == 1
    t = traces[0]
    _assert_connected(t)
    assert not t["open"] and t["status"] == "ok"
    assert {"handoff", "failover"} <= set(t["marks"])
    reps = _replicas_of(t)
    assert len(reps) >= 2 and "prefill0" in reps, reps
    # the acceptance artifact: Chrome JSON on disk, loaded back by the
    # CLI's parser, connected, spans on >= 2 replicas under ONE trace id
    export = tmp_path / "export.json"
    export.write_text(json.dumps(col.export_chrome()))
    cli = _load_cli()
    loaded = cli.load_spans(str(export))
    assert len(loaded) == 1
    (tid, spans), = loaded.items()
    assert not validate_trace(spans)
    span_reps = {s["replica"] for s in spans} - {"router", "edge"}
    assert len(span_reps) >= 2
    # the kill dumped a postmortem with the orphaned request's trace
    assert fr.dumps
    bundle = json.load(open(fr.dumps[-1]))
    assert any(tr["id"] == tid for tr in bundle["in_flight_traces"])
    # exactly one fleet TTFT/E2E sample across all three hops
    snap = col.snapshot()
    assert snap["counters"]["ttft_samples"] == 1
    assert snap["counters"]["e2e_samples"] == 1


# ---------------------------------------------------------------------------
# service edge: root at the edge, /debug/trace, disconnect trace
# ---------------------------------------------------------------------------


@pytest.mark.service
def test_edge_trace_debug_endpoint_and_disconnect(tiny_model_params):
    import http.client
    from deepspeed_tpu.inference.v2.service import (EdgeConfig, FleetDriver,
                                                    ServiceEdge)
    model, params = tiny_model_params
    router = EngineRouter({"a": _engine(model, params),
                           "b": _engine(model, params)})
    driver = FleetDriver(router)
    driver.start(max_new_tokens=MAX_NEW)
    edge = ServiceEdge(driver, EdgeConfig(keepalive_s=0.5)).start()
    try:
        body = {"prompt": [int(t) for t in PROMPTS[0]], "stream": False}
        conn = http.client.HTTPConnection("127.0.0.1", edge.edge_port,
                                          timeout=120)
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        uid = json.loads(resp.read())["uid"]
        # per-request lookup by uid, JSONL form -> connected, rooted at
        # the EDGE, spans from edge + router + one replica
        conn.request("GET", f"/debug/trace?uid={uid}&format=jsonl")
        spans = [json.loads(ln) for ln in
                 conn.getresponse().read().decode().splitlines()]
        assert not validate_trace(spans)
        root = next(s for s in spans if s["parent"] is None)
        assert root["name"] == "edge.recv" and root["replica"] == "edge"
        names = [s["name"] for s in spans]
        assert "edge.admit" in names and "router.place" in names
        # chrome form parses and carries the same trace
        conn.request("GET", f"/debug/trace?uid={uid}")
        chrome = json.loads(conn.getresponse().read())
        assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
        # flight bundle over HTTP
        conn.request("GET", "/debug/flight")
        bundle = json.loads(conn.getresponse().read())
        assert bundle["format"] == "dstpu-flight-bundle/1"
        # /metrics carries the fleet-merged attribution series
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "ds_fleet_ttft_ms_count 1" in text
        assert "ds_trace_traces_minted_total" in text
        assert "ds_flight_events_total" in text
        conn.close()

        # client disconnect mid-stream: the trace closes as a cancelled/
        # disconnect trace and stays sampled
        long_body = json.dumps({"prompt": [int(t) for t in PROMPTS[1]],
                                "max_new_tokens": 120}).encode()
        s = socket.create_connection(("127.0.0.1", edge.edge_port))
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  b"Content-Type: application/json\r\n"
                  + f"Content-Length: {len(long_body)}\r\n\r\n".encode()
                  + long_body)
        buf = b""
        while b"event: token" not in buf:
            chunk = s.recv(4096)
            assert chunk, f"stream ended early: {buf!r}"
            buf += chunk
        s.close()
        deadline = time.monotonic() + 60
        tr = None
        while time.monotonic() < deadline:
            tr = edge.tracer.get(uid=2)
            if tr is not None and not tr["open"]:
                break
            time.sleep(0.05)
        assert tr is not None and not tr["open"], tr
        assert not validate_trace(tr["spans"])
        assert ("disconnect" in tr["marks"]) or ("cancelled" in tr["marks"])
    finally:
        edge.shutdown()
        driver.stop()


# ---------------------------------------------------------------------------
# the CLI gate
# ---------------------------------------------------------------------------


def _load_cli():
    import importlib.machinery
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bin", "dstpu_trace")
    loader = importlib.machinery.SourceFileLoader("dstpu_trace_cli", path)
    spec = importlib.util.spec_from_loader("dstpu_trace_cli", loader)
    mod = importlib.util.module_from_spec(spec)
    loader.exec_module(mod)
    return mod


def test_dstpu_trace_cli_gate(tmp_path, monkeypatch, capsys):
    """The ASCII-timeline CLI is a parity-style gate: exit 0 + lanes on a
    connected export, exit 1 naming the orphan on a broken one. Exercised
    in-process (the script is import-safe) on both chrome and JSONL
    inputs."""
    cli = _load_cli()
    col = TraceCollector()
    tid, root = col.mint("edge.recv", replica="edge", t=0.0,
                         attrs={"uid": 5})
    col.span(tid, "engine.prefill", 0.1, 0.5, parent=root, replica="a")
    col.span(tid, "engine.decode", 0.5, 0.9, parent=root, replica="b")
    col.finish(tid, t=1.0, status="ok")
    good_chrome = tmp_path / "good.json"
    good_chrome.write_text(json.dumps(col.export_chrome()))
    good_jsonl = tmp_path / "good.jsonl"
    good_jsonl.write_text(col.export_jsonl())

    monkeypatch.setattr("sys.argv", ["dstpu_trace", str(good_chrome)])
    assert cli.main() == 0
    out = capsys.readouterr().out
    assert "all connected" in out
    assert "edge" in out and "engine.prefill" in out     # lanes rendered
    monkeypatch.setattr("sys.argv",
                        ["dstpu_trace", str(good_jsonl), "--uid", "5"])
    assert cli.main() == 0
    capsys.readouterr()

    # break the parent chain -> nonzero exit naming the orphan
    broken = [dict(s) for s in col.get(trace_id=tid)["spans"]]
    broken[1]["parent"] = "s777"
    bad = tmp_path / "bad.jsonl"
    bad.write_text("\n".join(json.dumps(s) for s in broken) + "\n")
    monkeypatch.setattr("sys.argv", ["dstpu_trace", str(bad), "--check"])
    assert cli.main() == 1
    err = capsys.readouterr().err
    assert "DISCONNECTED" in err and "s777" in err


# ---------------------------------------------------------------------------
# snapshot round trip: the trace context survives serialization
# ---------------------------------------------------------------------------


def test_trace_context_survives_snapshot_split(tiny_model_params):
    model, params = tiny_model_params
    eng = _engine(model, params)
    col = TraceCollector()
    eng.telemetry.set_tracer(col, replica="solo")
    gen = eng.serve(iter([[(0, PROMPTS[0], 64)]]), max_new_tokens=64,
                    yield_boundaries=True)
    for ev in gen:
        if not isinstance(ev, tuple) and ev.dispatched:
            break                      # a live frame ran; ledger populated
    snap = eng.snapshot_serving_state()
    gen.close()
    assert json.loads(json.dumps(snap)) == snap   # JSON-serializable
    items = snapshot_split(snap)
    assert len(items) == 1
    tr = items[0]["trace"]
    assert tr is not None and tr["id"] in {t["id"] for t in col.traces()}
    assert tr["parent"] == "s0"


# ---------------------------------------------------------------------------
# TTFT stage samples: none without a first token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fate", ["shed", "deadline_expired", "poison_row",
                                  "cancelled"])
def test_no_ttft_stage_sample_without_a_first_token(tiny_model_params, fate):
    """A request shed, expired, quarantined or cancelled BEFORE its first
    token adds nothing to the stage counters; its neighbour adds one."""
    from deepspeed_tpu.inference.v2.faults import FaultInjector
    from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                      SchedulerConfig)
    model, params = tiny_model_params
    eng = _engine(model, params)
    col = TraceCollector(sample_rate=0.0)
    eng.telemetry.set_tracer(col, replica="solo")
    # 40 tokens at 8 a step, 2 steps a frame: no first token before frame 3
    victim = {"uid": 1, "tokens": RNG.integers(0, 200, (40,)).astype(np.int32),
              "tenant": "t0"}
    kw = {}
    if fate == "shed":
        kw["scheduler"] = RequestScheduler(SchedulerConfig(tenant_max_queued=1))
    elif fate == "deadline_expired":
        victim["deadline_ms"] = 1e-6
    elif fate == "poison_row":
        kw["faults"] = FaultInjector(
            [{"kind": "poison_row", "frame": 0, "uid": 1}])
    arrivals = iter([[{"uid": 0, "tokens": PROMPTS[0], "tenant": "t0"},
                      victim]])
    out = {}
    for ev in eng.serve(arrivals, max_new_tokens=MAX_NEW,
                        yield_boundaries=True, **kw):
        if isinstance(ev, tuple):
            out[ev[0]] = ev[1]
        elif fate == "cancelled" and ev.dispatched and ev.index == 0:
            assert eng.cancel_request(1)
    assert set(out) == {0}
    assert _ttft_samples(col, "solo") == [1]
    assert eng.telemetry.counters["ttft_requests"] == 1
    assert not col._ttft_pending
    status = {t["uid"]: t["status"] for t in col.traces()}[1]
    assert status.startswith("shed:") if fate == "shed" else \
        status in (fate, "cancelled"), status
