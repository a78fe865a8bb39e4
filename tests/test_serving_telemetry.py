"""Serving telemetry tests.

The telemetry subsystem (``inference/v2/telemetry.py``) has one hard
contract: every number it reports must match a host-side replay of the same
arithmetic EXACTLY (the in-graph counters are not estimates), and measuring
must add zero device→host transfers inside a frame. The scripted-schedule
tests below derive ground truth from the SplitFuse scheduling arithmetic
(prefill steps = ceil(P/chunk), decode steps = N-1 after the
prefill-completing emission) and assert counter equality; the transfer-guard
test pins the no-in-frame-transfer invariant; the histogram/Prometheus tests
pin the fixed-memory bucket math and the exposition format.
"""

import json
import logging
import os

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
from deepspeed_tpu.inference.v2.telemetry import (LogBucketHistogram,
                                                  ServingTelemetry)
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils.logging import logger as ds_logger


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


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


PROMPT_LENS = {0: 7, 1: 24, 2: 33}
MAX_NEW = 8
CHUNK = 16


def _prompts():
    rng = np.random.default_rng(5)
    return {u: rng.integers(0, 200, (n,)).astype(np.int32)
            for u, n in PROMPT_LENS.items()}


def _arrivals(prompts, schedule={0: [0, 1], 2: [2]}):
    for k in range(max(schedule) + 2):
        yield [(u, prompts[u]) for u in schedule.get(k, [])]


class StubMonitor:
    """Minimal Monitor-protocol sink: records every event batch."""

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


@pytest.fixture(scope="module")
def served(tiny_model_params, tmp_path_factory):
    """ONE scripted serve() run, with a stub monitor AND a real
    CSV-MonitorMaster attached; telemetry state is snapshotted immediately
    (later tests reuse the engine, which resets the per-serve view)."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import DeepSpeedMonitorConfig

    model, params = tiny_model_params
    e = _engine(model, params)
    stub = StubMonitor()
    csv_dir = tmp_path_factory.mktemp("csv_monitor")
    master = MonitorMaster(DeepSpeedMonitorConfig(
        csv_monitor={"enabled": True, "output_path": str(csv_dir),
                     "job_name": "serve"}))

    class Tee:
        def write_events(self, events):
            stub.write_events(events)
            master.write_events(events)

    e.attach_monitor(Tee())
    e.telemetry.record_spans = True
    prompts = _prompts()
    outs = dict(e.serve(_arrivals(prompts), max_new_tokens=MAX_NEW))
    snap = {
        "snapshot": e.telemetry.snapshot(),
        "prom": e.telemetry.render_prometheus(),
        "latency_ms": e.telemetry.latency_ms(),
        "spans": list(e.telemetry.spans),
        "events": list(stub.events),
        "csv_dir": csv_dir,
        "serve_view": {k: (dict(v) if isinstance(v, dict) else v)
                       for k, v in e.serve_stats.items()},
    }
    return e, prompts, outs, snap


# ---------------------------------------------------------------------------
# in-graph counters vs host-replay ground truth
# ---------------------------------------------------------------------------


def test_counters_match_host_replay(served):
    """The device counters must equal the SplitFuse arithmetic replayed on
    the host: per row, ceil(P/chunk) prefill steps (the last one emits the
    first token) then N-1 decode steps; no EOS in this schedule."""
    _e, prompts, outs, snap = served
    c = snap["snapshot"]["counters"]
    n_tokens = sum(len(v) for v in outs.values())
    assert n_tokens == len(PROMPT_LENS) * MAX_NEW
    assert c["tokens_emitted"] == n_tokens
    assert c["prefill_tokens"] == sum(PROMPT_LENS.values())
    assert c["eos_events"] == 0
    expect_decode_fwd = sum(MAX_NEW - 1 for _ in PROMPT_LENS)
    assert c["target_forwards"] == expect_decode_fwd
    expect_active = sum(-(-p // CHUNK) + MAX_NEW - 1
                        for p in PROMPT_LENS.values())
    assert c["active_row_steps"] == expect_active
    assert c["drafted_tokens"] == 0 and c["accepted_draft_tokens"] == 0
    assert c["requests_enqueued"] == c["requests_admitted"] \
        == c["requests_retired"] == len(PROMPT_LENS)
    assert c["admission_deferrals"] == 0
    assert c["frames"] == snap["serve_view"]["frames"]
    # positions_computed is a device lane: what each step's per-token
    # layers ran. 8 slots x 16 positions has one rung (the chunk whole), so
    # frame by frame it is slots x steps x the frame's width, and a frame
    # is one chunk wide exactly when it consumed prompt tokens
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    assert pack_ladder(8, CHUNK) == (8 * CHUNK,) and c["rung_steps"] == 0
    per_frame = {}
    for tag, value, step in snap["events"]:
        per_frame.setdefault(step, {})[tag] = value
    last = {"serving/positions_computed": 0, "serving/prefill_tokens": 0,
            "serving/slot_steps_capacity": 0}
    for step in sorted(per_frame):
        d = {t: per_frame[step][t] - last[t] for t in last}
        last = {t: per_frame[step][t] for t in last}
        width = CHUNK if d["serving/prefill_tokens"] else 1
        assert d["serving/positions_computed"] \
            == d["serving/slot_steps_capacity"] * width, (step, d)
    assert last["serving/positions_computed"] == c["positions_computed"] > 0


def _layer_reader(name):
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "layer_metrics",
        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_counters_count_the_steps_that_ran(tiny_model_params, monkeypatch):
    """A fixed arrival list served as the loop plans its frames and with
    every frame forced whole: ``frame_steps_hist``, ``frame_steps`` /
    ``wide_steps``, ``slot_steps_capacity`` and ``positions_computed``
    count the steps each run RAN; the work is the same, so the benchmark's
    ``useful_position_share`` and ``slot_occupancy`` read the same up to
    the steps saved, and ``wide_step_share`` reads the saving."""
    model, params = tiny_model_params
    e = _engine(model, params, frame_steps=8)
    rng = np.random.default_rng(46)
    reqs = [(u, rng.integers(0, 200, (n,)).astype(np.int32))
            for u, n in enumerate((60, 7, 24, 33, 5, 90))]
    slots, runs = 8, {}
    for how in ("planned", "whole"):
        if how == "whole":
            monkeypatch.setattr(InferenceEngineV2, "_plan_frame_steps",
                                staticmethod(lambda cur, *rest: cur))
        frames = []
        e.telemetry.attach_monitor(type("Log", (), {"write_events": (
            lambda self, ev: frames.append((
                e.telemetry.counters["prefill_tokens"],
                e.serve_stats["frame_steps_last"])))})())
        outs = dict(e.serve(iter([[r] for r in reqs]), max_new_tokens=12))
        c, hist = dict(e.telemetry.counters), e.serve_stats["frame_steps_hist"]
        steps = sum(k * n for k, n in hist.items())
        assert c["frame_steps"] == steps and c["frames"] == sum(hist.values())
        assert c["slot_steps_capacity"] == slots * steps
        narrow = c["frame_steps"] - c["wide_steps"]
        assert c["positions_computed"] == slots * (
            CHUNK * c["wide_steps"] + narrow)
        text = e.telemetry.render_prometheus()
        assert f"ds_serving_wide_steps_total {c['wide_steps']}" in text
        assert f"ds_serving_frame_steps_total {steps}" in text
        # the monitor's rows, as perfbench's FrameLog keeps them
        rows = [(0.0, 0, p - q, n) for (q, _), (p, n)
                in zip([(0, 0)] + frames, frames)]
        assert sum(r[3] for r in rows) == steps
        runs[how] = (outs, c, _layer_reader("wide_step_share")(
            {"frames": rows}))
    (outs, c, share), (outs_w, w, share_w) = runs["planned"], runs["whole"]
    for u in outs_w:
        np.testing.assert_array_equal(outs[u], outs_w[u])
    for name in ("prefill_tokens", "target_forwards", "active_row_steps",
                 "tokens_emitted"):
        assert c[name] == w[name], name
    assert c["wide_steps"] < w["wide_steps"]
    assert c["frame_steps"] < w["frame_steps"]
    assert share < share_w == pytest.approx(
        100.0 * w["wide_steps"] / w["frame_steps"])
    for name, total in (("useful_position_share", "positions_computed"),
                        ("slot_occupancy", "slot_steps_capacity")):
        read = _layer_reader(name)
        assert read({"counters": c}) * c[total] == pytest.approx(
            read({"counters": w}) * w[total])
        assert read({"counters": c}) > read({"counters": w})


def test_row_tile_counters_match_host_replay():
    """``attn_row_tiles`` / ``attn_row_tiles_live``: what the wide paged
    kernel had to cut and what it computed, one layer's, replayed on the
    host over a scripted serve whose wide frames hold a decoding row riding
    the chunk, a full chunk, a prompt's last partial chunk and frozen
    slots. 8 query heads on 2 kv heads and chunks of 64: 256 query rows a
    kv head, two tiles of 128 rows = 32 positions."""
    model = build_model("tiny", num_heads=8, num_kv_heads=2)
    chunk, slots, steps, new = 64, 4, 4, 8
    e = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        kv_block_size=16, prefill_chunk_size=chunk, max_tokens_per_step=256,
        dtype="float32", max_ragged_batch_size=slots, frame_steps=steps),
        params=model.init(jax.random.PRNGKey(0)), max_seq_len=256)
    assert e.runner.row_heads == (2, 4)
    rng = np.random.default_rng(9)
    plens = {0: 10, 1: chunk + 37}
    arrivals = [[(u, rng.integers(0, 200, (n,)).astype(np.int32))]
                for u, n in plens.items()]
    outs = dict(e.serve(iter(arrivals), max_new_tokens=new))
    assert {len(v) for v in outs.values()} == {new}
    # frame 1 (uid 0 alone): its 10 prompt tokens and one decode step: half
    # of the four. Frame 2: uid 0 rides two steps while uid 1 consumes a
    # full chunk and its last 37 tokens, and the frame ends. Every later
    # frame is narrow and cuts nothing: four steps, then three to uid 1's
    # last token (uid 0's is the fourth of the four: nothing to wait for).
    wide = [[10, 1], [1, 1], [chunk, 37]]
    kvh, group, tile = 2, 4, 128
    live = sum(kvh * -(-w * group // tile) for row in wide for w in row)
    c = e.telemetry.counters
    assert e.serve_stats["frame_steps_hist"] == {2: 2, 3: 1, steps: 1}
    assert (c["wide_steps"], c["frame_steps"]) == (2 + 2, 2 + 2 + 4 + 3)
    assert c["attn_row_tiles"] == (2 + 2) * slots * kvh * (chunk * group
                                                           // tile)
    assert c["attn_row_tiles_live"] == live == 2 * (2 + 2 + 4)
    text = e.telemetry.render_prometheus()
    assert f"ds_serving_attn_row_tiles_live_total {live}" in text
    assert f"ds_serving_attn_row_tiles_total {c['attn_row_tiles']}" in text


def test_eos_counted_in_graph(tiny_model_params, served):
    """A scripted per-row EOS registers exactly one in-graph EOS event and
    one fewer emitted token than the budget."""
    e, prompts, outs, _snap = served
    eos = int(outs[0][2])
    stop = outs[0].tolist().index(eos)
    got = dict(e.serve(iter([[(0, prompts[0], None, None, eos)]]),
                       max_new_tokens=MAX_NEW))
    c = e.telemetry.counters
    assert len(got[0]) == stop + 1
    assert c["eos_events"] == 1
    assert c["tokens_emitted"] == stop + 1


def test_lifecycle_latency_histograms(served):
    """TTFT/queue-wait/E2E get one sample per request; ITL gets one sample
    per token after each row's first emission (frame-granularity measure)."""
    _e, _prompts, outs, snap = served
    lat = snap["latency_ms"]
    n_req = len(PROMPT_LENS)
    for name in ("ttft", "queue_wait", "e2e"):
        assert lat[name]["count"] == n_req, (name, lat)
        assert lat[name]["p50"] is not None and lat[name]["p50"] >= 0
        assert lat[name]["p99"] is not None
    assert 0 < lat["itl"]["count"] < n_req * MAX_NEW
    spans = snap["spans"]
    assert len(spans) == n_req
    for s in spans:
        assert s["enqueue_t"] <= s["admit_t"] <= s["first_token_t"] \
            <= s["retire_t"]
        assert s["tokens"] == MAX_NEW


def test_occupancy_and_kv_gauges(served):
    e, _prompts, _outs, snap = served
    g = snap["snapshot"]["gauges"]
    assert g["kv_blocks_total"] == e.kv.num_blocks
    assert 1 <= g["kv_blocks_in_use"] <= e.kv.num_blocks
    assert 0.0 < g["occupancy"] <= 1.0
    assert g["slot_count"] == 8
    assert g["recompiled_programs"] >= 1   # the frame programs themselves


# ---------------------------------------------------------------------------
# speculative counter parity (device counters vs host emit-mask replay)
# ---------------------------------------------------------------------------


def test_spec_counter_parity_with_host_replay(tiny_model_params, monkeypatch):
    """serve_stats' speculative counters now come from the device; they must
    equal the old host arithmetic (verify forwards = emit column 0 of
    width-1 frames, accepted = the other columns) replayed on the frames'
    emit masks — and the emitted totals must match the actual outputs."""
    model, params = tiny_model_params
    e = _engine(model, params)
    e.attach_draft(model, params)           # self-draft: high acceptance

    host = {"fwds": 0, "emitted": 0}
    orig = DeviceSlotTable.dispatch_frame

    def spy(self, runner, eng_params, kv, width, steps, greedy, draft=None,
            **kw):
        toks, emit = orig(self, runner, eng_params, kv, width, steps, greedy,
                          draft=draft, **kw)
        if emit.ndim == 3 and width == 1:
            seen = np.asarray(emit)
            host["fwds"] += int(seen[:, :, 0].sum())
            host["emitted"] += int(seen.sum())
        return toks, emit

    monkeypatch.setattr(DeviceSlotTable, "dispatch_frame", spy)
    prompts = _prompts()
    outs = dict(e.serve(_arrivals(prompts), max_new_tokens=MAX_NEW, gamma=2))
    sp = e.serve_stats["spec"]
    assert sp["target_forwards"] == host["fwds"]
    assert sp["emitted_tokens"] == host["emitted"]
    assert sp["accepted_drafts"] == host["emitted"] - host["fwds"]
    assert sp["acceptance_rate"] == round(
        sp["accepted_drafts"] / (2 * sp["target_forwards"]), 4)
    c = e.telemetry.counters
    assert c["tokens_emitted"] == sum(len(v) for v in outs.values())
    assert c["drafted_tokens"] == 2 * sp["target_forwards"]
    # self-draft under greedy: near-full acceptance => >2 tokens per verify
    assert sp["tokens_per_target_forward"] > 2.0, sp


# ---------------------------------------------------------------------------
# no in-frame host transfers
# ---------------------------------------------------------------------------


def test_telemetry_adds_no_in_frame_transfers(served, frame_transfer_guard):
    """Frame dispatch performs ZERO device→host transfers with telemetry on:
    the counters ride the donated carry and are read only at the frame
    boundary (outside the guarded region, with the token/emit fetch).
    Uses conftest's shared guard — the single definition of "in-frame"
    that graft-lint GL001 checks statically."""
    e, prompts, _outs, _snap = served
    got = dict(e.serve(iter([[(0, prompts[0]), (1, prompts[1])]]),
                       max_new_tokens=MAX_NEW))
    assert len(got) == 2 and all(len(v) == MAX_NEW for v in got.values())
    assert e.telemetry.counters["tokens_emitted"] == 2 * MAX_NEW


# ---------------------------------------------------------------------------
# overload deferral visibility
# ---------------------------------------------------------------------------


def test_admission_deferral_warns_once_and_counts(served):
    """Overloading every slot logs ONE rate-limited structured warning
    (queue depth + frame bucket included) while the deferral counter keeps
    counting every deferred frame boundary."""
    e, _prompts, _outs, _snap = served
    rng = np.random.default_rng(21)
    # 10 arrivals into 8 slots; 24-token prompts reuse the served fixture's
    # compiled shape buckets (prompt width 32, table width 4)
    arr = [(u, rng.integers(0, 200, (24,)).astype(np.int32))
           for u in range(10)]
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = Capture()
    ds_logger.addHandler(h)
    try:
        got = dict(e.serve(iter([arr]), max_new_tokens=MAX_NEW))
    finally:
        ds_logger.removeHandler(h)
    assert len(got) == 10
    warns = [m for m in records if "admission deferred" in m]
    assert len(warns) == 1, warns          # rate-limited to one
    assert "queue_depth=2" in warns[0]
    assert "frame_steps_bucket=" in warns[0]
    assert e.telemetry.counters["admission_deferrals"] >= 2


def test_defer_warning_rate_limit_scripted_clock():
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    tel = ServingTelemetry(clock=clk, defer_warn_interval_s=5.0)
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = Capture()
    ds_logger.addHandler(h)
    try:
        tel.on_defer(queue_depth=3, frame_steps=8, free_slots=0,
                     free_blocks=11)
        clk.t = 1.0
        tel.on_defer(queue_depth=4, frame_steps=8, free_slots=0,
                     free_blocks=11)
        clk.t = 6.1                        # past the interval: warns again
        tel.on_defer(queue_depth=5, frame_steps=4, free_slots=0,
                     free_blocks=11)
    finally:
        ds_logger.removeHandler(h)
    warns = [m for m in records if "admission deferred" in m]
    assert len(warns) == 2
    assert "queue_depth=3" in warns[0] and "no free slots" in warns[0]
    assert "deferral_events_since_last_warning=2" in warns[1]
    assert tel.counters["admission_deferrals"] == 3


# ---------------------------------------------------------------------------
# histogram bucket math (fixed memory, exact placement)
# ---------------------------------------------------------------------------


def test_log_bucket_histogram_math():
    h = LogBucketHistogram(lo=1e-3, growth=10.0, n_buckets=3)
    assert h.bounds == [1e-3, 1e-2, 1e-1]
    for v in (0.0005, 0.001, 0.005, 0.01, 0.05, 5.0):
        h.record(v)
    # placement: <= lo -> bucket 0; bound-exact values stay in their bucket;
    # past the top bound -> overflow
    np.testing.assert_array_equal(h.counts, [2, 2, 1, 1])
    assert h.total == 6
    assert abs(h.sum - 5.0665) < 1e-12
    # p50: rank 3 lands in bucket 1 -> geometric midpoint sqrt(1e-3 * 1e-2)
    assert abs(h.percentile(50) - 10 ** -2.5) < 1e-12
    # p10: rank 0.6 -> bucket 0 -> upper/2
    assert h.percentile(10) == 0.0005
    # p99: rank 5.94 -> overflow bucket -> top bound * growth
    assert h.percentile(99) == 1.0
    assert LogBucketHistogram().percentile(50) is None   # empty
    h.reset()
    assert h.total == 0 and h.sum == 0.0
    # weighted record: one call, n samples
    h.record(0.02, count=5)
    assert h.counts[2] == 5 and h.total == 5


def test_scripted_lifecycle_stamps():
    """Deterministic clock: every histogram sample lands where the
    enqueue→admit→first-token→retire arithmetic says it must."""
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    tel = ServingTelemetry(clock=clk, record_spans=True)
    tel.begin_serve(speculate=False, gamma=0, adaptive=False, n_slots=4,
                    kv_blocks_total=64)
    clk.t = 10.0
    tel.on_enqueue(7)
    clk.t = 10.5
    tel.on_admit(7)                         # queue_wait = 0.5
    clk.t = 11.0
    tel.on_emit(7, 3)                       # first emission: TTFT = 1.0
    clk.t = 12.0
    tel.on_emit(7, 2)                       # 2 ITL samples of 0.5
    clk.t = 13.0
    tel.on_retire(7)                        # e2e = 3.0
    assert tel.hists["queue_wait"].total == 1
    assert tel.hists["ttft"].total == 1
    assert abs(tel.hists["ttft"].sum - 1.0) < 1e-9
    assert tel.hists["itl"].total == 2
    assert abs(tel.hists["itl"].sum - 1.0) < 1e-9    # 2 x 0.5
    assert abs(tel.hists["e2e"].sum - 3.0) < 1e-9
    assert tel.counters["requests_retired"] == 1
    (span,) = tel.spans
    assert span == {"uid": 7, "enqueue_t": 10.0, "admit_t": 10.5,
                    "first_token_t": 11.0, "retire_t": 13.0, "tokens": 5}


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def test_prometheus_render_golden():
    """Exact text for one histogram section (cumulative le buckets, sum,
    count, quantiles) — the scrape format is a wire contract."""
    tel = ServingTelemetry(clock=lambda: 0.0)
    h = LogBucketHistogram(lo=1e-3, growth=10.0, n_buckets=3)
    for v in (0.0005, 0.005, 0.05, 5.0):
        h.record(v)
    tel.hists = {"ttft": h}
    text = tel.render_prometheus()
    golden = """# TYPE ds_serving_ttft_seconds histogram
ds_serving_ttft_seconds_bucket{le="0.001"} 1
ds_serving_ttft_seconds_bucket{le="0.01"} 2
ds_serving_ttft_seconds_bucket{le="0.1"} 3
ds_serving_ttft_seconds_bucket{le="+Inf"} 4
ds_serving_ttft_seconds_sum 5.0555
ds_serving_ttft_seconds_count 4
ds_serving_ttft_seconds_quantile{quantile="0.50"} 0.00316228
ds_serving_ttft_seconds_quantile{quantile="0.90"} 1
ds_serving_ttft_seconds_quantile{quantile="0.99"} 1"""
    assert golden in text
    # counters and gauges render with their types
    assert "# TYPE ds_serving_tokens_emitted_total counter" in text
    assert "ds_serving_tokens_emitted_total 0" in text
    assert "# TYPE ds_serving_kv_blocks_in_use gauge" in text
    assert "ds_serving_spec_acceptance_rate NaN" in text
    assert text.endswith("\n")


def test_prometheus_render_from_serve(served):
    """The acceptance-criteria surface: a scripted serve() run exposes
    token counts, occupancy, KV usage, and latency quantiles via
    render_prometheus()."""
    _e, _prompts, outs, snap = served
    text = snap["prom"]
    n_tokens = sum(len(v) for v in outs.values())
    assert f"ds_serving_tokens_emitted_total {n_tokens}" in text
    assert f"ds_serving_requests_retired_total {len(outs)}" in text
    assert 'ds_serving_ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "ds_serving_ttft_seconds_count 3" in text
    assert 'ds_serving_e2e_seconds_quantile{quantile="0.99"}' in text
    assert "ds_serving_occupancy" in text
    assert "ds_serving_kv_blocks_in_use" in text


# ---------------------------------------------------------------------------
# what an operator scrapes and snapshots under scheduler=None, pinned to the
# tree that still had a FIFO loop of its own
# ---------------------------------------------------------------------------

FIFO_GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures",
                           "fifo_tuple_serve_golden.json")
# counters whose value is a clock reading or depends on what this process
# compiled before the test ran: their series are pinned, not their values
_UNPINNED = ("_ns_total", "programs_requested_total")


def _fifo_tuple_serve_record(e):
    """Tuple arrivals under ``scheduler=None`` on a scripted schedule, two
    slots so that requests queue and admissions defer: the retirement
    order, ``snapshot_serving_state()`` taken mid-serve (every field but
    the clock's), and ``render_prometheus()`` as the set of series (name
    and labels) plus every counter's value. The golden file was this
    function's result at b6ef6c1, the last tree with ``_serve_loop`` for
    FIFO and ``_serve_loop_sched`` for a scheduler, and the one loop gave
    it to the digit until PR 41 planned how many steps a frame runs: that
    moved where frames end, so the boundary the snapshot is taken at,
    ``frames``, ``positions_computed`` and the work's split by width, and
    nothing a policy writes (the order, every request's tokens, no label).
    Recorded again there, with the series younger than b6ef6c1."""
    rng = np.random.default_rng(5)
    prompts = {u: rng.integers(0, 200, (n,)).astype(np.int32)
               for u, n in {0: 7, 1: 24, 2: 33, 3: 5}.items()}
    schedule = {0: [0, 1], 1: [2], 2: [3]}
    arrivals = ([(u, prompts[u]) for u in schedule.get(k, [])]
                for k in range(4))
    order, snap = [], None
    for ev in e.serve(arrivals, max_new_tokens=MAX_NEW, frame_slots=2,
                      yield_boundaries=True):
        if isinstance(ev, tuple):
            order.append(int(ev[0]))
        elif ev.index == 2:
            snap = e.snapshot_serving_state()
    for r in snap["requests"]:
        del r["deadline_remaining_ms"]
    series, counters = [], {}
    for line in e.telemetry.render_prometheus().splitlines():
        if line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        series.append(key)
        if key.split("{")[0].endswith("_total") \
                and not key.split("{")[0].endswith(_UNPINNED):
            counters[key] = val
    return {"order": order, "snapshot": snap, "series": series,
            "counters": counters}


def test_fifo_tuple_serve_matches_the_fifo_loop(tiny_model_params):
    """``serve(scheduler=None)`` resolves to a policy object in the one
    serve loop; for tuple arrivals nothing an operator reads may show it:
    no tenant / class in the ledger or a snapshot, no labeled series, the
    same counters."""
    model, params = tiny_model_params
    got = _fifo_tuple_serve_record(_engine(model, params))
    with open(FIFO_GOLDEN) as f:
        want = json.load(f)
    assert got["order"] == want["order"]
    assert got["snapshot"] == want["snapshot"]
    assert [r["uid"] for r in got["snapshot"]["requests"]] and all(
        r["tenant"] is None and r["priority"] is None and r["slo_ms"] is None
        for r in got["snapshot"]["requests"])
    assert got["series"] == want["series"]
    assert got["counters"] == want["counters"]
    assert got["counters"]["ds_serving_admission_deferrals_total"] != "0"


# ---------------------------------------------------------------------------
# MonitorMaster fan-out
# ---------------------------------------------------------------------------


def test_monitor_fanout(served):
    """Frame-boundary events reach both an arbitrary write_events sink and
    a real CSV MonitorMaster (one file per tag, step = frame index)."""
    _e, _prompts, outs, snap = served
    events = snap["events"]
    tags = {t for t, _v, _s in events}
    assert "serving/tokens_emitted" in tags
    assert "serving/kv_blocks_in_use" in tags
    assert "serving/ttft_p50_ms" in tags
    final = {t: v for t, v, _s in events}    # last write per tag
    assert final["serving/tokens_emitted"] == sum(
        len(v) for v in outs.values())
    csv_files = list((snap["csv_dir"] / "serve").glob("*.csv"))
    assert any(f.name == "serving_tokens_emitted.csv" for f in csv_files)


# ---------------------------------------------------------------------------
# compile-count satellites
# ---------------------------------------------------------------------------


def test_compile_count_total_monotonic_and_reset():
    class FakeJit:
        def __init__(self, n):
            self.n = n

        def _cache_size(self):
            return self.n

    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    r = PagedModelRunner.__new__(PagedModelRunner)   # no model needed
    r._fns = {"frame": FakeJit(3), "chunk16": FakeJit(2)}
    r._evicted_programs = 0
    r._compile_base = 0
    assert r.compile_count() == {"frame": 3, "chunk16": 2}
    assert r.compile_count_total() == 5
    # eviction (draft re-attach) must not lower the monotonic total
    r.evict("frame", "missing")
    assert "frame" not in r._fns
    assert r.compile_count_total() == 5
    r._fns["spec_frame"] = FakeJit(4)
    assert r.compile_count_total() == 9
    r.reset_compile_count()
    assert r.compile_count_total() == 0
    r._fns["spec_frame"].n = 6
    assert r.compile_count_total() == 2


def test_recompile_gauge_exported(served):
    _e, _prompts, _outs, snap = served
    assert "ds_serving_recompiled_programs" in snap["prom"]
    assert snap["snapshot"]["gauges"]["recompiled_programs"] >= 1


# ---------------------------------------------------------------------------
# telemetry-off mode
# ---------------------------------------------------------------------------


def test_telemetry_disabled_keeps_serve_stats_shape(served):
    """telemetry=False skips the host stats path but serve_stats keeps the
    frame bookkeeping shape (and serving output is unchanged)."""
    e, prompts, outs, _snap = served
    e.telemetry.enabled = False
    try:
        got = dict(e.serve(iter([[(0, prompts[0])]]),
                           max_new_tokens=MAX_NEW))
    finally:
        e.telemetry.enabled = True
    np.testing.assert_array_equal(got[0], outs[0])
    view = e.serve_stats
    assert view["frames"] >= 1 and 4 in view["frame_steps_hist"]
    assert view["frame_steps_last"] in view["frame_steps_hist"]
    assert e.telemetry.counters["tokens_emitted"] == 0   # host path idle
    assert e.telemetry.hists["ttft"].total == 0


def test_telemetry_reenabled_mid_serve_discards_backlog(served):
    """Flipping telemetry on mid-serve must not dump the disabled-period
    device-counter backlog into one frame: the transition frame is rebased
    and discarded, so counters reflect only fully-measured frames and the
    occupancy gauge stays a ratio."""
    e, _prompts, _outs, _snap = served
    rng = np.random.default_rng(23)
    p0 = rng.integers(0, 200, (9,)).astype(np.int32)
    p1 = rng.integers(0, 200, (14,)).astype(np.int32)
    e.telemetry.enabled = False
    try:
        gen = e.serve(iter([[(0, p0, 4), (1, p1, 16)]]), max_new_tokens=16)
        uid, toks = next(gen)          # uid 0 retires first (budget 4)
        assert uid == 0 and len(toks) == 4
        e.telemetry.enabled = True     # re-enable while uid 1 is mid-decode
        rest = dict(gen)
    finally:
        e.telemetry.enabled = True
    assert len(rest[1]) == 16
    c = e.telemetry.counters
    # only frames after the (discarded) transition frame are counted
    assert 0 < c["tokens_emitted"] < 4 + 16
    assert 0.0 < e.telemetry.gauges["occupancy"] <= 1.0
    snap = e.telemetry.snapshot()
    assert 0.0 < snap["derived"]["occupancy_avg"] <= 1.0
    assert c["active_row_steps"] <= c["slot_steps_capacity"]


@pytest.mark.slow
def test_wall_clock_latency_values_plausible(tiny_model_params):
    """Wall-clock-sensitive (hence slow-marked): real latencies must be
    positive and ordered TTFT <= E2E for a single-request serve."""
    model, params = tiny_model_params
    e = _engine(model, params)
    prompts = _prompts()
    dict(e.serve(iter([[(0, prompts[0])]]), max_new_tokens=MAX_NEW))
    lat = e.telemetry.latency_ms()
    assert lat["ttft"]["p50"] > 0
    assert lat["e2e"]["p50"] >= lat["ttft"]["p50"]


# ---------------------------------------------------------------------------
# caches by layer kind: the layered lanes, counters and gauges exist for a
# model that mixes windowed and global layers, and for no other
# ---------------------------------------------------------------------------

LAYERED_SERIES = (
    "ds_serving_kv_positions_read_layers_narrow_total",
    "ds_serving_kv_positions_read_layers_wide_total",
    "ds_serving_attn_pairs_layers_narrow_total",
    "ds_serving_attn_pairs_layers_wide_total",
    "ds_serving_kv_positions_read_window_narrow_total",
    "ds_serving_kv_positions_read_window_wide_total",
    "ds_serving_kv_bytes_in_use_sum_total",
    "ds_serving_context_tokens_reserved_sum_total",
    "ds_serving_kv_bytes_in_use",
    "ds_serving_context_tokens_reserved",
)


def _mixed_engine():
    """8 layers ``S, S, S, F`` twice, window 16 over pages of 8: rings of 4
    pages under tables of 16."""
    from deepspeed_tpu.models import get_config
    cfg = get_config(
        "mellum2-12b-a2.5b", vocab_size=256, hidden_size=32, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=32,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        sliding_window=16, window_pattern=(16, 16, 16, 0),
        rope_yarn=(4.0, 32, 32.0, 1.0, None), max_seq_len=128,
        dtype="float32")
    model = build_model(cfg)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            dtype="float32", max_ragged_batch_size=4, prefill_chunk_size=8,
            kv_block_size=8, max_tokens_per_step=64, frame_steps=2),
        params=model.init(jax.random.PRNGKey(0)), max_seq_len=128)


def test_layered_lanes_and_gauges_exist_for_a_model_of_mixed_kinds():
    from deepspeed_tpu.inference.v2.telemetry import (LAYER_STAT_NAMES,
                                                      MOE_STAT_NAMES,
                                                      MOVED_STAT_NAMES,
                                                      N_STATS, n_stats)
    e = _mixed_engine()
    assert e.runner.n_stats == N_STATS + len(MOE_STAT_NAMES) \
        + len(MOVED_STAT_NAMES) + len(LAYER_STAT_NAMES) == n_stats(True, True)
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((0, 45), (1, 9))}
    outs = dict(e.serve(iter([[(u, p) for u, p in prompts.items()]]),
                        max_new_tokens=5))
    assert {len(v) for v in outs.values()} == {5}
    text = e.telemetry.render_prometheus()
    for series in LAYERED_SERIES:
        assert f"# TYPE {series} " in text, series
    # pages by kind beside the gauge without a label, which stays the table
    # kind's pool (what kv_pool_peak_share divides by kv_blocks)
    # (sampled where kv_blocks_in_use is: after a frame, before it retires)
    assert 'ds_serving_kv_blocks_in_use{kind="full"} ' in text
    assert 'ds_serving_kv_blocks_in_use{kind="window16"} ' in text
    # (45 + 5 + 1) and (9 + 5 + 1) tokens: 7 + 2 pages of the table kind,
    # the ring's 4 and 2 of the window kind
    assert 'ds_serving_kv_blocks_in_use_peak{kind="full"} 9' in text
    assert 'ds_serving_kv_blocks_in_use_peak{kind="window16"} 6' in text
    assert "ds_serving_kv_blocks_in_use_peak 10" in text     # + trash page
    snap = e.telemetry.snapshot()
    assert snap["kind_gauges"]["kv_blocks_in_use_peak"] == {
        "full": 9, "window16": 6}
    c = snap["counters"]
    assert 0 < c["kv_positions_read_window_narrow"] \
        < c["kv_positions_read_layers_narrow"]
    # the layered reads are the old lane's (ONE layer at full context:
    # documented as an upper bound a layer) bounded above by 8 layers of it
    one = c["kv_positions_read_narrow"] + c["kv_positions_read_wide"]
    layered = c["kv_positions_read_layers_narrow"] \
        + c["kv_positions_read_layers_wide"]
    assert 2 * one < layered < 8 * one


def test_layered_lanes_and_gauges_are_absent_for_a_model_of_one_kind(
        served):
    """A model whose layers are alike has no trace of them: not in its
    stats vector, its counters, its gauges or ``/metrics``."""
    from deepspeed_tpu.inference.v2.telemetry import N_STATS
    e, _prompts, _outs, snap = served
    assert e.runner.kinds is None and e.runner.layer_work is None
    assert e.runner.n_stats == N_STATS
    assert "kind=" not in snap["prom"]
    for series in LAYERED_SERIES:
        assert series not in snap["prom"], series
    assert not snap["snapshot"]["kind_gauges"]
    assert not [k for k in snap["snapshot"]["counters"]
                if "_layers_" in k or "_window_" in k or k.endswith("_sum")]
    assert "kv_bytes_in_use" not in snap["snapshot"]["gauges"]


# ---------------------------------------------------------------------------
# latent attention and a share of the experts: their lanes, counters and
# gauges exist for such a model, and for no other
# ---------------------------------------------------------------------------

LATENT_SERIES = (
    "ds_serving_latent_positions_read_narrow_total",
    "ds_serving_latent_positions_read_wide_total",
    "ds_serving_latent_pairs_narrow_total",
    "ds_serving_latent_pairs_wide_total",
    "ds_serving_expert_selections_total",
    "ds_serving_zero_expert_selections_total",
    "ds_serving_absent_expert_selections_total",
)


def _latent_engine():
    """2 double layers of latent attention (rows of 20 values in 128
    lanes), a router over 8 experts of which 4 are held and 4 zero ones."""
    from deepspeed_tpu.models import get_config
    cfg = get_config(
        "longcat-flash-omni", vocab_size=256, hidden_size=32, num_layers=2,
        num_heads=4, intermediate_size=48, moe_intermediate_size=16,
        q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, num_experts=4,
        moe_router_experts=8, moe_zero_experts=4, num_experts_per_tok=2,
        max_seq_len=128, dtype="float32")
    model = build_model(cfg)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            dtype="float32", max_ragged_batch_size=4, prefill_chunk_size=8,
            kv_block_size=8, max_tokens_per_step=64, frame_steps=2),
        params=model.init(jax.random.PRNGKey(0)), max_seq_len=128)


def test_latent_and_share_lanes_exist_for_such_a_model():
    from deepspeed_tpu.inference.v2.telemetry import (LATENT_STAT_NAMES,
                                                      MOE_STAT_NAMES,
                                                      MOVED_STAT_NAMES,
                                                      N_STATS,
                                                      SHARE_STAT_NAMES,
                                                      n_stats)
    e = _latent_engine()
    assert e.runner.n_stats == N_STATS + len(MOE_STAT_NAMES) \
        + len(MOVED_STAT_NAMES) + len(SHARE_STAT_NAMES) \
        + len(LATENT_STAT_NAMES) \
        == n_stats(True, share=True, latent=True)
    assert e.runner.latent_layers == 4 and e.kv.v is None
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((0, 45), (1, 9))}
    outs = dict(e.serve(iter([[(u, p) for u, p in prompts.items()]]),
                        max_new_tokens=5))
    assert {len(v) for v in outs.values()} == {5}
    text = e.telemetry.render_prometheus()
    for series in LATENT_SERIES + LAYERED_SERIES[-4:]:
        assert f"# TYPE {series} " in text, series
    assert 'ds_serving_kv_blocks_in_use{kind="latent"} ' in text
    # (45 + 5 + 1) and (9 + 5 + 1) tokens: 7 + 2 pages of the one pool
    assert 'ds_serving_kv_blocks_in_use_peak{kind="latent"} 9' in text
    c = e.telemetry.snapshot()["counters"]
    live = c["prefill_tokens"] + c["target_forwards"]
    assert c["expert_selections"] == live * 2 * 2       # k x layers
    assert c["expert_rows"] + c["zero_expert_selections"] \
        + c["absent_expert_selections"] == c["expert_selections"]
    assert min(c["expert_rows"], c["zero_expert_selections"],
               c["absent_expert_selections"]) > 0
    # 4 attention layers read what the one-layer lane counts
    for split in ("narrow", "wide"):
        assert c[f"latent_positions_read_{split}"] == \
            4 * c[f"kv_positions_read_{split}"] > 0
        assert c[f"latent_pairs_{split}"] == 4 * c[f"attn_pairs_{split}"]
    # one pool: a page is 4 layers x 8 rows x 128 lanes of float32
    assert c["kv_bytes_in_use_sum"] == \
        c["context_tokens_reserved_sum"] * 4 * 128 * 4
    assert e.kv.free_blocks == e.kv.num_blocks - 1


@pytest.mark.parametrize("other", ["dense", "mixed-kinds-routed"])
def test_latent_and_share_lanes_are_absent_for_every_other_model(
        served, other):
    """A dense model, and a routed model of mixed cache kinds that holds
    every expert its router scores, have no trace of them: not in the stats
    vector, the counters or ``/metrics``."""
    from deepspeed_tpu.inference.v2.telemetry import n_stats
    if other == "dense":
        e, _prompts, _outs, snap = served
        text, counters = snap["prom"], snap["snapshot"]["counters"]
        assert e.runner.n_stats == n_stats(False)
    else:
        e = _mixed_engine()
        dict(e.serve(iter([[(0, np.arange(20, dtype=np.int32))]]),
                     max_new_tokens=3))
        text = e.telemetry.render_prometheus()
        counters = e.telemetry.snapshot()["counters"]
        assert e.runner.n_stats == n_stats(True, True)
    assert e.runner.latent_layers is None and e.kv.v is not None
    assert 'kind="latent"' not in text
    for series in LATENT_SERIES:
        assert series not in text, series
    assert not [k for k in counters
                if "latent" in k or k.endswith("_selections")]
