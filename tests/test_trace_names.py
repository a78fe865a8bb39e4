"""What the chip runs and what the host does between frames have names.

Three contracts (ISSUE 23):

- **device side**: the lowered frame programs and a training step carry
  the layer scopes (``jax.named_scope``) and every Pallas kernel its
  ``name=`` — metadata only, so these tests read lowered text and run
  nothing;
- **host side**: ``ServingTelemetry.phase`` accumulates exclusive times
  that tile the serve loop and is a ``serve/<name>`` span only when
  ``trace`` is set; ``train_batch`` is a profiler step with its two phases;
- **counters**: ``positions_computed``, ``kv_positions_read_*`` and
  ``attn_pairs_*`` equal a host mirror of the in-graph arithmetic exactly.
"""

import ast
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
from deepspeed_tpu.inference.v2.telemetry import PHASES, ServingTelemetry
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils import groups

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_SCOPES = ("embed", "attn_qkv", "paged_attn", "attn_out", "mlp",
                "kv_commit", "lm_head", "frame_plan", "sample")
TRAIN_SCOPES = ("embed", "attn", "attn_qkv", "attn_out", "mlp",
                "lm_head_loss", "optimizer")


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


def _scope_components(lowered):
    """Every component of every op_name in a lowering's HLO text."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    parts = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        # autodiff wraps a scope it differentiates: transpose(jvp(mlp))
        parts.update(re.sub(r"^(?:\w+\()+|\)+$", "", part)
                     for part in name.split("/"))
    return parts


# ---------------------------------------------------------------------------
# device side: scopes and kernel names in the lowered programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [8, 1])
def test_frame_loop_lowering_names_scopes_and_kernel(width, monkeypatch):
    """The tiny frame program (the registry's, w=8 and w=1) names every
    layer scope, and its two paged kernels by chunk width: the decode
    kernels are ``paged_attn_c1`` and ``kv_commit_c1`` whatever XLA numbers
    them."""
    from deepspeed_tpu.analysis import programs as P
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    eng = P._tiny_engine()
    args = P._frame_args(eng, P._slot_table(eng))
    lowered = eng.runner._build_frame_loop().lower(
        *args, width=width, steps=2, greedy=True)
    parts = _scope_components(lowered)
    assert set(SERVE_SCOPES) <= parts, set(SERVE_SCOPES) - parts
    assert {f"paged_attn_c{width}", f"kv_commit_c{width}"} <= parts


@pytest.mark.parametrize("width", [8, 1])
def test_mixed_kinds_frame_lowering_names_the_ring_kernels(width,
                                                           monkeypatch):
    """A model that mixes windowed and global layers (8 layers ``S, S, S,
    F`` twice, window 16 over pages of 8: rings of 4) names its kernels by
    cache kind under the same scopes: ``paged_attn_ring_c<C>`` and
    ``kv_commit_ring_c<C>`` beside the kernels over whole tables, so the
    accepted readers' ``^paged_attn_c\\d+$`` keeps meaning what it meant."""
    from deepspeed_tpu.models import get_config
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    cfg = get_config(
        "mellum2-12b-a2.5b", vocab_size=128, hidden_size=32, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=8, intermediate_size=32,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        sliding_window=16, window_pattern=(16, 16, 16, 0),
        rope_yarn=(4.0, 32, 32.0, 1.0, None), max_seq_len=128,
        dtype="float32")
    model = build_model(cfg)
    eng = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            dtype="float32", max_ragged_batch_size=4, prefill_chunk_size=8,
            kv_block_size=8, max_tokens_per_step=64, frame_steps=2),
        params=model.init(jax.random.PRNGKey(0)), max_seq_len=128)
    slots = DeviceSlotTable(4, prompt_width=8, table_width=16,
                            rng=jax.random.PRNGKey(0),
                            n_stats=eng.runner.n_stats, rings=[4])
    lowered = eng.runner._build_frame_loop().lower(
        eng.params, slots.prompts, slots.prompt_lens, slots.limits,
        slots.eos_ids, slots.temps, (slots.tables,) + slots.ring_tables,
        slots.cached, slots.produced, slots.last_tok, slots.done,
        slots.poison, slots.nonfinite, slots.stats, slots.rng, eng.kv.k,
        eng.kv.v, width=width, steps=2, greedy=True)
    parts = _scope_components(lowered)
    assert set(SERVE_SCOPES) <= parts, set(SERVE_SCOPES) - parts
    assert {f"paged_attn_c{width}", f"paged_attn_ring_c{width}",
            f"kv_commit_c{width}", f"kv_commit_ring_c{width}"} <= parts


def _tiny_longcat_engine():
    from deepspeed_tpu.models import get_config
    cfg = get_config(
        "longcat-flash-omni", vocab_size=128, hidden_size=32, num_layers=2,
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


@pytest.mark.parametrize("width", [8, 1])
def test_latent_frame_lowering_names_the_mla_kernels_and_scopes(width,
                                                                monkeypatch):
    """A model with latent attention names its two kernels
    ``paged_attn_mla_c<C>`` and ``kv_commit_mla_c<C>`` under the scopes every
    model has (so ``^paged_attn(?:_ring)?_c\\d+$`` keeps meaning K and V by
    head), and the latent projections ``mla_q`` / ``mla_kv`` / ``mla_absorb``
    inside ``attn_qkv`` and ``attn_out``; the routed block's scopes are the
    ones a routed model has. None of the other models' kernels is in it."""
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    eng = _tiny_longcat_engine()
    slots = DeviceSlotTable(4, prompt_width=8, table_width=16,
                            rng=jax.random.PRNGKey(0),
                            n_stats=eng.runner.n_stats)
    lowered = eng.runner._build_frame_loop().lower(
        eng.params, slots.prompts, slots.prompt_lens, slots.limits,
        slots.eos_ids, slots.temps, slots.tables, slots.cached,
        slots.produced, slots.last_tok, slots.done, slots.poison,
        slots.nonfinite, slots.stats, slots.rng, eng.kv.k, eng.kv.v,
        width=width, steps=2, greedy=True)
    parts = _scope_components(lowered)
    assert set(SERVE_SCOPES) <= parts, set(SERVE_SCOPES) - parts
    assert {f"paged_attn_mla_c{width}", f"kv_commit_mla_c{width}", "mla_q",
            "mla_kv", "mla_absorb", "moe_mlp", "moe_route", "moe_dispatch",
            "moe_experts", "moe_combine"} <= parts
    assert not {f"paged_attn_c{width}", f"kv_commit_c{width}"} & parts
    text = lowered.as_text(dialect="hlo", debug_info=True)
    assert "attn_qkv/mla_q" in text and "attn_qkv/mla_kv" in text
    assert "attn_qkv/mla_absorb" in text and "attn_out/mla_absorb" in text


def test_train_step_lowering_names_scopes_and_flash_kernels():
    """A tiny training step names its layer scopes, the optimizer, and the
    three flash kernels (interpret mode off the chip)."""
    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(data=8))
    model = build_model("tiny", attn_impl="flash", max_seq_len=128)
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}, "steps_per_print": 10 ** 9})
    ids = np.zeros((8, 128), np.int32)
    batch = engine.stage_batch({"input_ids": ids, "labels": ids})
    lowered = engine._train_step_fn.lower(
        engine.module_params, engine.opt_state, engine.scaler_state, batch,
        jnp.float32(1e-3), gas=1)
    parts = _scope_components(lowered)
    assert set(TRAIN_SCOPES) <= parts, set(TRAIN_SCOPES) - parts
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= parts


def _pallas_call_files():
    return sorted(
        os.path.relpath(p, ROOT) for p in glob.glob(
            os.path.join(ROOT, "deepspeed_tpu", "**", "*.py"), recursive=True)
        if "pl.pallas_call(" in open(p).read())


@pytest.mark.parametrize("path", _pallas_call_files())
def test_every_pallas_call_is_named(path):
    """No Mosaic kernel is anonymous: every ``pl.pallas_call`` passes
    ``name=`` (the HLO instruction is then ``%<name>.N``, and the name is
    a component of the op's path in a trace)."""
    tree = ast.parse(open(os.path.join(ROOT, path)).read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "pallas_call"]
    assert calls
    for call in calls:
        assert any(k.arg == "name" for k in call.keywords), \
            f"{path}:{call.lineno}: pallas_call without name="


# ---------------------------------------------------------------------------
# host side: phases
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


def test_phase_adds_exclusive_time_under_injected_clock(monkeypatch):
    """``phase`` adds elapsed ``phase_clock`` time to ``host_<name>_ns``;
    a nested phase pauses its parent; with ``trace`` off no
    ``TraceAnnotation`` is ever built."""
    def forbidden(*a, **kw):
        raise AssertionError("TraceAnnotation entered with trace off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    clock = _Clock()
    tel = ServingTelemetry(phase_clock=clock)
    with tel.phase("admit"):
        clock.t += 5
    with tel.phase("retire"):
        clock.t += 2
        with tel.phase("yield"):
            clock.t += 100
        clock.t += 3
    c = tel.counters
    assert (c["host_admit_ns"], c["host_retire_ns"], c["host_yield_ns"]) \
        == (5, 5, 100)
    assert sum(c[f"host_{n}_ns"] for n in PHASES) == clock.t
    tel.enabled = False
    with tel.phase("admit"):
        clock.t += 7
    assert tel.counters["host_admit_ns"] == 5


def test_phase_is_a_serve_span_when_trace_is_on(monkeypatch):
    entered = []

    class Spy:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    tel = ServingTelemetry(trace=True)
    with tel.phase("poll"):
        with tel.frame_trace(128, 8):
            pass
    assert entered == ["serve/poll", "serve_frame/w128/s8",
                       "/serve_frame/w128/s8", "/serve/poll"]


def test_frame_trace_has_no_silent_fallback(monkeypatch):
    """A profiler that cannot annotate raises; it used to degrade to a
    ``nullcontext`` behind a bare ``except``."""
    def broken(*a, **kw):
        raise RuntimeError("no profiler")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", broken)
    with pytest.raises(RuntimeError, match="no profiler"):
        ServingTelemetry(trace=True).frame_trace(1, 8)


@pytest.fixture(scope="module")
def tiny_model_params():
    model = build_model("tiny", num_heads=8)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **over):
    kw = dict(kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
              dtype="float32", max_ragged_batch_size=8, frame_steps=4)
    kw.update(over)
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(**kw),
                             params=params, max_seq_len=128)


PROMPTS = {u: np.random.default_rng(5).integers(0, 200, (200,))
           .astype(np.int32)[o:o + n]
           for u, (o, n) in enumerate(((0, 7), (10, 24), (40, 33), (80, 5)))}
MAX_NEW = 8


def _arrivals(schedule):
    for k in range(max(schedule) + 2):
        yield [(u, PROMPTS[u]) for u in schedule.get(k, [])]


@pytest.mark.parametrize("scheduled", [False, True])
def test_phases_tile_the_serve_loop(tiny_model_params, scheduled):
    """The phases of a scripted serve() sum to the loop's wall time within
    2%, under the FIFO policy and under a scheduler; the consumer's time
    lands in ``yield``."""
    model, params = tiny_model_params
    e = _engine(model, params)
    sched = None
    if scheduled:
        from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                          SchedulerConfig)
        sched = RequestScheduler(SchedulerConfig())
    # warm the shape buckets, so the measured run is three frames of
    # steady work and not compilation
    dict(e.serve(_arrivals({0: [0, 1, 2]}), max_new_tokens=MAX_NEW,
                 scheduler=sched))
    gen = e.serve(_arrivals({0: [0, 1, 2]}), max_new_tokens=MAX_NEW,
                  scheduler=sched)
    consumer_ns = 0
    t0 = time.perf_counter_ns()
    for _uid, _toks in gen:
        c0 = time.perf_counter_ns()
        time.sleep(0.01)
        consumer_ns += time.perf_counter_ns() - c0
    wall = time.perf_counter_ns() - t0
    c = e.telemetry.counters
    assert c["frames"] >= 3
    total = sum(c[f"host_{n}_ns"] for n in PHASES)
    assert abs(total - wall) <= 0.02 * wall, (total, wall)
    assert c["host_yield_ns"] >= consumer_ns
    # idle: the first poll, on a server still empty
    for n in ("idle", "poll", "admit", "plan", "dispatch", "fetch", "absorb",
              "retire"):
        assert c[f"host_{n}_ns"] > 0, n


@pytest.mark.parametrize("scheduled", [False, True])
def test_an_empty_servers_wait_is_idle_not_poll(tiny_model_params, scheduled):
    """A poll with nothing live and nothing queued is the wait for the next
    arrival: its time goes to ``host_idle_ns`` (span ``serve/idle``), so a
    quiet stretch of traffic does not read as host work between frames."""
    model, params = tiny_model_params
    e = _engine(model, params)
    sched = None
    if scheduled:
        from deepspeed_tpu.inference.v2.scheduler import (RequestScheduler,
                                                          SchedulerConfig)
        sched = RequestScheduler(SchedulerConfig())
    dict(e.serve(_arrivals({0: [0]}), max_new_tokens=MAX_NEW,
                 scheduler=sched))                     # warm the programs

    def gappy():
        yield [(0, PROMPTS[0])]
        for _ in range(3 * MAX_NEW):    # request 0 runs out: server empties
            yield []
        for _ in range(4):
            time.sleep(0.05)            # the arrival gap
            yield []
        yield [(3, PROMPTS[3])]

    outs = dict(e.serve(gappy(), max_new_tokens=MAX_NEW, scheduler=sched))
    assert set(outs) == {0, 3}
    c = e.telemetry.counters
    assert c["host_idle_ns"] >= 4 * 0.05e9
    assert c["host_poll_ns"] < 0.05e9


def test_stat_range_is_checked_when_serving_starts(tiny_model_params):
    """The int32 work lanes hold 2^32 between two reads: a frame that could
    score more pairs is refused at serve(), not read wrapped."""
    from deepspeed_tpu.inference.v2.telemetry import check_stat_range
    check_stat_range(16, 128, 8, 4096)               # the benchmark: 69 M
    with pytest.raises(ValueError, match="frame counters"):
        check_stat_range(64, 512, 8, 32768)
    model, params = tiny_model_params
    e = _engine(model, params)
    with pytest.raises(ValueError, match="lower frame_steps"):
        e.serve(iter([]), frame_steps=1 << 22)


# ---------------------------------------------------------------------------
# counters where the work happens
# ---------------------------------------------------------------------------


def _mirror_frame(slots, width, steps, window):
    """The in-graph step arithmetic (``_wide_plan`` + ``_attn_work`` +
    ``_rung_of``) replayed on the host mirrors as they stand BEFORE a
    frame: returns (kv positions read, query x key pairs, positions the
    per-token layers ran, {rung: steps}) of the frame."""
    from deepspeed_tpu.inference.v2.telemetry import pack_ladder
    ladder = pack_ladder(slots.n_slots, width)
    kv_read = pairs = positions = 0
    rung_steps = {}
    rows = [[int(slots.cached_h[i]), int(slots.plen_h[i]),
             int(slots.produced_h[i]), int(slots.limit_h[i])]
            for i in range(slots.n_slots) if slots.uid_of_slot[i] >= 0]
    for _ in range(steps):
        live = 0
        for row in rows:
            cached, plen, produced, limit = row
            prefilling = cached < plen
            if not (prefilling or produced < limit):
                continue
            w = min(width, plen - cached) if prefilling else 1
            kv = cached + w if window is None else min(cached + w, window + w)
            kv_read += kv
            pairs += w * kv
            live += w
            if not prefilling or cached + w == plen:
                row[2] += 1
            row[0] += w
        rung = next(t for t in ladder if live <= t)
        positions += rung
        if len(ladder) > 1:
            rung_steps[rung] = rung_steps.get(rung, 0) + 1
    return kv_read, pairs, positions, rung_steps


@pytest.mark.parametrize("tp", [1, 8])
@pytest.mark.parametrize("window", [None, 8])
def test_work_counters_equal_the_host_mirror(window, tp, monkeypatch):
    """``positions_computed`` (the rung each step chose in the graph, a
    device lane), the per-rung step counters, ``kv_positions_read_*`` and
    ``attn_pairs_*`` equal the host-mirror arithmetic exactly on a mixed
    prefill/decode run (arrivals land mid-decode), with and without a
    sliding window, tp=1 and tp=8. The chunk is 32 wide so that a wide step
    of the 8 slots has rungs (16 and 144 tokens, and the 256 of the chunk
    whole) and packs its live tokens, under ``shard_map`` too."""
    over = {} if window is None else {"sliding_window": window}
    model = build_model("tiny", num_heads=8, **over)
    e = _engine(model, model.init(jax.random.PRNGKey(0)), tp=tp,
                prefill_chunk_size=32)
    want = {"positions_computed": 0, "kv_positions_read_narrow": 0,
            "kv_positions_read_wide": 0, "attn_pairs_narrow": 0,
            "attn_pairs_wide": 0}
    want_rungs = {}
    orig = DeviceSlotTable.dispatch_frame

    def spy(self, runner, params, kv, width, steps, greedy, **kw):
        # the steps the frame runs (its ``n_steps`` operand), not the
        # ``steps`` rows of its emission buffers
        kv_read, pairs, positions, rungs = _mirror_frame(
            self, width, kw["n_steps"], window)
        split = "wide" if width > 1 else "narrow"
        want[f"kv_positions_read_{split}"] += kv_read
        want[f"attn_pairs_{split}"] += pairs
        want["positions_computed"] += positions
        for t, n in rungs.items():
            want_rungs[t] = want_rungs.get(t, 0) + n
        return orig(self, runner, params, kv, width, steps, greedy, **kw)

    monkeypatch.setattr(DeviceSlotTable, "dispatch_frame", spy)
    outs = dict(e.serve(_arrivals({0: [0, 1], 2: [2], 3: [3]}),
                        max_new_tokens=MAX_NEW))
    assert len(outs) == 4
    got = {k: e.telemetry.counters[k] for k in want}
    assert got == want
    assert want["kv_positions_read_narrow"] and want["attn_pairs_wide"]
    got_rungs = {int(dict(k)["tokens"]): n for k, n in
                 e.telemetry.labeled["rung_steps"].items()}
    assert got_rungs == want_rungs and set(want_rungs) == {16, 144}
    c = e.telemetry.counters
    # the identity useful_position_share rests on
    assert c["prefill_tokens"] == sum(len(p) for p in PROMPTS.values())
    assert c["prefill_tokens"] + c["target_forwards"] \
        <= c["positions_computed"]


def test_work_counters_add_no_in_frame_transfers(tiny_model_params,
                                                 frame_transfer_guard):
    """The two new lanes ride the donated carry like the rest: with the
    frame dispatch under a device->host transfer guard, and with ``trace``
    on (phases and the per-frame ``serve/frame_work`` annotation), a serve
    still performs zero in-frame D2H."""
    model, params = tiny_model_params
    e = _engine(model, params, telemetry_trace=True)
    outs = dict(e.serve(_arrivals({0: [0, 1]}), max_new_tokens=MAX_NEW))
    assert len(outs) == 2
    c = e.telemetry.counters
    assert c["kv_positions_read_wide"] > 0 and c["attn_pairs_narrow"] > 0


def test_recompiled_programs_counts_every_program_the_loop_asked_for(
        tiny_model_params):
    """The gauge used to see frame programs only; it now counts every
    program the serve loop's thread asked XLA for (admission's small ones
    too), with the time the loop waited."""
    model, params = tiny_model_params
    # a slot count no other test of the process uses: jnp's small programs
    # are cached process-wide by shape, and these must be asked for here
    e = _engine(model, params, max_ragged_batch_size=5)
    dict(e.serve(_arrivals({0: [0, 1], 2: [2]}), max_new_tokens=MAX_NEW))
    c, g = e.telemetry.counters, e.telemetry.gauges
    assert c["programs_requested"] > e.runner.compile_count_total() >= 2
    assert c["compile_wait_ns"] > 0
    assert g["recompiled_programs"] == c["programs_requested"]
    # a second serve on warm programs asks for none
    dict(e.serve(_arrivals({0: [0, 1], 2: [2]}), max_new_tokens=MAX_NEW))
    assert e.telemetry.counters["programs_requested"] == 0


# ---------------------------------------------------------------------------
# on the profiler's clock
# ---------------------------------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve", "train")):
                    out.append((ev.name, dict(ev.stats)))
    return out


def test_spans_reach_the_profiler(tiny_model_params, tmp_path):
    """One profiled run of both entry points: ``train_batch`` is a step
    with its number and its two phases; a traced serve writes every phase,
    the frame spans, and each frame's work as the stats of a
    ``serve/frame_work`` span."""
    model, params = tiny_model_params
    e = _engine(model, params, telemetry_trace=True)
    groups.reset_mesh()
    groups.set_mesh(groups.build_mesh(data=8))
    engine, _, _, _ = ds.initialize(model=build_model("tiny"), config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10 ** 9})
    ids = np.zeros((8, 16), np.int32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        dict(e.serve(_arrivals({0: [0, 1]}), max_new_tokens=MAX_NEW))
        jax.block_until_ready(
            engine.train_batch({"input_ids": ids, "labels": ids}))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert {f"serve/{n}" for n in PHASES if n != "publish"} <= names
    assert "serve/publish" in names
    # the wide frame ends with uid 1's second chunk of 16, the narrow
    # frames behind it run the engine's 4 steps or end with a last token
    assert any(n.startswith("serve_frame/w16/s2") for n in names)
    assert any(n.startswith("serve_frame/w1/s4") for n in names)
    assert not any(n.startswith("serve_frame/w16/s4") for n in names)
    assert {"train_batch", "train/stage", "train/dispatch"} <= names
    steps = [s for n, s in events if n == "train_batch"]
    assert steps and steps[0]["step_num"] == 0
    work = [s for n, s in events if n == "serve/frame_work"]
    c = e.telemetry.counters
    assert len(work) == c["frames"]
    # a frame is one length everywhere it is told: the plan's trace, the
    # span's ``steps``, the histogram, the step counters, and the positions
    # the device counted (8 slots x width a step: a chunk of 16 has one rung)
    ran = [(int(w["width"]), int(w["steps"])) for w in work]
    assert ran[0] == (16, 2) and (1, 4) in ran[1:]
    assert {w for w, _ in ran[1:]} == {1}
    assert [s for _, s in ran] \
        == [r["steps"] for r in e.serve_stats["frame_steps_trace"]]
    assert e.serve_stats["frame_steps_last"] == ran[-1][1]
    assert (c["wide_steps"], c["frame_steps"]) == (2, sum(s for _, s in ran))
    assert c["positions_computed"] == sum(8 * w * s for w, s in ran)
    assert sum(int(w["prefill_tokens"]) for w in work) == c["prefill_tokens"]
    assert sum(int(w["attn_pairs"]) for w in work) \
        == c["attn_pairs_narrow"] + c["attn_pairs_wide"]
