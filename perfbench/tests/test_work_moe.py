"""work_moe.py and the five readers of the routed experts' layer, on
numbers worked by hand and on a hand-made trace; every reader leaves its
metric out (None, no exception) where the program has no expert counters,
as the parent commit has not, or the model no routed experts; and the new
cell's code path end to end at a tiny size on the CPU (a rehearsal:
counts only)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, peaks, scope_reduce, work_moe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("moe_share", "moe_experts_roofline", "moe_overhead_share",
           "expert_rows_per_token", "expert_load_max_over_mean")
PATH = "jit(loop)/while/body/closed_call/while/body/closed_call/mlp/"
MS = 1_000_000


def olmoe():
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          "olmoe-1b-7b-l8-serve.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_expert_work_by_hand():
    config = olmoe()
    assert work_moe.expert_matrix_values(config) == 3 * 2048 * 1024 \
        == 6_291_456
    # a narrow step of 16 live rows: 128 rows to 56 experts, one layer
    assert work_moe.expert_flops(config, 128) == 2 * 6_291_456 * 128 \
        == 1_610_612_736
    assert work_moe.expert_bytes(config, 56) == 2 * 6_291_456 * 56 \
        == 704_643_072
    pk = peaks.peaks_for("TPU v5 lite")
    floor, bound = work_moe.experts_floor_s(config, pk, expert_rows=128,
                                            experts_touched=56)
    assert bound == "memory"
    assert floor == pytest.approx(704_643_072 / 819e9) \
        == pytest.approx(0.860e-3, rel=1e-3)
    # the 1,040 rung: 8,320 rows to every expert; still the weights' read
    floor, bound = work_moe.experts_floor_s(config, pk, expert_rows=8320,
                                            experts_touched=64)
    assert bound == "memory" and floor == pytest.approx(0.983e-3, rel=1e-3)
    # rows enough that the products bind: 104.7 GFLOP x 2 at 197 TFLOP/s
    floor, bound = work_moe.experts_floor_s(config, pk, expert_rows=16640,
                                            experts_touched=64)
    assert bound == "compute" and floor == pytest.approx(1.063e-3, rel=1e-3)
    # whole model: all 64 experts of the 8 layers are 6.44 GB
    assert work_moe.expert_bytes(config, 64 * 8) == 6_442_450_944


def test_moe_scope_of_takes_the_innermost_and_the_kernels_name():
    s = work_moe.moe_scope_of
    assert s("fusion.3 fusion f32[128,64]",
             PATH + "moe_mlp/moe_route/dot_general:") == "moe_route"
    assert s("sort.1 sort s32[128]", PATH + "moe_mlp/moe_dispatch/sort:") \
        == "moe_dispatch"
    assert s("fusion.9 fusion bf16[128,1024]",
             PATH + "moe_mlp/moe_experts/mul:") == "moe_experts"
    assert s("scatter.2 scatter bf16[16,2048]",
             PATH + "moe_mlp/moe_combine/scatter-add:") == "moe_combine"
    assert s("fusion.4 fusion bf16[16,2048]", PATH + "moe_mlp/reshape:") \
        == "moe_mlp"
    # XLA's grouped-product kernels carry their name and no path
    assert s("ragged-dot-none.2 custom-call(tpu_custom_call) bf16[128,2048]",
             "ragged-dot-none") == "moe_experts"
    assert s("ragged-dot-metadata custom-call(tpu_custom_call) s32[65]",
             "") == "moe_experts"
    assert s("fusion.1 fusion bf16[16,2048]", PATH + "add:") is None
    assert s("paged_attn_c1.3 custom-call(tpu_custom_call) bf16[16,16,1,128]",
             "jit(loop)/while/body/paged_attn/paged_attn_c1/pallas_call:") \
        is None


def frame_events(t0):
    """One 10 ms frame's ops on the device from ``t0``: a while shell
    holding 1 ms of routing, 0.5 ms of dispatch, 4 ms of experts (3.5 ms of
    kernels found by name, 0.5 ms of the gate), 0.5 ms of combine, 0.5 ms
    under moe_mlp alone, 2 ms of attention; 1 ms of the shell is idle."""
    ops = [("fusion.1 fusion f32[128,64]", 1.0, PATH + "moe_mlp/moe_route/dot_general:"),
           ("sort.1 sort s32[128]", 0.5, PATH + "moe_mlp/moe_dispatch/sort:"),
           ("ragged-dot-metadata custom-call(tpu_custom_call) s32[65]", 0.1, "ragged-dot-metadata"),
           ("ragged-dot-none.1 custom-call(tpu_custom_call) bf16[128,1024]", 1.2, "ragged-dot-none"),
           ("ragged-dot-none custom-call(tpu_custom_call) bf16[128,1024]", 1.2, "ragged-dot-none"),
           ("fusion.2 fusion bf16[128,1024]", 0.5, PATH + "moe_mlp/moe_experts/mul:"),
           ("ragged-dot-none.2 custom-call(tpu_custom_call) bf16[128,2048]", 1.0, "ragged-dot-none"),
           ("scatter.1 scatter bf16[16,2048]", 0.5, PATH + "moe_mlp/moe_combine/scatter-add:"),
           ("fusion.3 fusion bf16[16,2048]", 0.5, PATH + "moe_mlp/reshape:"),
           ("paged_attn_c1.1 custom-call(tpu_custom_call) bf16[16,16,1,128]", 2.0,
            "jit(loop)/while/body/paged_attn/paged_attn_c1/pallas_call:")]
    out, t = [], t0 + MS // 2
    for name, ms, path in ops:
        out.append([name, t, int(ms * MS), path])
        t += int(ms * MS)
    return out


def made_trace(counters=True):
    """Two whole frames of 10 ms inside a traced window, each followed by
    its ``serve/frame_work``."""
    host, device, work = [["perfbench/trace_window", 0, 40 * MS]], [], []
    for i, t0 in enumerate((5 * MS, 20 * MS)):
        host.append([f"serve_frame/w1/s8", t0, 10 * MS])
        device += frame_events(t0)
        stats = {"width": 1, "steps": 8, "kv_positions_read": 100,
                 "attn_pairs": 100}
        if counters:
            stats.update(expert_rows=8192 * (i + 1),
                         experts_touched=3584 * (i + 1),
                         expert_rows_max=400)
        work.append([t0 + 10 * MS + 1000, stats])
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": host}]}],
        "frame_work": work}


def test_reduction_of_a_hand_made_trace():
    red = work_moe.serve_reduction(made_trace())
    assert red["frames"] == 2
    assert red["expert_rows"] == 8192 * 3
    assert red["experts_touched"] == 3584 * 3
    # from the first frame's start to the last one's end: 25 ms, 17 busy
    assert red["busy_s"] == pytest.approx(17e-3)
    s = red["scope_s"]
    assert s["moe_route"] == pytest.approx(2e-3)
    assert s["moe_dispatch"] == pytest.approx(1e-3)
    assert s["moe_experts"] == pytest.approx(8e-3)
    assert s["moe_combine"] == pytest.approx(1e-3)
    assert s["moe_mlp"] == pytest.approx(1e-3)
    assert work_moe.moe_seconds(red) == pytest.approx(13e-3)


def test_trace_readers_on_the_hand_made_reduction(monkeypatch):
    red = work_moe.serve_reduction(made_trace())
    monkeypatch.setattr(work_moe, "for_ctx", lambda ctx: red)
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "TPU v5 lite"})()])
    ctx = {"config": olmoe()}
    assert reader("moe_share").read(ctx) == pytest.approx(100 * 13 / 17)
    assert reader("moe_overhead_share").read(ctx) == pytest.approx(
        100 * 4 / 13)
    # 10,752 expert reads of 12.58 MB: 165.2 ms of HBM time against 8 ms of
    # kernel cannot be; the reader reports what it reads, unclamped
    floor = 2 * 6_291_456 * 3584 * 3 / 819e9
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        100 * floor / 8e-3)


def test_counter_readers_by_hand():
    ctx = {"config": olmoe(), "counters": {
        "prefill_tokens": 1000, "target_forwards": 3000,
        "expert_rows": 4000 * 8 * 8, "expert_rows_max": 9000,
        "experts_touched": 20000}}
    assert reader("expert_rows_per_token").read(ctx) == 8.0
    assert reader("expert_load_max_over_mean").read(ctx) == pytest.approx(
        9000 * 64 / 256000)
    # dead positions that reach experts show as rows per token over k
    ctx["counters"]["expert_rows"] += 4000 * 8
    assert reader("expert_rows_per_token").read(ctx) == 9.0


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No trace; a trace whose frames carry no expert counters (the parent
    commit's program); a dense model (the lanes read zero); no counters."""
    read = reader(name).read
    assert work_moe.serve_reduction(made_trace(counters=False)) is None
    assert work_moe.serve_reduction({"planes": [], "frame_work": []}) is None
    parent = {"prefill_tokens": 10, "target_forwards": 10, "frames": 3}
    dense = dict(parent, expert_rows=0, experts_touched=0, expert_rows_max=0)
    for ctx in ({}, {"trace": None}, {"config": olmoe()},
                {"config": olmoe(), "counters": parent, "trace": None},
                {"config": olmoe(), "counters": dense, "kind": "serve"}):
        assert read(ctx) is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """An OLMoE-shaped tiny configuration under a closed loop, found by
    name from a BENCHMARK.json of its own: the preset, the reference's
    check through the served path, and the counter readers (a rehearsal
    prints counts only)."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "olmoe-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 32, "vocab_size": 256, "rope_theta": 10000,
        "rms_norm_eps": 1e-05, "num_experts": 16, "num_experts_per_tok": 4,
        "norm_topk_prob": False, "reference": "olmoe_reference",
        "preset": "olmoe-1b-7b",
        "preset_overrides": {"num_experts": 16, "num_experts_per_tok": 4,
                             "dtype": "float32", "max_seq_len": 512},
        "serve": {"batch": 4, "max_seq_len": 512, "kv_blocks": None}}))
    (tmp_path / "traffic" / "tiny-decode.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 0.4, "schedule_seed": 5,
        "classes": [{"name": "longout", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 20, "max": 150},
                     "output": {"dist": "uniform", "min": 6, "max": 12}}],
        "pre_window_s": 1.0, "drain_s": 30.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = "olmoe-decode-closed"
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "olmoe-tiny", "source": "test",
                          "file": "configs/olmoe-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": cell, "config": "olmoe-tiny",
                            "traffic": "tiny-decode", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if cell in m.get("workloads", [cell])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    assert m["expert_rows_per_token"] == {"value": 4.0, "unit": "rows/token"}
    assert 1.0 <= m["expert_load_max_over_mean"]["value"] <= 16.0
    assert m["window_compiles"]["value"] == 0
    assert "moe_share" not in m and "moe_experts_roofline" not in m
