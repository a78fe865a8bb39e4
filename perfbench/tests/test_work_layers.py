"""work_layers.py, the three readers of the caches by layer kind and the
routed experts' roofline at the experts' own width, on
numbers worked by hand; every reader leaves its metric out (None, no
exception) where the program has no layered counters, as a model of one
kind and the parent commit have not; and the new cell's code path end to end
at a tiny size on the CPU (a rehearsal: counts only)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, peaks, work, work_layers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("kv_bytes_per_context_token", "window_kind_read_share",
           "paged_prefill_roofline_layered", "routed_experts_roofline")


def mellum2():
    return harness.read_json(os.path.join(
        harness.HERE, "configs", "mellum2-12b-a2.5b-l8-serve.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers():
    """Every key of the catalog's ``config`` as published, but the depth."""
    config = mellum2()
    d = work.dims(config)
    assert (d["E"], d["H"], d["KVH"], d["D"], d["V"], d["W"], d["L"]) == (
        2304, 32, 4, 128, 98304, 1024, 8)
    assert config["intermediate_size"] == 7168
    assert config["moe_intermediate_size"] == 896
    assert len(config["layer_types"]) == 28 == len(config["mlp_layer_types"])
    assert config["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert config["rope_parameters"]["full_attention"]["factor"] == 16


def test_layered_attention_work_by_hand():
    d = work.dims(mellum2())
    assert work_layers.position_bytes(d) == 2 * 4 * 128 * 2 == 2048
    assert work_layers.pair_flops(d) == 4 * 32 * 128 == 16384
    pk = peaks.peaks_for("TPU v5 lite")
    # a decode step of 16 rows at 10k tokens: 2 full layers read 10,001
    # positions a row, 6 window layers 1,025
    positions = 16 * (2 * 10001 + 6 * 1025)
    assert positions * 2048 == 856_948_736
    floor = work_layers.attention_floor_s(d, pk, positions=positions,
                                          pairs=positions)
    assert floor == pytest.approx(856_948_736 / 819e9)
    # a prefill chunk of 128 rows over 20k: pairs bind, not bytes
    positions = 2 * 20128 + 6 * 1152
    floor = work_layers.attention_floor_s(d, pk, positions=positions,
                                          pairs=128 * positions)
    assert floor == pytest.approx(128 * positions * 16384 / 197e12)
    kernels = {"paged_attn_c1": 1.0, "paged_attn_ring_c1": 2.0,
               "paged_attn_c128": 4.0, "paged_attn_ring_c128": 8.0,
               "kv_commit_ring_c1": 16.0, "paged_attn_c1x": 32.0}
    assert work_layers.kernel_seconds(kernels, wide=False) == 3.0
    assert work_layers.kernel_seconds(kernels, wide=True) == 12.0


def test_counter_readers_by_hand():
    ctx = {"counters": {
        "kv_bytes_in_use_sum": 100 * (1400 * 524288 + 160 * 1572864),
        "context_tokens_reserved_sum": 100 * 1400 * 128,
        "kv_positions_read_layers_narrow": 3000,
        "kv_positions_read_layers_wide": 1000,
        "kv_positions_read_window_narrow": 700,
        "kv_positions_read_window_wide": 100}}
    assert reader("kv_bytes_per_context_token").read(ctx) == pytest.approx(
        4096 + 160 * 1572864 / (1400 * 128))
    assert reader("window_kind_read_share").read(ctx) == 20.0


def test_routed_experts_roofline_reads_the_experts_own_width(monkeypatch):
    """A wide step at the 272 rung: 2,176 rows a layer over all 64 experts
    of 3 x 2304 x 896 values; the bytes bind. ``moe_experts_roofline``'s
    reader, on the file's dense 7168, would count eight times as much."""
    from perfbench import work_moe
    red = {"expert_rows": 8 * 2176, "experts_touched": 8 * 64,
           "scope_s": {work_moe.EXPERTS: 0.040}}
    monkeypatch.setattr(work_moe, "for_ctx", lambda ctx: red)
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: v5e)
    ctx = {"config": mellum2(), "trace": True, "kind": "serve"}
    floor = 8 * 64 * 3 * 2304 * 896 * 2 / 819e9
    assert floor > 8 * 2176 * 3 * 2304 * 896 * 2 / 197e12
    assert reader("routed_experts_roofline").read(ctx) == pytest.approx(
        100 * floor / 0.040)
    assert reader("moe_experts_roofline").read(ctx) == pytest.approx(
        8 * 100 * floor / 0.040)
    # a file without the key (OLMoE's): the two readers agree
    olmoe = harness.read_json(os.path.join(
        harness.HERE, "configs", "olmoe-1b-7b-l8-serve.json"))
    assert "moe_intermediate_size" not in olmoe
    ctx["config"] = olmoe
    assert reader("routed_experts_roofline").read(ctx) == reader(
        "moe_experts_roofline").read(ctx)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No counters, the counters of a model of one kind (no layered ones),
    no trace."""
    read = reader(name).read
    one_kind = {"prefill_tokens": 10, "kv_positions_read_narrow": 5}
    for ctx in ({}, {"counters": {}}, {"counters": one_kind, "trace": None},
                {"counters": one_kind, "kind": "serve", "trace": None}):
        assert read(ctx) is None
    assert work_layers.serve_reduction({"planes": [], "frame_work": []}) \
        is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """A Mellum2-shaped tiny configuration (window 128 over pages of 128:
    rings of 3 pages under tables of 16) under a closed loop, found by name
    from a BENCHMARK.json of its own: the preset, the reference's check
    through the served path and both cache kinds, the drain, and the
    counter readers (a rehearsal prints counts only)."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    (tmp_path / "configs" / "mellum2-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "vocab_size": 256, "rms_norm_eps": 1e-06, "num_experts": 8,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "sliding_window": 128, "layer_types": kinds * 7,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                "original_max_position_embeddings": 256, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0}},
        "reference": "mellum2_reference", "preset": "mellum2-12b-a2.5b",
        "preset_overrides": {
            "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
            "num_experts_per_tok": 2, "rope_theta": 10000.0,
            "rope_yarn": [4.0, 256, 32.0, 1.0, None],
            "window_pattern": [128, 128, 128, 0], "dtype": "float32",
            "max_seq_len": 2048},
        "serve": {"batch": 4, "max_seq_len": 2048, "kv_blocks": None}}))
    (tmp_path / "traffic" / "tiny-repo.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 0.4, "schedule_seed": 5,
        "classes": [{"name": "file", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 150, "max": 700},
                     "output": {"dist": "uniform", "min": 4, "max": 9}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = "mellum2-repo-closed"
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "mellum2-tiny", "source": "test",
                          "file": "configs/mellum2-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": cell, "config": "mellum2-tiny",
                            "traffic": "tiny-repo", "chips": 1,
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
    assert m["expert_rows_per_token"] == {"value": 2.0, "unit": "rows/token"}
    assert m["window_compiles"]["value"] == 0
    # one table page of 1 layer is a third of a ring page of 3 layers; the
    # rings hold 2 or 3 pages a sequence whatever the 2 to 6 of its table
    page = 2 * 2 * 128 * 16 * 4
    assert page < m["kv_bytes_per_context_token"]["value"] * 128 < 4 * page
    assert 0 < m["window_kind_read_share"]["value"] < 75
    assert "paged_prefill_roofline_layered" not in m
    assert "moe_experts_roofline" not in m


def test_repo_context_traffic_is_the_plain_closed_loop():
    """The cell's callers are ``closed_loop``'s, a second apart, and the
    window opens after the ramp and the longest request's life; no context
    passes the configuration's ``max_seq_len``."""
    params = harness.read_json(os.path.join(
        harness.HERE, "traffic", "repo-context-closed.json"))
    assert params["generator"] == "closed_loop"
    plan = harness.load_module("generators", "closed_loop").plan(
        params, params["schedule_seed"], 126.0)
    assert plan["mode"] == "closed" and plan["clients"] == 16
    assert plan["think_s"] == 0.0
    assert plan["starts"] == [float(c) for c in range(16)]
    assert params["pre_window_s"] >= plan["starts"][-1] + 56
    lens = [r["prompt_len"] + r["max_new"] + 1 for r in plan["requests"]]
    assert max(lens) < 32768
