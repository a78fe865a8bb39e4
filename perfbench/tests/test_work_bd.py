"""work_bd.py and the five readers of the block-diffusion cell, on numbers
worked by hand at the published widths and on a small trace excerpt made
here; every reader leaves its metric out (None, no exception) where the
program has no such counts, as the other models and the parent commit have
not; the configuration file against the catalog's row; and the new cell's
code path end to end at a tiny size on the CPU (a rehearsal: counts
only)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, peaks, scope_reduce, trace_reduce, work_bd

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("bd_tokens_per_forward", "bd_commit_forward_share",
           "bd_unmask_share", "bd_step_mfu", "bd_block_attn_roofline")
CELL = "sdar-decode-closed"
NAME = "sdar-30b-a3b-l6-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def sdar():
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          f"{NAME}.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers():
    """Every key of the catalog's ``config`` as published but the depth,
    the one key under ``reduced``; what config.json does not name in the
    group ``diffusion`` and, the same values, in the preset's overrides;
    the traffic is OLMoE's file and fits the serve shape; the cell's
    entries in BENCHMARK.json, behind every entry the benchmark had."""
    config = sdar()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row, = [r for r in map(json.loads, fh)
                    if r["name"] == "SDAR-30B-A3B-Chat"]
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 6 \
        == config["reduced"]["num_hidden_layers"]["here"]
    assert config["reduced"]["num_hidden_layers"]["source"] == 48
    assert config["diffusion"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_static",
        "confidence_threshold": 0.9, "mask_token_id": 151669} \
        == config["preset_overrides"]
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"]) == (1, "decode-closed")
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(READERS[0]):][:5] == list(READERS)
    assert names.index(READERS[0]) > names.index("moved_rows_per_expert_row")
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["moves"] == "tokens_per_s" and m["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)
    traffic = harness.read_json(os.path.join(
        harness.HERE, "traffic", "decode-closed.json"))
    cls, = traffic["classes"]
    assert cls["prompt"]["max"] + cls["output"]["max"] + 4 \
        < config["serve"]["max_seq_len"] == 4096
    assert traffic["clients"] == config["serve"]["batch"] == 16
    # 16 slots of 4,096 positions in pages of 128, and the trash page
    assert config["serve"]["kv_blocks"] == 16 * 32 + 1


def test_needed_work_by_hand():
    config = sdar()
    s = work_bd.sizes(config)
    assert (s["layers"], s["blk"], s["k"], s["f"]) == (6, 4, 8, 768)
    # ISSUE 47's arithmetic: attention 18.87 M + router 0.26 M a layer
    assert work_bd.mixer_params(config) == (
        2048 * (4096 + 512 + 512) + 4096 * 2048 + 2048 * 128) == 19_136_512
    assert work_bd.position_flops(config) == 2 * 6 * 19_136_512
    assert work_bd.expert_row_flops(config) == 2 * 3 * 2048 * 768 \
        == 9_437_184
    assert work_bd.pair_flops(config) == 4 * 6 * 32 * 128 == 98_304
    assert work_bd.head_flops(config) == 2 * 2048 * 151936
    # K and V of a position: 6 layers x 2 x 4 heads x 128 x 2 B
    assert work_bd.position_bytes(config) == 12_288
    assert work_bd.block_kernel(config) == "paged_attn_c4"
    # one narrow step of 16 rows at context 1,000, 12 denoising and 4
    # committing: 64 positions, 8 experts a position and layer
    work = {"prefill_tokens": 0, "target_forwards": 16,
            "bd_denoise_forwards": 12, "bd_commit_forwards": 4,
            "tokens_emitted": 16, "expert_rows": 64 * 8 * 6,
            "kv_positions_read": 16 * 1004, "attn_pairs": 16 * 4 * 1004}
    assert work_bd.frame_flops(config, work) == (
        64 * 229_638_144 + 3072 * 9_437_184 + 64_256 * 98_304
        + 48 * 622_329_856)
    v5e = peaks.peaks_for("TPU v5 lite")
    floor, bound = work_bd.attention_floor_s(
        config, v5e, positions=16 * 1004, pairs=16 * 4 * 1004)
    assert bound == "memory"
    assert floor == pytest.approx(16 * 1004 * 12_288 / 819e9)


def _excerpt():
    """Two narrow frames (a block wide) and a wide one with their work, a
    third narrow frame whose work the trace lacks; ops under the scopes the
    program writes."""
    host = [[scope_reduce.WINDOW_SPAN, 0, 1000],
            ["serve_frame/w4/s8", 100, 200], ["serve/frame_work", 310, 1],
            ["serve_frame/w128/s8", 400, 100], ["serve/frame_work", 510, 1],
            ["serve_frame/w4/s8", 600, 100], ["serve/frame_work", 710, 1],
            ["serve_frame/w4/s8", 800, 100]]
    path = "jit(loop)/jit(main)/while/body/"
    ops = [["fusion.1", 100, 40, path + "sample/bd_unmask/reduce_max:"],
           ["paged_attn_c4.3 custom-call(tpu_custom_call)", 150, 50,
            path + "paged_attn/pallas_call:"],
           ["fusion.2", 210, 30, path + "mlp/moe_mlp/moe_experts/x:"],
           ["paged_attn_c128.3 custom-call(tpu_custom_call)", 410, 60,
            path + "paged_attn/pallas_call:"],
           ["fusion.3", 610, 20, path + "sample/bd_unmask/select_n:"],
           ["paged_attn_c4.3 custom-call(tpu_custom_call)", 640, 30,
            path + "paged_attn/pallas_call:"],
           ["fusion.4", 820, 50, path + "sample/bd_unmask/x:"]]  # past them
    narrow = {"width": 4, "steps": 8, "prefill_tokens": 0,
              "tokens_emitted": 100, "target_forwards": 128,
              "expert_rows": 128 * 4 * 8 * 6, "kv_positions_read": 50_000,
              "attn_pairs": 200_000, "bd_denoise_forwards": 100,
              "bd_commit_forwards": 28}
    wide = dict(narrow, width=128, prefill_tokens=900, target_forwards=30,
                kv_positions_read=9_000, attn_pairs=700_000,
                bd_denoise_forwards=24, bd_commit_forwards=6)
    return {"planes": [{"name": trace_reduce.HOST_PLANE,
                        "lines": [{"name": "python", "events": host}]},
                       {"name": "/device:TPU:0",
                        "lines": [{"name": trace_reduce.OPS_LINE,
                                   "events": ops}]}],
            "frame_work": [(310, dict(narrow)), (510, dict(wide)),
                           (710, dict(narrow))]}


def test_readers_on_a_small_trace_excerpt(monkeypatch):
    config = sdar()
    trace = _excerpt()
    red = work_bd.serve_reduction(trace, config)
    narrow, wide = trace["frame_work"][0][1], trace["frame_work"][1][1]
    assert (red["frames"], red["narrow_frames"]) == (3, 2)
    assert red["flops"] == 2 * work_bd.frame_flops(config, narrow) \
        + work_bd.frame_flops(config, wide)
    assert (red["positions_narrow"], red["pairs_narrow"]) == (100_000, 400_000)
    assert red["unmask_s"] == pytest.approx(60e-9)
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(work_bd, "device_peaks", lambda: v5e)
    monkeypatch.setattr(work_bd, "for_ctx", lambda ctx: red)
    monkeypatch.setattr(scope_reduce, "for_ctx", lambda ctx: {
        "busy_s": 230e-9,
        "kernel_s": {"paged_attn_c4": 80e-9, "paged_attn_c128": 60e-9}})
    ctx = {"config": config, "trace": True, "kind": "serve",
           "counters": {"tokens_emitted": 790, "target_forwards": 1000,
                        "bd_denoise_forwards": 800,
                        "bd_commit_forwards": 200}}
    assert reader("bd_tokens_per_forward").read(ctx) == pytest.approx(0.79)
    assert reader("bd_commit_forward_share").read(ctx) == pytest.approx(20.0)
    assert reader("bd_unmask_share").read(ctx) == pytest.approx(100 * 60 / 230)
    assert reader("bd_step_mfu").read(ctx) == pytest.approx(
        100 * red["flops"] / (230e-9 * 197e12))
    floor = max(100_000 * 12_288 / 819e9, 400_000 * 98_304 / 197e12)
    assert reader("bd_block_attn_roofline").read(ctx) == pytest.approx(
        100 * floor / 80e-9)
    # the kernel off the path: nothing to hold the floor against
    monkeypatch.setattr(scope_reduce, "for_ctx", lambda ctx: {
        "busy_s": 230e-9, "kernel_s": {"paged_attn_c128": 60e-9}})
    assert reader("bd_block_attn_roofline").read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No counters, the counters of a left-to-right model, no trace,
    another configuration's keys; and a trace whose frames' work has no
    block counts (the parent's, another model's)."""
    read = reader(name).read
    other = {"prefill_tokens": 10, "expert_rows": 5, "target_forwards": 7,
             "tokens_emitted": 7}
    olmoe = harness.read_json(os.path.join(
        harness.HERE, "configs", "olmoe-1b-7b-l8-serve.json"))
    for ctx in ({}, {"counters": {}}, {"counters": other, "trace": None},
                {"counters": other, "kind": "serve", "trace": None},
                {"counters": other, "kind": "serve", "trace": True,
                 "config": olmoe}):
        assert read(ctx) is None
    config = sdar()
    assert work_bd.serve_reduction({"planes": [], "frame_work": []},
                                   config) is None
    parents = _excerpt()
    for _, work in parents["frame_work"]:
        del work["bd_denoise_forwards"]
    assert work_bd.serve_reduction(parents, config) is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """An SDAR-shaped tiny configuration (2 layers, 8 experts top 2, blocks
    of 4 in 4 steps) under the closed loop, found by name from a
    BENCHMARK.json of its own through the real one's metric lists: the
    preset and its overrides, the reference's replay through the served
    path (slots reused: admission masks the block), the drain, and the
    counter readers."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    diffusion = {"block_length": 4, "denoising_steps": 4,
                 "remasking_strategy": "low_confidence_static",
                 "confidence_threshold": 0.9, "mask_token_id": 255}
    (tmp_path / "configs" / "sdar-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "diffusion": diffusion,
        "reference": "sdar_moe_reference", "preset": "sdar-30b-a3b",
        "preset_overrides": dict(
            diffusion, num_experts=8, num_experts_per_tok=2, head_dim=16,
            moe_intermediate_size=32, dtype="float32"),
        "serve": {"batch": 4, "max_seq_len": 512}}))
    (tmp_path / "traffic" / "tiny-decode.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 1.0, "schedule_seed": 3,
        "classes": [{"name": "longout", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 5, "max": 150},
                     "output": {"dist": "uniform", "min": 6, "max": 24}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) <= set(listed) and len(listed) == 22
    # another model's arithmetic: one token a forward at a dense MLP's
    # width, a latent cache, linear layers, a prediction module
    assert not {"step_mfu", "step_roofline_share", "paged_decode_roofline",
                "paged_prefill_roofline", "moe_experts_roofline",
                "paged_mla_prefill_roofline", "mtp_step_mfu",
                "gdn_step_mfu"} & set(listed)
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "sdar-tiny", "source": "test",
                          "file": "configs/sdar-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": CELL, "config": "sdar-tiny",
                            "traffic": "tiny-decode", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if CELL in m.get("workloads", [CELL])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", CELL,
         "--seed", "3000000047", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert "generated tokens compared, block by block" in proc.stderr
    m = line["metrics"]
    assert m["window_compiles"]["value"] == 0
    # S + 1 forwards a block of 4: 0.8 less the first blocks' remainders
    # and the last blocks' cut positions
    assert 0.4 < m["bd_tokens_per_forward"]["value"] <= 0.8
    assert 20.0 <= m["bd_commit_forward_share"]["value"] < 40.0
    # 2 picks a position and layer, and a row-forward is 4 positions where
    # the reader counts one: between 2 (all prefill) and 8 (all blocks)
    assert 2.0 < m["expert_rows_per_token"]["value"] < 8.0
    assert 0 < m["useful_position_share"]["value"] <= 100
    for name in ("bd_unmask_share", "bd_step_mfu", "bd_block_attn_roofline"):
        assert name not in m
