"""work_gdn.py and the four readers of the Gated DeltaNet cell, on numbers
worked by hand and on a small trace excerpt made here; every reader leaves
its metric out (None, no exception) where the program has no such counts,
as the other models and the parent commit have not; the configuration file
against the catalog's numbers; and the new cell's code path end to end at a
tiny size on the CPU (a rehearsal: counts only)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, peaks, scope_reduce, trace_reduce, work_gdn

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("gdn_share", "gdn_scan_roofline", "gdn_step_mfu",
           "recurrent_state_share")
CELL = "qwen3next-longdoc-closed"
NAME = "qwen3-next-80b-a3b-l8-ep4-serve"


def qwen():
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          f"{NAME}.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers():
    """Every key of the catalog's ``config`` as published but the depth and
    the experts held, the two keys under ``reduced``; the traffic is
    LongCat's file and fits the serve shape; the cell's entries in
    BENCHMARK.json."""
    config = qwen()
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    assert {k: config[k] for k in published} == published
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts"}
    assert config["num_hidden_layers"] == 8 \
        == config["reduced"]["num_hidden_layers"]["here"]
    assert config["reduced"]["num_hidden_layers"]["source"] == 48
    assert config["num_experts"] == 128 \
        == config["reduced"]["num_experts"]["here"]
    assert config["reduced"]["num_experts"]["source"] == 512 \
        == config["n_routed_experts_published"]
    assert config["layer_shared_by_chips"] == 4
    assert config["preset_overrides"] == {"num_experts": 128,
                                          "moe_router_experts": 512}
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert entry["source"] == config["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1
    assert cell["traffic"] == "longdoc-closed"
    # behind every entry the benchmark had (where a later PR's come is not
    # this test's business: ``test_work_mtp`` pins GLM's as the LAST and
    # fails since)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(READERS[0]):][:4] == list(READERS)
    assert names.index(READERS[0]) > names.index("wide_step_share")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)
    traffic = harness.read_json(os.path.join(
        harness.HERE, "traffic", "longdoc-closed.json"))
    cls, = traffic["classes"]
    assert cls["prompt"]["max"] + cls["output"]["max"] + 1 \
        < config["serve"]["max_seq_len"] == 16384
    assert traffic["clients"] == config["serve"]["batch"] == 16
    # 16 slots of 16,384 positions in pages of 128, and the trash page
    assert config["serve"]["kv_blocks"] == 16 * 128 + 1


def test_needed_work_by_hand():
    config = qwen()
    s = work_gdn.sizes(config)
    assert (s["linear"], s["full"], s["channels"]) == (6, 2, 8192)
    # ISSUE 43's arithmetic: 25.17 M + 0.13 M + 8.39 M; 16.78 + 2 x 1.05 +
    # 8.39 M; router 1.05 M + shared expert 3.15 M
    assert work_gdn.linear_mixer_params(config) == (
        2048 * 12288 + 2048 * 64 + 4096 * 2048) == 33_685_504
    assert work_gdn.full_mixer_params(config) == (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) == 27_262_976
    assert work_gdn.block_params(config) == (
        2048 * 512 + 3 * 2048 * 512 + 2048) == 4_196_352
    assert work_gdn.token_flops(config) == 2 * (
        6 * 33_685_504 + 2 * 27_262_976 + 8 * 4_196_352)
    assert work_gdn.expert_row_flops(config) == 2 * 3_145_728
    assert work_gdn.recurrence_flops(config) == 7 * 128 * 128 * 32
    assert work_gdn.pair_flops(config) == 4 * 16 * 256
    assert work_gdn.head_flops(config) == 2 * 2048 * 151936
    assert work_gdn.position_bytes(config) == 12288 * 2
    assert work_gdn.state_bytes(config) == 2_097_152
    v5e = peaks.peaks_for("TPU v5 lite")
    # 600 live positions and 16 live rows a step, 6 linear layers: the
    # states' traffic binds, not the recurrence's FLOPs
    floor, bound = work_gdn.scan_floor_s(config, v5e, positions=3600,
                                         state_rw=96)
    assert bound == "memory"
    assert floor == pytest.approx(
        (3600 * 24576 + 96 * 2 * 2_097_152) / 819e9)
    assert 3600 * 7 * 128 * 128 * 32 / 197e12 < floor
    # even a whole chunk a row: a position's q, k, v, o (24,576 B, 30 ns at
    # 819 GB/s) outweigh its 3.67 MFLOP (19 ns at 197 TFLOP/s)
    assert work_gdn.scan_floor_s(config, v5e, positions=6 * 2048,
                                 state_rw=96)[1] == "memory"
    work = dict(width=128, prefill_tokens=589, target_forwards=11,
                tokens_emitted=12, expert_rows=12_000,
                attn_pairs_layers=10 ** 7, gdn_positions=3600,
                gdn_state_rw=96)
    assert work_gdn.frame_flops(config, work) == (
        600 * work_gdn.token_flops(config) + 12_000 * 2 * 3_145_728
        + 3600 * 7 * 128 * 128 * 32 + 10 ** 7 * 16384
        + 12 * 2 * 2048 * 151936)


def _excerpt():
    """A trace as ``scope_reduce.load_scoped`` gives it: the window, two
    whole wide frames, their work, and a device whose operations lie under
    the mixer's scopes for 120 of 300 ns, 70 of them under ``gdn_scan``."""
    work = dict(width=128, prefill_tokens=589, target_forwards=11,
                tokens_emitted=12, expert_rows=12_000, experts_touched=900,
                kv_positions_read_layers=10 ** 5, attn_pairs_layers=10 ** 7,
                gdn_positions=3600, gdn_state_rw=96)
    host = [[scope_reduce.WINDOW_SPAN, 0, 1000],
            ["serve_frame/w128/s8", 100, 200],
            ["serve_frame/w128/s8", 400, 200]]
    path = "jit(loop)/while/body/while/body/closed_call/"
    ops = [["fusion.1", 110, 30, path + "attn/gdn_proj/dot_general:"],
           ["fusion.2", 140, 40, path + "attn/gdn_scan/dot_general:"],
           ["fusion.3", 180, 20, path + "attn/gdn_conv/mul:"],
           ["fusion.4", 410, 30,
            path + "attn/gdn_scan/transpose(jvp(x))/dot_general:"],
           ["fusion.5", 440, 100, path + "mlp/moe_mlp/moe_experts/x:"],
           ["fusion.6", 540, 60,
            path + "mlp/attn_out/attn_gate/mul:"],
           ["fusion.7", 700, 50, path + "attn/gdn_scan/x:"]]   # past the frames
    return {"planes": [{"name": trace_reduce.HOST_PLANE,
                        "lines": [{"name": "python", "events": host}]},
                       {"name": "/device:TPU:0",
                        "lines": [{"name": trace_reduce.OPS_LINE,
                                   "events": ops}]}],
            "frame_work": [(310, dict(work)), (610, dict(work))]}


def test_readers_on_a_small_trace_excerpt(monkeypatch):
    config = qwen()
    red = work_gdn.serve_reduction(_excerpt(), config)
    work = _excerpt()["frame_work"][0][1]
    assert red["frames"] == 2
    assert red["flops"] == 2 * work_gdn.frame_flops(config, work)
    assert (red["positions"], red["state_rw"]) == (7200, 192)
    assert red["scope_s"] == pytest.approx(
        {"gdn_proj": 30e-9, "gdn_scan": 70e-9, "gdn_conv": 20e-9})
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(work_gdn, "device_peaks", lambda: v5e)
    monkeypatch.setattr(work_gdn, "for_ctx", lambda ctx: red)
    monkeypatch.setattr(scope_reduce, "for_ctx",
                        lambda ctx: {"busy_s": 280e-9})
    ctx = {"config": config, "trace": True, "kind": "serve",
           "counters": {"recurrent_bytes_in_use_sum": 3 * 10 ** 9,
                        "kv_bytes_in_use_sum": 9 * 10 ** 9}}
    assert reader("gdn_share").read(ctx) == pytest.approx(100 * 120 / 280)
    floor = (7200 * 24576 + 192 * 2 * 2_097_152) / 819e9
    assert reader("gdn_scan_roofline").read(ctx) == pytest.approx(
        100 * floor / 70e-9)
    assert reader("gdn_step_mfu").read(ctx) == pytest.approx(
        100 * red["flops"] / (280e-9 * 197e12))
    assert reader("recurrent_state_share").read(ctx) == pytest.approx(25.0)
    # no op under gdn_scan in the trace: nothing to hold the floor against
    red["scope_s"] = {"gdn_proj": 30e-9}
    assert reader("gdn_scan_roofline").read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No counters, the counters of a model without linear layers, no
    trace, another configuration's keys; and a trace whose frames' work has
    no counts of the mixer (the parent's, another model's)."""
    read = reader(name).read
    other = {"prefill_tokens": 10, "expert_rows": 5, "target_forwards": 7,
             "kv_bytes_in_use_sum": 100}
    longcat = harness.read_json(os.path.join(
        harness.HERE, "configs", "longcat-flash-omni-l4-ep32-serve.json"))
    for ctx in ({}, {"counters": {}}, {"counters": other, "trace": None},
                {"counters": other, "kind": "serve", "trace": None},
                {"counters": other, "kind": "serve", "trace": True,
                 "config": longcat}):
        assert read(ctx) is None
    config = qwen()
    assert work_gdn.serve_reduction({"planes": [], "frame_work": []},
                                    config) is None
    parents = _excerpt()
    for _, work in parents["frame_work"]:
        del work["gdn_positions"]
    assert work_gdn.serve_reduction(parents, config) is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """A Qwen3-Next-shaped tiny configuration (two periods of three linear
    layers and a full one, Hv = 2 Hk, a quarter rotary, experts 0..3 of a
    router of 16 beside a shared expert) under the closed loop, found by
    name from a BENCHMARK.json of its own through the real one's metric
    lists: the preset, the reference's check through the served path (slots
    reused: admission zeroes the state), the drain, and the counter
    readers."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "qwen-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256, "rope_theta": 1e7,
        "rms_norm_eps": 1e-6, "partial_rotary_factor": 0.25,
        "full_attention_interval": 4, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
        "num_experts_per_tok": 4, "norm_topk_prob": True, "num_experts": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "n_routed_experts_published": 16,
        "reference": "qwen3_next_reference", "preset": "qwen3-next-80b-a3b",
        "preset_overrides": {
            "num_experts": 4, "moe_router_experts": 16,
            "num_experts_per_tok": 4, "head_dim": 16,
            "moe_intermediate_size": 32, "moe_shared_expert_size": 32,
            "linear_num_key_heads": 2, "linear_num_value_heads": 4,
            "linear_key_head_dim": 8, "linear_value_head_dim": 8,
            "dtype": "float32"},
        "serve": {"batch": 4, "max_seq_len": 512}}))
    (tmp_path / "traffic" / "tiny-longdoc.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 1.0, "schedule_seed": 3,
        "classes": [{"name": "longdoc", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 100, "max": 300},
                     "output": {"dist": "uniform", "min": 3, "max": 8}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) <= set(listed) and len(listed) == 23
    # another model's arithmetic: a dense step's or a latent cache's roofs,
    # one window's count x L, the prediction module's lanes
    assert not {"step_mfu", "step_roofline_share", "paged_decode_roofline",
                "paged_prefill_roofline", "paged_mla_prefill_roofline",
                "moe_experts_roofline", "window_kind_read_share",
                "zero_expert_share", "mtp_step_mfu"} & set(listed)
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "qwen-tiny", "source": "test",
                          "file": "configs/qwen-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": CELL, "config": "qwen-tiny",
                            "traffic": "tiny-longdoc", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if CELL in m.get("workloads", [CELL])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", CELL,
         "--seed", "3000000043", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    assert m["window_compiles"]["value"] == 0
    # the 2 full layers' K and V: 2 x 2 x 2 heads x 16 x 4 B a token
    assert m["kv_bytes_per_context_token"]["value"] == 2 * 2 * 2 * 16 * 4
    # a quarter of the router's outputs held: ~ a quarter of 4 picks
    assert 0.4 < m["expert_rows_per_token"]["value"] < 2.0
    assert 0 < m["recurrent_state_share"]["value"] < 100
    assert 0 < m["useful_position_share"]["value"] <= 100
    for name in ("gdn_share", "gdn_scan_roofline", "gdn_step_mfu"):
        assert name not in m
