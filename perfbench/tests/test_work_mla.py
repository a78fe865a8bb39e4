"""work_mla.py and the three readers of the latent-attention cell, on
numbers worked by hand and on a small trace excerpt made here; every reader
leaves its metric out (None, no exception) where the program has no latent
counters, as the other models and the parent commit have not; the
configuration file against the catalog's numbers; and the new cell's code
path end to end at a tiny size on the CPU (a rehearsal: counts only)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import harness, peaks, scope_reduce, trace_reduce, work_mla

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("paged_mla_prefill_roofline", "step_mfu", "zero_expert_share")
CELL = "longcat-longdoc-closed"


def longcat():
    return harness.read_json(os.path.join(
        harness.HERE, "configs", "longcat-flash-omni-l4-ep32-serve.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers():
    """Every key of the catalog's ``config`` as published but the three
    under ``reduced``, and the readers' names equal to the keys they
    repeat."""
    config = longcat()
    published = {
        "attention_bias": False, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert {k: config[k] for k in published} == published
    cut = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
           "vocab_size": (131072, 16384)}
    assert set(config["reduced"]) == set(cut)
    for key, (source, here) in cut.items():
        assert config[key] == here == config["reduced"][key]["here"]
        assert config["reduced"][key]["source"] == source
    assert config["num_hidden_layers"] == config["num_layers"]
    assert config["intermediate_size"] == config["ffn_hidden_size"]
    assert config["moe_intermediate_size"] == config["expert_ffn_hidden_size"]
    assert config["num_experts"] == config["n_routed_experts"]
    assert config["n_routed_experts_published"] == 512
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"]
              if c["name"] == "longcat-flash-omni-l4-ep32-serve"]
    assert sorted(entry["reduced"]) == sorted(cut)
    traffic = harness.read_json(os.path.join(
        harness.HERE, "traffic", "longdoc-closed.json"))
    cls, = traffic["classes"]
    assert cls["prompt"]["max"] + cls["output"]["max"] \
        < config["serve"]["max_seq_len"]
    assert traffic["clients"] == config["serve"]["batch"] == 16


def test_latent_work_by_hand():
    config = longcat()
    assert work_mla.row_bytes(config) == (512 + 64) * 2 == 1152
    assert work_mla.pair_flops(config) == 2 * 64 * (128 + 64 + 128) == 40960
    assert work_mla.mla_params(config) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 8192 * 6144) == 90_570_752
    # two MLAs, two dense FFNs and the router over 768 outputs, x 2 FLOPs
    assert work_mla.token_flops(config) == 2 * (
        2 * 90_570_752 + 2 * 3 * 6144 * 12288 + 6144 * 768) \
        == 2 * 638_844_928
    assert work_mla.expert_row_flops(config) == 2 * 37_748_736
    flops = work_mla.step_flops(config, live_tokens=1000, expert_rows=250,
                                pairs=10 ** 6, emitting_rows=16)
    assert flops == (1000 * 2 * 638_844_928 * 4 + 250 * 2 * 37_748_736
                     + 10 ** 6 * 40960 + 16 * 2 * 6144 * 16384)
    pk = peaks.peaks_for("TPU v5 lite")
    # a decode step of 16 rows at 8k over 8 attention layers: bytes bind
    positions = 16 * 8 * 8193
    floor = work_mla.attention_floor_s(config, pk, positions=positions,
                                       pairs=positions)
    assert floor == pytest.approx(positions * 1152 / 819e9)
    # a prefill chunk of 128 rows over 4k: pairs bind
    floor = work_mla.attention_floor_s(config, pk, positions=8 * 4224,
                                       pairs=128 * 8 * 4224)
    assert floor == pytest.approx(128 * 8 * 4224 * 40960 / 197e12)
    kernels = {"paged_attn_mla_c1": 1.0, "paged_attn_mla_c128": 4.0,
               "paged_attn_c128": 8.0, "kv_commit_mla_c128": 16.0,
               "paged_attn_mla_c128x": 32.0}
    assert work_mla.kernel_seconds(kernels, wide=False) == 1.0
    assert work_mla.kernel_seconds(kernels, wide=True) == 4.0


def _excerpt():
    """A trace as ``scope_reduce.load_scoped`` gives it: the window, two
    whole frames (one wide, one narrow) and their work."""
    work = dict(prefill_tokens=0, target_forwards=0, tokens_emitted=0,
                expert_rows=0, latent_positions_read=0, latent_pairs=0)
    host = [[scope_reduce.WINDOW_SPAN, 0, 1000],
            ["serve_frame/w128/s8", 100, 300], ["serve_frame/w1/s8", 500, 100]]
    return {"planes": [{"name": trace_reduce.HOST_PLANE,
                        "lines": [{"name": "python", "events": host}]}],
            "frame_work": [
                (410, dict(work, width=128, prefill_tokens=512,
                           target_forwards=12, tokens_emitted=13,
                           expert_rows=130, latent_positions_read=40000,
                           latent_pairs=5000000)),
                (610, dict(work, width=1, target_forwards=128,
                           tokens_emitted=128, expert_rows=32,
                           latent_positions_read=900000,
                           latent_pairs=900000))]}


def test_readers_on_a_small_trace_excerpt(monkeypatch):
    red = work_mla.serve_reduction(_excerpt())
    assert red["latent_pairs_wide"] == 5000000
    assert red["latent_positions_read_narrow"] == 900000
    assert red["prefill_tokens"] == 512 and red["target_forwards"] == 140
    assert red["expert_rows"] == 162 and red["tokens_emitted"] == 141
    assert red["latent_pairs"] == 5900000
    config = longcat()
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(work_mla, "device_peaks", lambda: v5e)
    monkeypatch.setattr(work_mla, "for_ctx", lambda ctx: red)
    scoped = {"busy_s": 0.4, "kernel_s": {"paged_attn_mla_c128": 0.010,
                                          "paged_attn_mla_c1": 0.020}}
    monkeypatch.setattr(scope_reduce, "for_ctx", lambda ctx: scoped)
    ctx = {"config": config, "trace": True, "kind": "serve",
           "counters": {"expert_selections": 3000,
                        "zero_expert_selections": 1000}}
    floor = 5000000 * 40960 / 197e12
    assert floor > 40000 * 1152 / 819e9
    assert reader("paged_mla_prefill_roofline").read(ctx) == pytest.approx(
        100 * floor / 0.010)
    flops = work_mla.step_flops(config, live_tokens=652, expert_rows=162,
                                pairs=5900000, emitting_rows=141)
    assert reader("step_mfu").read(ctx) == pytest.approx(
        100 * flops / (0.4 * 197e12))
    assert reader("zero_expert_share").read(ctx) == pytest.approx(100 / 3)
    # no wide latent kernel in the trace: nothing to read
    scoped["kernel_s"] = {"paged_attn_c128": 1.0}
    assert reader("paged_mla_prefill_roofline").read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No counters, the counters of another model (no latent or selection
    ones), no trace; and a trace whose frames' work has no latent counts
    (the parent's)."""
    read = reader(name).read
    other = {"prefill_tokens": 10, "expert_rows": 5}
    for ctx in ({}, {"counters": {}}, {"counters": other, "trace": None},
                {"counters": other, "kind": "serve", "trace": None}):
        assert read(ctx) is None
    assert work_mla.serve_reduction({"planes": [], "frame_work": []}) is None
    parents = _excerpt()
    for _, work in parents["frame_work"]:
        del work["latent_pairs"]
    assert work_mla.serve_reduction(parents) is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """A LongCat-shaped tiny configuration (2 double layers, latent rows of
    20 values, a router over 16 experts of which 4 are held and 8 zero ones)
    under the closed loop, found by name from a BENCHMARK.json of its own
    through the real one's metric lists: the preset, the reference's check
    through the served path, the drain, and the counter readers."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "longcat-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_attention_heads": 4,
        "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
        "num_hidden_layers": 2, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_experts": 4, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "kv_lora_rank": 16,
        "q_lora_rank": 24, "qk_rope_head_dim": 4, "qk_nope_head_dim": 8,
        "v_head_dim": 8, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "zero_expert_num": 8, "moe_topk": 4,
        "rope_theta": 10000000, "rms_norm_eps": 1e-5, "vocab_size": 256,
        "reference": "longcat_flash_reference",
        "preset": "longcat-flash-omni",
        "preset_overrides": {
            "num_experts": 4, "moe_router_experts": 16, "moe_zero_experts": 8,
            "num_experts_per_tok": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
            "moe_intermediate_size": 32, "dtype": "float32"},
        "serve": {"batch": 4, "max_seq_len": 256}}))
    (tmp_path / "traffic" / "tiny-longdoc.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 1.0, "schedule_seed": 3,
        "classes": [{"name": "longdoc", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 20, "max": 150},
                     "output": {"dist": "uniform", "min": 4, "max": 12}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) <= set(listed) and len(listed) == 21
    assert not {"paged_decode_roofline", "paged_prefill_roofline",
                "paged_prefill_roofline_layered", "step_roofline_share",
                "moe_experts_roofline"} & set(listed)
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "longcat-tiny", "source": "test",
                          "file": "configs/longcat-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": CELL, "config": "longcat-tiny",
                            "traffic": "tiny-longdoc", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if CELL in m.get("workloads", [CELL])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    assert m["window_compiles"]["value"] == 0
    # 4 attention layers x 128 lanes of float32 a token, whatever 20 hold
    assert m["kv_bytes_per_context_token"]["value"] == 4 * 128 * 4
    # 4 of 24 outputs are held and 8 are zero experts
    assert 0.3 < m["expert_rows_per_token"]["value"] < 1.1
    assert 25 < m["zero_expert_share"]["value"] < 42
    assert "paged_mla_prefill_roofline" not in m and "step_mfu" not in m
