"""work_mtp.py and the six readers of the self-drafting cell, on numbers
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

from perfbench import harness, peaks, scope_reduce, trace_reduce, work_mla
from perfbench import work_moe, work_mtp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ("mtp_step_mfu", "paged_mla_decode_roofline", "mtp_draft_share",
           "mtp_acceptance_rate", "mtp_drafts_per_verify",
           "mtp_routed_experts_roofline")
CELL = "glm47-reason-closed"
NAME = "glm-4.7-flash-l7-mtp-serve"


def glm():
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          f"{NAME}.json"))


def reader(name):
    return harness.load_module("layer_metrics", name)


def test_the_file_holds_the_catalogs_numbers():
    """Every key of the catalog's ``config`` as published but the depth,
    which is the one key under ``reduced``; the traffic fits the serve
    shape; the cell's entries in BENCHMARK.json."""
    config = glm()
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "v_head_dim": 256, "vocab_size": 154880}
    assert {k: config[k] for k in published} == published
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert config["num_hidden_layers"] == 7 \
        == config["reduced"]["num_hidden_layers"]["here"]
    assert config["reduced"]["num_hidden_layers"]["source"] == 47
    assert config["num_experts"] == config["n_routed_experts"]
    assert "preset_overrides" not in config      # the preset alone
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry == bench["configs"][-1]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == bench["workloads"][-1] and cell["chips"] == 1
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(READERS)
    traffic = harness.read_json(os.path.join(
        harness.HERE, "traffic", f"{cell['traffic']}.json"))
    cls, = traffic["classes"]
    assert cls["prompt"]["max"] + cls["output"]["max"] == 4096 \
        < config["serve"]["max_seq_len"]
    assert traffic["clients"] == config["serve"]["batch"] == 16
    # 16 slots of 4,096 + 1 positions in pages of 128, and the trash page
    assert config["serve"]["kv_blocks"] >= 16 * -(-4097 // 128) + 1


def test_needed_work_by_hand():
    config = glm()
    assert work_mla.row_bytes(config) == (512 + 64) * 2 == 1152
    assert work_mla.pair_flops(config) == 2 * 20 * (192 + 64 + 256) == 20480
    assert work_mla.mla_params(config) == (
        2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
        + 5120 * 2048) == 21_757_952
    # 7 attentions, the dense FFN, 6 routers and 6 shared experts, x 2
    assert work_mtp.token_flops(config) == 2 * (
        7 * 21_757_952 + 3 * 2048 * 10240
        + 6 * (2048 * 64 + 3 * 2048 * 1536))
    assert work_mtp.expert_row_flops(config) == 2 * 9_437_184
    assert work_mtp.head_flops(config) == 2 * 2048 * 154880
    token, row = work_mtp.token_flops(config), 2 * 9_437_184
    # a wide frame: 300 live tokens computed 300 x 4 x 6 expert rows
    wide = dict(width=128, tokens_emitted=9, target_forwards=0,
                expert_rows=7200, latent_pairs=10 ** 6,
                latent_positions_read=10 ** 4)
    assert work_mtp.frame_flops(config, wide) == (
        300 * token + 7200 * row + 10 ** 6 * 20480
        + 9 * 2 * 2048 * 154880)
    # a narrow frame at acceptance 0: 128 verifies of two positions at
    # context 1,000 emitted 128 tokens; the second position earns nothing
    narrow = dict(width=1, tokens_emitted=128, target_forwards=128,
                  expert_rows=128 * 2 * 24, latent_pairs=7 * 128 * 2 * 1002,
                  latent_positions_read=7 * 128 * 1002)
    assert work_mtp.frame_flops(config, narrow) == (
        128 * token + 128 * 24 * row + 7 * 128 * 1001 * 20480
        + 128 * 2 * 2048 * 154880)
    # every draft accepted: both positions earn, one pair a verify short
    narrow["tokens_emitted"] = 256
    assert work_mtp.frame_flops(config, narrow) == (
        256 * token + 256 * 24 * row
        + 7 * (128 * (1001 + 1002) - 128) * 20480
        + 256 * 2 * 2048 * 154880)
    assert work_mtp.frame_flops(
        config, dict(narrow, tokens_emitted=0, target_forwards=0)) == 0


def _excerpt():
    """A trace as ``scope_reduce.load_scoped`` gives it: the window, two
    whole frames (one wide, one narrow), their work, and a device whose
    operations lie under ``mtp_draft`` for 30 of 200 ns."""
    work = dict(prefill_tokens=0, target_forwards=0, tokens_emitted=0,
                expert_rows=0, experts_touched=0, latent_positions_read=0,
                latent_pairs=0, mtp_latent_positions_read=0,
                mtp_expert_rows=0, mtp_experts_touched=0)
    host = [[scope_reduce.WINDOW_SPAN, 0, 1000],
            ["serve_frame/w128/s8", 100, 300], ["serve_frame/w1/s8", 500, 100]]
    path = "jit(loop)/while/body/closed_call/"
    ops = [["fusion.1", 120, 100, path + "mlp/moe_mlp/moe_experts/x:"],
           ["fusion.2", 510, 20, path + "mtp_draft/attn_qkv/mla_q/dot:"],
           ["paged_attn_mla_c1.1 custom-call(tpu_custom_call)", 530, 10,
            path + "mtp_draft/paged_attn/paged_attn_mla_c1/pallas_call:"],
           ["paged_attn_mla_c2.1 custom-call(tpu_custom_call)", 550, 40,
            path + "while/body/closed_call/paged_attn/paged_attn_mla_c2/"
            "pallas_call:"],
           ["fusion.3", 700, 30, path + "mtp_draft/x:"]]      # past the frames
    return {"planes": [{"name": trace_reduce.HOST_PLANE,
                        "lines": [{"name": "python", "events": host}]},
                       {"name": "/device:TPU:0",
                        "lines": [{"name": trace_reduce.OPS_LINE,
                                   "events": ops}]}],
            "frame_work": [
                (410, dict(work, width=128, prefill_tokens=291,
                           tokens_emitted=9, expert_rows=7200,
                           experts_touched=6 * 64,
                           latent_positions_read=10 ** 4,
                           latent_pairs=10 ** 6)),
                (610, dict(work, width=1, target_forwards=128,
                           tokens_emitted=128, expert_rows=128 * 48,
                           experts_touched=8 * 6 * 55,
                           mtp_experts_touched=8 * 41,
                           latent_positions_read=7 * 128 * 1002,
                           latent_pairs=7 * 128 * 2 * 1002,
                           mtp_latent_positions_read=128 * 1000,
                           mtp_expert_rows=512))]}


def test_readers_on_a_small_trace_excerpt(monkeypatch):
    config = glm()
    red = work_mtp.serve_reduction(_excerpt(), config)
    wide, narrow = (w for _, w in _excerpt()["frame_work"])
    assert red["frames"] == 2 and red["frames_narrow"] == 1
    assert red["flops"] == work_mtp.frame_flops(config, wide) \
        + work_mtp.frame_flops(config, narrow)
    assert red["positions_narrow"] == 7 * 128 * 1002 + 128 * 1000
    assert red["pairs_narrow"] == 7 * 128 * 2 * 1002 + 128 * 1000
    assert red["draft_s"] == pytest.approx(30e-9)
    assert red["expert_rows"] == 7200 + 128 * 48 + 512
    assert red["experts_touched"] == 6 * 64 + 8 * 6 * 55 + 8 * 41
    v5e = peaks.peaks_for("TPU v5 lite")
    monkeypatch.setattr(work_mla, "device_peaks", lambda: v5e)
    monkeypatch.setattr(work_mtp, "for_ctx", lambda ctx: red)
    scoped = {"busy_s": 170e-9, "kernel_s": {
        "paged_attn_mla_c1": 10e-9, "paged_attn_mla_c2": 40e-9,
        "paged_attn_mla_c128": 1.0, "kv_commit_mla_c2": 1.0}}
    monkeypatch.setattr(scope_reduce, "for_ctx", lambda ctx: scoped)
    ctx = {"config": config, "trace": True, "kind": "serve",
           "counters": {"drafted_tokens": 4000, "target_forwards": 4000,
                        "accepted_draft_tokens": 3}}
    assert reader("mtp_step_mfu").read(ctx) == pytest.approx(
        100 * red["flops"] / (170e-9 * 197e12))
    floor = red["positions_narrow"] * 1152 / 819e9
    assert floor > red["pairs_narrow"] * 20480 / 197e12     # bytes bind
    assert reader("paged_mla_decode_roofline").read(ctx) == pytest.approx(
        100 * floor / 50e-9)
    assert reader("mtp_draft_share").read(ctx) == pytest.approx(
        100 * 30 / 170)
    assert reader("mtp_acceptance_rate").read(ctx) == pytest.approx(0.075)
    # the stack's and the module's grouped products: 3,352 touched experts'
    # 3 x 2048 x 1536 values of bf16 bind, not the 13,856 rows
    monkeypatch.setattr(work_moe, "for_ctx", lambda ctx: {
        "scope_s": {work_moe.EXPERTS: 100e-9, "moe_route": 1.0}})
    read_bytes = 3352 * 3 * 2048 * 1536 * 2 / 819e9
    assert read_bytes > 13856 * 2 * 3 * 2048 * 1536 / 197e12
    assert reader("mtp_routed_experts_roofline").read(ctx) == pytest.approx(
        100 * read_bytes / 100e-9)
    monkeypatch.setattr(work_moe, "for_ctx", lambda ctx: None)
    assert reader("mtp_routed_experts_roofline").read(ctx) is None
    assert reader("mtp_drafts_per_verify").read(ctx) == 1.0
    # no narrow latent kernel in the trace: nothing to read
    scoped["kernel_s"] = {"paged_attn_mla_c128": 1.0}
    assert reader("paged_mla_decode_roofline").read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_there_is_nothing_to_read(name):
    """No counters, the counters of a model that drafts nothing, no trace;
    and a trace whose frames' work has no module's counts (the parent's,
    another model's)."""
    read = reader(name).read
    other = {"prefill_tokens": 10, "expert_rows": 5, "drafted_tokens": 0,
             "target_forwards": 7}
    for ctx in ({}, {"counters": {}}, {"counters": other, "trace": None},
                {"counters": other, "kind": "serve", "trace": None}):
        assert read(ctx) is None
    config = glm()
    assert work_mtp.serve_reduction({"planes": [], "frame_work": []},
                                    config) is None
    parents = _excerpt()
    for _, work in parents["frame_work"]:
        del work["mtp_latent_positions_read"]
    assert work_mtp.serve_reduction(parents, config) is None


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """A GLM-4.7-Flash-shaped tiny configuration (a dense layer and two
    routed ones, 5 heads over latent rows, a sigmoid router over 16 experts
    beside a shared one, the prediction module drafting) under the closed
    loop, found by name from a BENCHMARK.json of its own through the real
    one's metric lists: the preset, the reference's check through the
    speculative served path, the drain, and the counter readers."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "glm-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_attention_heads": 5,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "num_experts": 16, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
        "norm_topk_prob": True, "num_nextn_predict_layers": 1,
        "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 4,
        "qk_nope_head_dim": 12, "v_head_dim": 16, "rope_theta": 1000000,
        "rms_norm_eps": 1e-5, "vocab_size": 256,
        "reference": "glm4_moe_lite_reference", "preset": "glm-4.7-flash",
        "preset_overrides": {
            "num_experts": 16, "moe_shared_expert_size": 32,
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
            "qk_rope_head_dim": 4, "v_head_dim": 16,
            "moe_intermediate_size": 32, "dtype": "float32"},
        "serve": {"batch": 4, "max_seq_len": 256}}))
    (tmp_path / "traffic" / "tiny-reason.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 1.0, "schedule_seed": 3,
        "classes": [{"name": "reason", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 5, "max": 60},
                     "output": {"dist": "uniform", "min": 12, "max": 40}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = [m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])]
    assert set(READERS) <= set(listed) and len(listed) == 21
    # another model's arithmetic: LongCat's step, K and V by head, a verify
    # counted as one token (``expert_rows_per_token`` also divides by every
    # layer, the dense one too), ``work_mla.KERNEL`` classing the verify's
    # ``_c2`` wide, the module's seconds under the experts' scope held
    # against the stack's rows alone
    assert not {"step_mfu", "paged_decode_roofline", "paged_prefill_roofline",
                "paged_mla_prefill_roofline", "step_roofline_share",
                "expert_rows_per_token", "moe_experts_roofline",
                "routed_experts_roofline"} & set(listed)
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "glm-tiny", "source": "test",
                          "file": "configs/glm-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": CELL, "config": "glm-tiny",
                            "traffic": "tiny-reason", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if CELL in m.get("workloads", [CELL])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", CELL,
         "--seed", "3000000039", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    assert m["window_compiles"]["value"] == 0
    # 3 + 1 cache layers x 128 lanes of float32 a token
    assert m["kv_bytes_per_context_token"]["value"] == 4 * 128 * 4
    assert m["mtp_drafts_per_verify"]["value"] == 1.0
    assert 0 <= m["mtp_acceptance_rate"]["value"] < 20
    # a verify computes two positions and emits one
    assert 0 < m["useful_position_share"]["value"] < 100
    assert "mtp_step_mfu" not in m and "mtp_draft_share" not in m
    assert "mtp_routed_experts_roofline" not in m
