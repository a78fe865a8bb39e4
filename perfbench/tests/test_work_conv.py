"""work_conv.py and the two readers of the LFM2 cell, on numbers worked by
hand; every reader leaves its metric out (None, no exception) where the
program has no such counts, as the other models and the parent commit have
not; the configuration file against the catalog's numbers and the cell's
entries in BENCHMARK.json."""

import os

import pytest

from perfbench import harness, work_conv

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ("paged_decode_roofline_d64", "conv_tail_share")
CELL = "lfm2-decode-closed"
NAME = "lfm2-24b-a2b-l9-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def lfm2():
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          f"{NAME}.json"))


def test_the_file_holds_the_catalogs_numbers():
    config = lfm2()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    assert set(config["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                      "layer_types"}
    assert config["num_hidden_layers"] == 9 == len(config["layer_types"])
    assert config["layer_types"] == (["conv", "conv", "full_attention",
                                      "conv"] * 10)[1:10]
    assert config["num_dense_layers"] == 1
    assert config["preset_overrides"]["mixer_pattern"] == [
        {"conv": "conv", "full_attention": "full"}[t]
        for t in config["layer_types"]]
    cell = harness.find_cell(CELL)
    assert cell.config == config and cell.traffic_name == "decode-closed"
    assert cell.chips == 1
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names and "kv_bytes_per_context_token" in names
    assert not names & {"paged_decode_roofline", "step_mfu", "gdn_share",
                        "conv_share", "conv_mixer_roofline",
                        "recurrent_state_share", "expert_rows_per_token"}
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "tokens_per_s", "setup_s"]


def test_the_arithmetic_by_hand():
    config = lfm2()
    assert work_conv.sizes(config) == {"e": 2048, "taps": 3, "conv": 7,
                                       "full": 2, "h": 32, "kvh": 8, "d": 64}
    # 16 rows at context 1,024, 2 attending layers: 4,096 B a token
    floor_s, bound = work_conv.attention_floor_s(
        config, PEAKS, positions=2 * 16 * 1024, pairs=2 * 16 * 1024)
    assert bound == "memory"
    assert floor_s == pytest.approx(16 * 1024 * 4096 / 819e9)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_without_the_counts(name):
    read = harness.load_module("layer_metrics", name).read
    assert read(None) is None and read({}) is None
    assert read({"kind": "serve", "trace": None, "config": lfm2(),
                 "counters": {}}) is None
    # another model's run: no conv keys, whatever its counters hold
    assert read({"kind": "serve", "trace": None,
                 "config": {"hidden_size": 64},
                 "counters": {"recurrent_bytes_in_use_sum": 5,
                              "kv_bytes_in_use_sum": 7}}) is None


def test_the_tails_share_of_what_slots_hold():
    ctx = {"config": lfm2(), "counters": {
        "recurrent_bytes_in_use_sum": 16 * 57344,
        "kv_bytes_in_use_sum": 16 * 1024 * 4096}}
    assert work_conv.conv_tail_share(ctx) == pytest.approx(
        100 * 57344 / (57344 + 1024 * 4096))


def test_the_cells_code_path_at_a_tiny_size_on_the_cpu(tmp_path):
    """An LFM2-shaped tiny configuration (mixers ``conv | full conv conv
    conv``, one leading dense layer, 8 experts top 2) under the closed loop,
    found by name from a BENCHMARK.json of its own through the real one's
    metric lists: the preset with its pattern through ``--set``, the
    reference's check through the served path (slots reused: admission
    zeroes the tails), the drain, and the counter readers."""
    import json
    import subprocess
    import sys
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    kinds = ["conv", "full_attention", "conv", "conv", "conv"]
    (tmp_path / "configs" / "lfm2-tiny.json").write_text(json.dumps({
        "kind": "serve", "hidden_size": 64, "num_hidden_layers": 5,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "vocab_size": 256, "norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "conv_L_cache": 3, "layer_types": kinds, "num_dense_layers": 1,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "moe_intermediate_size": 32,
        "reference": "lfm2_moe_reference", "preset": "lfm2-24b-a2b",
        "preset_overrides": {
            "moe_first_dense": 1, "num_experts": 8, "num_experts_per_tok": 2,
            "moe_intermediate_size": 32, "dtype": "float32",
            "mixer_pattern": ["conv", "full", "conv", "conv", "conv"]},
        "serve": {"batch": 4, "max_seq_len": 512}}))
    (tmp_path / "traffic" / "tiny-decode.json").write_text(json.dumps({
        "generator": "closed_loop", "clients": 4, "think_s": 0.0,
        "ramp_s": 1.0, "schedule_seed": 3,
        "classes": [{"name": "longout", "weight": 1.0,
                     "prompt": {"dist": "uniform", "min": 20, "max": 200},
                     "output": {"dist": "uniform", "min": 8, "max": 24}}],
        "pre_window_s": 1.0, "drain_s": 60.0,
        "check": {"short": 1, "long": 1}}))
    real = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench = {"command": real["command"], "paths": ["."], "run_seconds": 3,
             "configs": [{"name": "lfm2-tiny", "source": "test",
                          "file": "configs/lfm2-tiny.json", "reduced": [],
                          "why": "test"}],
             "workloads": [{"name": CELL, "config": "lfm2-tiny",
                            "traffic": "tiny-decode", "chips": 1,
                            "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [m for m in real["per_layer"]
                           if CELL in m.get("workloads", [CELL])]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--benchmark", str(tmp_path / "BENCHMARK.json"), "--workload", CELL,
         "--seed", "3000000051", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = line["metrics"]
    assert m["window_compiles"]["value"] == 0
    # the ONE full layer's K and V: 2 x 2 heads x 16 x 4 B a token
    assert m["kv_bytes_per_context_token"]["value"] == 2 * 2 * 16 * 4
    assert 0 < m["conv_tail_share"]["value"] < 100
    assert 0 < m["useful_position_share"]["value"] <= 100
    assert "paged_decode_roofline_d64" not in m
