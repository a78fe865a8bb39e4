"""work.py against numbers worked by hand for both configurations."""

import pytest

from perfbench import harness, peaks, work


def cfg(name):
    import os
    return harness.read_json(os.path.join(harness.HERE, "configs",
                                          f"{name}.json"))


def test_parameter_counts_by_hand():
    d = work.dims(cfg("mistral-7b-l16-serve"))
    # q, o: 4096*32*128 each; k, v: 4096*8*128 each; MLP 3*4096*14336
    layer = 2 * 16_777_216 + 2 * 4_194_304 + 3 * 58_720_256
    assert layer == 218_103_808
    assert work.layer_matmul_params(d) == layer
    head = 4096 * 32000
    assert work.matmul_params(d) == 16 * layer + head == 3_620_732_928
    assert work.param_count(d) == 16 * layer + 2 * head + 33 * 4096
    # bf16: what one step reads of the weights (7.24 GB -> 8.8 ms at 819 GB/s)
    assert work.weight_bytes(d) == 2 * (16 * layer + head + 33 * 4096)
    assert work.weight_bytes(d) / peaks.peaks_for("TPU v5 lite")[
        "hbm_bytes_per_s"] == pytest.approx(8.84e-3, rel=1e-3)


def test_train_flops_by_hand():
    d = work.dims(cfg("mistral-7b-l8-zero3"))
    n = 8 * 218_103_808 + 4096 * 32000
    assert work.matmul_params(d) == n == 1_875_902_464
    # causal window 4096 over 8192 positions: 4096*4097/2 + 4096*4096
    pairs = 8_390_656 + 16_777_216
    assert work.causal_visible_sum(d, 8192) == pairs
    assert work.causal_visible_sum(d, 8192) == work.visible_sum(d, 0, 8192)
    assert work.causal_visible_sum(d, 100) == 5050
    attn_fwd = 4 * 32 * 128 * 8 * pairs
    assert work.attn_flops(d, pairs) == attn_fwd
    step = 6 * n * 8192 + 3 * attn_fwd
    assert work.train_step_flops(d, 1, 8192) == step
    assert step / 1e12 == pytest.approx(102.1, rel=1e-3)   # a chip a step
    assert work.train_step_flops(d, 4, 8192) == 4 * step


def test_request_work_by_hand():
    d = work.dims(cfg("mistral-7b-l16-serve"))
    w = work.request_work(d, prompt_len=300, n_out=3)
    assert w["prefill_attn_flops"] == 4 * 32 * 128 * 16 * (300 * 301 // 2)
    # generated tokens 2 and 3 are computed at contexts 301 and 302 (the
    # first comes out of the prefill)
    assert w["decode_attn_flops"] == 4 * 32 * 128 * 16 * (301 + 302)
    kv_token = 2 * 8 * 128 * 16 * 2            # bytes a cached position
    assert w["decode_kv_bytes"] == kv_token * (301 + 302)
    # chunks end at 128, 256, 300
    assert w["prefill_kv_bytes"] == kv_token * (128 + 256 + 300)
    # past the window a query sees 4096 keys
    far = work.request_work(d, prompt_len=5000, n_out=2)
    assert far["decode_attn_flops"] == 4 * 32 * 128 * 16 * 4096


def test_serve_floor_names_its_bound():
    d = work.dims(cfg("mistral-7b-l16-serve"))
    pk = peaks.peaks_for("TPU v5 lite")
    # 8 decode steps of 16 rows: weights dominate
    t, bound, flops, nbytes = work.serve_span_floor(
        d, pk, prefill_tokens=0, decode_tokens=128, steps=8,
        requests=[(256, 128)])
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)
    assert nbytes > 8 * work.weight_bytes(d)
    # 8 prefill steps of 2048 useful positions: compute dominates
    t, bound, flops, nbytes = work.serve_span_floor(
        d, pk, prefill_tokens=8 * 2048, decode_tokens=0, steps=8,
        requests=[(3072, 32)])
    assert bound == "compute"
    assert t == pytest.approx(flops / 197e12)
    assert flops > 2 * 16 * 218_103_808 * 8 * 2048


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
