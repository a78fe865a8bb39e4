"""FastGen-analog tests (reference pattern: tests/unit/inference/v2/**):
allocator/paged-cache unit tests + ragged engine output equivalence against
the dense v1 engine."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_cache import BlockedKVCache
from deepspeed_tpu.models import build_model


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


def _engine(block_size=16, budget=256, chunk=32):
    model = build_model("tiny")
    cfg = RaggedInferenceEngineConfig(kv_block_size=block_size, prefill_chunk_size=chunk,
                                      max_tokens_per_step=budget, dtype="float32",
                                      max_ragged_batch_size=8)
    return InferenceEngineV2(model, cfg, max_seq_len=128)


def test_blocked_allocator():
    a = BlockedAllocator(10)
    got = a.allocate(4)
    assert len(got) == 4 and a.free_blocks == 6
    a.free(got[:2])
    assert a.free_blocks == 8
    with pytest.raises(RuntimeError):
        a.allocate(100)
    with pytest.raises(RuntimeError):
        a.free(got[:1] + got[:1])  # double free detected via free list
    # (second free of same id)


def test_kv_cache_write_gather():
    kv = BlockedKVCache(num_layers=2, kv_heads=2, head_dim=4, num_blocks=8,
                        block_size=4, dtype=jnp.float32)
    blocks = kv.allocator.allocate(2)
    table = jnp.asarray(blocks + [0, 0], jnp.int32)
    new_k = jnp.arange(2 * 6 * 2 * 4, dtype=jnp.float32).reshape(2, 6, 2, 4)
    kv.write(table, 0, new_k, new_k * 2)
    k, v = kv.gather(table[None])
    np.testing.assert_allclose(np.asarray(k[:, 0, :6]), np.asarray(new_k))
    np.testing.assert_allclose(np.asarray(v[:, 0, :6]), np.asarray(new_k * 2))


def test_ragged_generate_matches_dense():
    """v2 paged/ragged greedy output == v1 dense-cache greedy output."""
    model = build_model("tiny")
    params = model.init(jax.random.PRNGKey(0))

    v1 = ds.init_inference(model, dtype="float32")
    v1.module_params = jax.device_put(params, v1.param_shardings)

    v2 = _engine()
    v2.params = jax.device_put(params)

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 200, (1, 24))
    dense = np.asarray(v1.generate(prompt, max_new_tokens=8))[0, 24:]
    ragged = v2.generate([prompt[0]], max_new_tokens=8)[0]
    np.testing.assert_array_equal(dense, ragged)


def test_ragged_mixed_lengths():
    """Prompts of different lengths generate the same as one-by-one."""
    model = build_model("tiny")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 200, (n,)) for n in (7, 24, 50)]

    solo = []
    for p in prompts:
        e = _engine()
        e.params = jax.device_put(params)
        solo.append(e.generate([p], max_new_tokens=6)[0])

    e = _engine()
    e.params = jax.device_put(params)
    batch = e.generate(prompts, max_new_tokens=6)
    for s, b in zip(solo, batch):
        np.testing.assert_array_equal(s, b)


def test_split_fuse_chunking():
    """A prompt longer than the chunk size prefills over multiple steps."""
    e = _engine(chunk=16)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 200, (40,))
    e.put([7], [prompt])
    pending0 = e.query(7)[0]
    assert pending0 == 40
    e.step()
    assert e.query(7)[0] == 24     # one 16-token chunk consumed
    e.step()
    assert e.query(7)[0] == 8
    e.step()
    assert e.query(7)[0] == 0      # final chunk → first token sampled
    assert len(e.query(7)[1]) == 1


def test_can_schedule_block_exhaustion():
    e = _engine(block_size=16)
    assert e.can_schedule([1], [32])
    assert not e.can_schedule([1], [100000])


def test_flush_releases_blocks():
    e = _engine()
    free0 = e.kv.free_blocks
    e.put([1], [np.arange(40)])
    assert e.kv.free_blocks < free0
    e.flush([1])
    assert e.kv.free_blocks == free0


def _stepwise(eng, prompts, n):
    """``n`` greedy tokens a prompt through the host-step API (put / step),
    the oracle ``generate()``'s closed batch through ``serve()`` is held to."""
    uids = list(range(len(prompts)))
    eng.put(uids, prompts)
    counts = {u: 0 for u in uids}
    while not all(counts[u] >= n for u in uids):
        for u in eng.step(temperature=0.0):
            counts[u] += 1
            if counts[u] >= n:
                eng.state.seqs[u].done = True
    outs = [np.asarray(eng.state.seqs[u].generated[:n]) for u in uids]
    eng.flush(uids)
    return outs


def test_generate_matches_stepwise():
    """generate() (a closed batch through serve(): the frame program) must
    produce the same greedy tokens as per-token step() serving."""
    cfg = RaggedInferenceEngineConfig(dtype="float32")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32) for n in (5, 12, 3)]

    eng1 = InferenceEngineV2(build_model("tiny"), cfg)
    outs_loop = eng1.generate(prompts, max_new_tokens=8, temperature=0.0)
    assert eng1.serve_stats["frames"] > 0 and not eng1.state.seqs

    # stepwise baseline on a fresh engine with the SAME params
    eng2 = InferenceEngineV2(build_model("tiny"), cfg, params=eng1.params)
    for a, b in zip(outs_loop, _stepwise(eng2, prompts, 8)):
        np.testing.assert_array_equal(a, b)


def test_build_hf_engine_from_checkpoint_dir(tmp_path):
    """build_hf_engine(path) boots the ragged engine straight from an HF
    checkpoint directory — no torch module instantiated."""
    from transformers import LlamaConfig, LlamaForCausalLM
    import torch
    torch.manual_seed(0)
    hf = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=64))
    hf.eval()
    hf.save_pretrained(str(tmp_path))

    from deepspeed_tpu.inference.v2.engine_v2 import (build_hf_engine,
                                                      RaggedInferenceEngineConfig)
    eng = build_hf_engine(str(tmp_path),
                          RaggedInferenceEngineConfig(kv_block_size=16,
                                                      dtype="float32"),
                          max_seq_len=64)
    prompt = np.random.default_rng(0).integers(0, 128, (1, 8))
    out = eng.generate([prompt[0]], max_new_tokens=6)[0]
    # parity vs the module-injected v1 engine
    v1 = ds.init_inference(hf, dtype="float32")
    ref = np.asarray(v1.generate(prompt, max_new_tokens=6))[0, 8:]
    np.testing.assert_array_equal(ref, out)


def _het_cfg(layer_types):
    from deepspeed_tpu.models.config import TransformerConfig
    return TransformerConfig(
        vocab_size=256, hidden_size=64, num_layers=len(layer_types),
        num_heads=4, intermediate_size=128, max_seq_len=128, num_experts=2,
        num_experts_per_tok=1, layer_types=tuple(layer_types),
        dtype="float32", param_dtype="float32")


@pytest.mark.parametrize("layer_types", [
    ("dense", "moe", "dense", "moe"),   # Qwen2-MoE decoder_sparse_step (periodic)
    ("dense", "dense", "moe", "moe"),   # mlp_only prefix (contiguous segments)
])
def test_ragged_heterogeneous_stack_matches_dense(layer_types):
    """Heterogeneous stacks (cfg.layer_types) serve through the paged v2
    runner (reference FastGen serves Qwen2-MoE sparse stacks,
    ``inference/v2/model_implementations/qwen_v2_moe/model.py``): greedy
    output must match the v1 dense-cache engine for both layer plans."""
    model = build_model(_het_cfg(layer_types))
    params = model.init(jax.random.PRNGKey(0))

    v1 = ds.init_inference(model, dtype="float32")
    v1.module_params = jax.device_put(params, v1.param_shardings)

    cfg = RaggedInferenceEngineConfig(kv_block_size=16, prefill_chunk_size=32,
                                      max_tokens_per_step=256, dtype="float32",
                                      max_ragged_batch_size=8)
    v2 = InferenceEngineV2(model, cfg, max_seq_len=128)
    v2.params = jax.device_put(params)

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 200, (1, 24))
    dense = np.asarray(v1.generate(prompt, max_new_tokens=8))[0, 24:]
    ragged = v2.generate([prompt[0]], max_new_tokens=8)[0]
    np.testing.assert_array_equal(dense, ragged)


def test_generate_staggered_prompts_match_stepwise():
    """generate() (chunked prefill + staggered transitions + decode inside
    the frame program) produces exactly what the host-driven scheduler
    produces, including prompts that straddle chunk boundaries."""
    model = build_model("tiny")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    # lengths chosen to stagger prefill completion across wide steps
    prompts = [rng.integers(0, 200, (n,)) for n in (7, 24, 50, 33)]

    def engine():
        cfg = RaggedInferenceEngineConfig(
            kv_block_size=16, prefill_chunk_size=16, max_tokens_per_step=256,
            dtype="float32", max_ragged_batch_size=8)
        e = InferenceEngineV2(model, cfg, max_seq_len=128)
        e.params = jax.device_put(params)
        return e

    ref = _stepwise(engine(), prompts, 8)
    got = engine().generate(prompts, max_new_tokens=8)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
