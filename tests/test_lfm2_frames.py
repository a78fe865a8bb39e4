"""LFM2-24B-A2B served end to end, and what the serving path refuses, maps
and pins for it: a serve's counters over the conv layers' lane, the
validator's refusals for a stack of conv layers, the ``lfm2_moe``
checkpoint's names, and Qwen3-Next's frame programs, whose walk and carry
this family shares, pinned to the parent's. The small model and its helpers
are ``tests/test_lfm2_serving.py``'s (a file of its own so that neither
passes 40 s on one worker).
"""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations.archs import (
    Lfm2MoeContainer, resolve_container)
from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
from deepspeed_tpu.models import build_model, get_config
from test_lfm2_serving import (MIXERS, SEQ, SHAPE, engine,  # noqa: F401
                               tiny_lfm2, whole)


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


def test_a_serve_counts_the_conv_layers_work(whole):
    """After a serve the stat vector's last lane, ``conv_positions``, is the
    conv layers x (prompt tokens + decode forwards), the experts' rows the
    routed layers x k x the same, and a live slot holds its tails' bytes
    (4 layers x 2 rows x 64 channels x 4 B)."""
    model, params = whole
    e = engine(model, params)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 140, 33)]
    out = e.generate(prompts, max_new_tokens=5)
    assert [len(o) for o in out] == [5, 5, 5]
    c = e.telemetry.counters
    tokens = c["prefill_tokens"] + c["target_forwards"]
    assert c["prefill_tokens"] == 178
    assert c["conv_positions"] == 4 * tokens
    assert c["expert_rows"] == 4 * 2 * tokens
    assert "gdn_positions" not in c
    assert e.runner.recurrent_stat_names == ("conv_positions",)
    assert c["recurrent_bytes_in_use_sum"] > 0
    assert c["recurrent_bytes_in_use_sum"] % (4 * 2 * 64 * 4) == 0
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    # put() / step() walk the forward without the carry: refused, by kind
    e.put([0], [np.arange(10, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="conv layers"):
        e.step()
    e.flush([0])



# ---- (e) what a state a slot refuses ----------------------------------------


@pytest.mark.parametrize("option,match", [
    (dict(tp=2), "tp=2"),
    (dict(prefix_cache=True), "prefix_cache.*conv layers"),
    (dict(kv_swap_dir="/nonexistent"), "swap tier"),
    (dict(role="prefill"), "handoff"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(nonfinite_policy="repair"), "repair"),
    ("draft", "draft"),
    ("module", "prediction module"),
])
def test_the_validator_refuses_for_a_conv_stack(option, match):
    """What a state a slot cannot be served with yet is refused at engine
    build for a stack of conv layers as for one of linear layers, each with
    its reason, the error naming the kind."""
    kw, build = {}, {}
    if option == "draft":
        build["draft_model"] = build_model("tiny")
    elif option == "module":
        kw["num_nextn_predict_layers"] = 1
    else:
        build.update(option)
    model = tiny_lfm2(**kw)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError,
                       match=f"conv layers keeps a state a slot.*{match}"):
        InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                dtype="float32", **{**SHAPE, **{k: v for k, v in build.items()
                                                if k != "draft_model"}}),
            params=params, max_seq_len=SEQ,
            **{k: v for k, v in build.items() if k == "draft_model"})


# ---- (f) the checkpoint's names ---------------------------------------------


def test_container_maps_a_random_lfm2_moe_state_dict(whole):
    """``Lfm2MoeContainer`` reads the family's config and lays a state dict
    of its names out as the model's own tree: mixers by ``layer_types``, the
    Conv1d's (channels, 1, K) taps, the dense layer's w1 / w3 / w2, the
    routed layers' gate, expert_bias and experts."""
    model, params = whole
    hf = types.SimpleNamespace(
        architectures=["Lfm2MoeForCausalLM"], model_type="lfm2_moe",
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=32, max_position_embeddings=SEQ,
        norm_eps=1e-5, conv_L_cache=3, conv_bias=False, num_experts=8,
        num_experts_per_tok=2, num_dense_layers=1, norm_topk_prob=True,
        use_expert_bias=True, routed_scaling_factor=1.0,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        layer_types=["conv", "full_attention", "conv", "conv", "conv"])
    assert resolve_container(hf) is Lfm2MoeContainer
    got = Lfm2MoeContainer.config(hf)
    assert got.replace(dtype="float32") == model.cfg.replace(
        param_dtype=got.param_dtype)
    sd = {"model.embed_tokens.weight": params["embed"]["tok"],
          "model.embedding_norm.weight": params["final_norm"]["scale"]}
    for l, kind in enumerate(MIXERS):
        lp = jax.tree.map(lambda w: np.asarray(w[0]),
                          params["layers"][f"g{l}"])
        pre = f"model.layers.{l}."
        sd[pre + "operator_norm.weight"] = lp["norm1"]["scale"]
        sd[pre + "ffn_norm.weight"] = lp["norm2"]["scale"]
        a = lp["attn"]
        if kind == "conv":
            sd[pre + "conv.in_proj.weight"] = a["w_in"].T
            sd[pre + "conv.conv.weight"] = a["conv"].T[:, None, :]
            sd[pre + "conv.out_proj.weight"] = a["w_out"].T
        else:
            sd[pre + "self_attn.q_proj.weight"] = a["wq"].reshape(64, -1).T
            sd[pre + "self_attn.k_proj.weight"] = a["wk"].reshape(64, -1).T
            sd[pre + "self_attn.v_proj.weight"] = a["wv"].reshape(64, -1).T
            sd[pre + "self_attn.out_proj.weight"] = a["wo"].reshape(-1, 64).T
            sd[pre + "self_attn.q_layernorm.weight"] = a["q_norm"]["scale"]
            sd[pre + "self_attn.k_layernorm.weight"] = a["k_norm"]["scale"]
        m, ff = lp["mlp"], pre + "feed_forward."
        if l == 0:
            sd.update({ff + "w1.weight": m["wi_gate"].T,
                       ff + "w3.weight": m["wi_up"].T,
                       ff + "w2.weight": m["wo"].T})
        else:
            sd[ff + "gate.weight"] = m["router"].T
            sd[ff + "expert_bias"] = m["router_bias"]
            for x in range(8):
                sd.update({ff + f"experts.{x}.w1.weight": m["wi_gate"][x].T,
                           ff + f"experts.{x}.w3.weight": m["wi_up"][x].T,
                           ff + f"experts.{x}.w2.weight": m["wo"][x].T})
    back = Lfm2MoeContainer.build_params(sd, got)
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(back)[0]:
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat[path]),
                                      err_msg=jax.tree_util.keystr(path))
    assert len(flat) == len(jax.tree.leaves(back))


# ---- the family whose path this one shares ----------------------------------

#: sha256 of ``str(jaxpr)`` of Qwen3-Next's frame programs at 4 slots x 2
#: steps, pages of 8, at the PARENT commit (2350b40, PR 50's anchor): the
#: walk, the carry and the stat lanes this PR said of "what each kind
#: keeps" trace the programs they traced (mistral's, OLMoE's and GLM's are
#: pinned in ``tests/test_sdar_serving.py``). Re-pinned by PR 52 to its own
#: tree: at this size (256 selection rows, under two blocks) both programs
#: run the combine that became a gather by token (at 14b980e: 5fa82809... and
#: b8f53c6f...); at the cell's size the wide rungs' loops trace as they did
QWEN3_NEXT_PARENTS = {
    1: "a7e7562b3f01e951e07edf605bc8922e6fb3742adf81fd07d12fdbab9f58fdd9",
    16: "51f0d2b507d7a1756e5ae1008399b99ce025af4254576eac6e3a5c78573d3df3",
}


def qwen3_next_frame_jaxpr(width):
    cfg = get_config(
        "qwen3-next-80b-a3b", vocab_size=256, hidden_size=64, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
        moe_shared_expert_size=32, num_experts=8, moe_router_experts=16,
        num_experts_per_tok=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, max_seq_len=256, dtype="float32")
    model = build_model(cfg)
    runner = PagedModelRunner(model, 8, 32)
    slots, steps, i32, sds = 4, 2, jnp.int32, jax.ShapeDtypeStruct
    row, flag = sds((slots,), i32), sds((slots,), jnp.bool_)
    key = jax.random.PRNGKey(0)
    pool = sds((cfg.cache_layers, cfg.kv_heads, 33, 8, cfg.dims_per_head),
               jnp.float32)
    jaxpr = runner._build_frame_loop().trace(
        model.abstract_params(), sds((slots, 256), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, 32), i32), row, row, row,
        flag, flag, flag, sds((runner.n_stats,), i32),
        sds(key.shape, key.dtype), pool, pool,
        recurrent=tuple(sds(shape, dtype) for shape, dtype in
                        runner.recurrent_shapes(slots)),
        width=width, steps=steps, greedy=True, n_steps=sds((), i32)).jaxpr
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()


@pytest.mark.parametrize("width", list(QWEN3_NEXT_PARENTS),
                         ids=["narrow", "wide"])
def test_qwen3_next_frame_programs_are_the_parents(width):
    assert qwen3_next_frame_jaxpr(width) == QWEN3_NEXT_PARENTS[width]


# ---- heads of 64 lanes, two a row of a page ---------------------------------


def test_two_heads_share_a_row_of_a_page(monkeypatch):
    """Where the chip's kernels read the pages, heads of 64 lanes sit two a
    128-lane row (``kv_cache.heads_per_row``; ``_forward`` reads the packing
    off the pool's shape): the same step over a pool (layers, 2, pages, page,
    64) on the gather path and over one (layers, 1, pages, page, 128)
    through the paged kernel and the commit (interpreted here) gives the
    same logits, and the packed pool holds the other's rows head beside
    head: a query's lanes beside zeros meet its own head's keys alone."""
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.kv_cache import heads_per_row
    assert [heads_per_row(*s) for s in ((8, 64), (2, 64), (3, 64), (8, 128),
                                        (2, 256), (4, 16))] == [2, 2, 1, 1,
                                                                1, 1]
    cfg = get_config(
        "lfm2-24b-a2b", vocab_size=256, hidden_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=64, intermediate_size=64,
        moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
        mixer_pattern=("full", "conv"), moe_first_dense=0, max_seq_len=64,
        dtype="float32")
    assert cfg.dims_per_head == 64
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    params["layers"]["g0"]["attn"] = {
        n: w * 8.0 if n.startswith("w") else w
        for n, w in params["layers"]["g0"]["attn"].items()}
    slots, page, pages = 4, 16, 9
    runner = PagedModelRunner(model, page, 2)
    rng = np.random.default_rng(0)
    tables = np.asarray([[1, 2], [3, 4], [5, 6], [7, 8]], np.int32)
    tail = jnp.zeros(runner.recurrent_shapes(slots)[0][0], jnp.float32)

    def walk(in_row):
        """A chunk of 16 (rows of 16, 9 and 0 live positions), then a step
        of one position a row, through a pool of either layout."""
        kp = vp = jnp.zeros((1, 2 // in_row, pages, page, 64 * in_row))
        fwd = jax.jit(lambda *a, recurrent: runner._forward(
            *a, recurrent=recurrent))
        ids = rng.integers(0, 256, (slots, 16)).astype(np.int32)
        n = np.asarray([16, 9, 0, 3], np.int32)
        pos = np.where(np.arange(16)[None] < n[:, None], np.arange(16)[None],
                       -1).astype(np.int32)
        first, kp, vp, rec = fwd(params, ids, pos, tables, n, kp, vp,
                                 recurrent=(tail,))
        live = np.asarray([1, 1, 0, 1], np.int32)
        second, kp, vp, _ = fwd(
            params, ids[:, :1], np.where(live > 0, n, -1)[:, None].astype(
                np.int32), tables, live, kp, vp, recurrent=rec)
        return np.asarray(first), np.asarray(second), np.asarray(kp)

    rng = np.random.default_rng(0)
    f1, s1, k1 = walk(1)
    live = [0, 1, 3]
    assert np.abs(f1[live]).max() > 0.05
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    rng = np.random.default_rng(0)
    f2, s2, k2 = walk(2)
    np.testing.assert_allclose(f2[live], f1[live], atol=2e-5)
    np.testing.assert_allclose(s2[live], s1[live], atol=2e-5)
    # head r of a token in lanes [64 r, 64 r + 64) of the one row (page 0 is
    # the trash page)
    np.testing.assert_allclose(k2[0, 0, 1:, :, :64], k1[0, 0, 1:], atol=1e-6)
    np.testing.assert_allclose(k2[0, 0, 1:, :, 64:], k1[0, 1, 1:], atol=1e-6)
