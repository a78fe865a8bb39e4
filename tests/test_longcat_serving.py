"""LongCat-Flash-Omni's language model on the paged serving path, against
its plain reference.

The preset (``models/config.py`` ``longcat-flash-omni``) is served through
latent pages: one cached row a token and attention layer, the absorbed
attention over them, two attentions and two dense FFNs a layer with one
routed block beside the second pair, and a router that scores more outputs
than the experts held (zero experts; one chip's share of a layer). The
reference is the benchmark's (``perfbench/configs/longcat_flash_reference.py``:
float32, the EXPANDED attention, every held expert computed for every
token), which shares no code with the program. Sizes here are small; the
shape is LongCat's.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_model, get_config
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.moe import sharded_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (absorbed against
#: expanded products, rows grouped by expert against every expert dense and
#: masked, pages against one softmax): measured 1.9e-5 at most over every
#: compared row, on logits up to 4.5. Every planted fault below reads over
#: 0.02: three orders of magnitude outside.
LOGIT_TOL = 1e-4
FAULT_FLOOR = 0.02

#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
          "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
          "v_head_dim": 8, "mla_scale_q_lora": True,
          "mla_scale_kv_lora": True, "zero_expert_num": 8, "moe_topk": 4,
          "routed_scaling_factor": 6.0, "rope_theta": 1e7,
          "rms_norm_eps": 1e-5, "vocab_size": 256}
LAYERS, EXPERTS = 4, 16
#: 8 slots x 64 positions packs (rungs 16, 144, 272, 512); 8 x 1 does not
SHAPE = dict(max_ragged_batch_size=8, prefill_chunk_size=64, kv_block_size=8,
             max_tokens_per_step=512, frame_steps=2)
SLOTS, WIDTH, PAGE = 8, 64, 8


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs",
                        "longcat_flash_reference.py")
    spec = importlib.util.spec_from_file_location("longcat_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the benchmark's blocks are sized for 15k tokens
    mod.TOKEN_BLOCK, mod.Q_BLOCK, mod.WIDTH_BLOCK = 64, 16, 64
    return mod


def tiny_longcat(held=EXPERTS, first=0, **kw):
    cfg = get_config(
        "longcat-flash-omni", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"], num_layers=LAYERS,
        num_heads=CONFIG["num_attention_heads"], intermediate_size=96,
        moe_intermediate_size=32, q_lora_rank=CONFIG["q_lora_rank"],
        kv_lora_rank=CONFIG["kv_lora_rank"],
        qk_nope_head_dim=CONFIG["qk_nope_head_dim"],
        qk_rope_head_dim=CONFIG["qk_rope_head_dim"],
        v_head_dim=CONFIG["v_head_dim"], num_experts=held,
        moe_expert_first=first, moe_router_experts=EXPERTS,
        moe_zero_experts=CONFIG["zero_expert_num"],
        num_experts_per_tok=CONFIG["moe_topk"], max_seq_len=256,
        dtype="float32", **kw)
    return build_model(cfg)


def share_of(params, first, held):
    """``params`` with experts [first, first + held) of every layer."""
    layers = dict(params["layers"])
    layers["moe"] = {n: w[:, first:first + held] if n in L.EXPERT_MATRICES
                     else w for n, w in layers["moe"].items()}
    return {**params, "layers": layers}


@pytest.fixture(scope="module")
def whole():
    """Seeded float32 weights with every expert held, the layers' matrices
    scaled up from their initial 0.02 so that attention, routing and the
    experts all move the logits. At E = 64 a router drawn at 0.02 gives a
    flat softmax (logit std 0.16 against ~1.6 at E = 6144), so it is drawn
    20 x wider: the top 4 then carry most of the mass; the bias is halved
    to change some picks and not most."""
    model = tiny_longcat()
    params = model.init(jax.random.PRNGKey(34))
    layers = params["layers"]
    layers["attn"] = {n: w if n.endswith("_norm") else w * 4.0
                      for n, w in layers["attn"].items()}
    layers["mlp"] = {n: w * 6.0 for n, w in layers["mlp"].items()}
    layers["moe"] = {n: w * {"router": 20.0, "router_bias": 0.5}.get(n, 8.0)
                     for n, w in layers["moe"].items()}
    return model, params


def engine(model, params):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype="float32", **SHAPE),
        params=params, max_seq_len=256)


def sequences(short=False):
    """Requests of different lengths: (prompt + forced continuation)."""
    rng = np.random.default_rng(134)
    if short:
        return {1: (rng.integers(0, 256, 40 + 2).astype(np.int32), 40)}
    return {0: (rng.integers(0, 256, 100 + 6).astype(np.int32), 100),
            3: (rng.integers(0, 256, 37 + 8).astype(np.int32), 37)}


def paged_steps(e, params, seqs, garbage_seed=5):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a frame does: prompts in chunks of ``WIDTH`` beside each other
    (a chunk crosses pages of 8), then one position a step through the
    latent pages, the other slots idle with garbage ids under position -1.
    Yields per step (logits (slots, V), {slot: position of its last token},
    the routed block's work (6,), live tokens)."""
    rng = np.random.default_rng(garbage_seed)
    tables = np.zeros((SLOTS, 256 // PAGE), np.int32)
    for i, slot in enumerate(seqs):
        tables[slot] = 1 + i * tables.shape[1] + np.arange(tables.shape[1])
    assert e.kv.v is None and e.kv.k.shape[:2] == (2 * LAYERS, 1)
    pool = jnp.zeros_like(e.kv.k)
    fwd = jax.jit(lambda *a: e.runner._forward(*a, moe_work=True))
    done = {slot: 0 for slot in seqs}
    while any(done[s] < len(ids) for s, (ids, _) in seqs.items()):
        prefilling = any(done[s] < plen for s, (_, plen) in seqs.items())
        width = WIDTH if prefilling else 1
        ids = rng.integers(0, 256, (SLOTS, width)).astype(np.int32)
        positions = np.full((SLOTS, width), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        for slot, (seq, plen) in seqs.items():
            at = done[slot]
            n = min(width, plen - at) if at < plen else min(1, len(seq) - at)
            ids[slot, :n] = seq[at:at + n]
            positions[slot, :n] = at + np.arange(n)
            valid[slot], done[slot] = n, at + n
        logits, pool, none, work = fwd(params, ids, positions, tables, valid,
                                       pool, None)
        assert none is None
        yield (np.asarray(logits), {s: done[s] - 1 for s in seqs if valid[s]},
               np.asarray(work), int(valid.sum()))


def worst_gap(e, params, reference, seqs, config=CONFIG):
    """Largest |served logit - reference logit| over every compared row."""
    want = {s: reference.logits_rows(params, ids, np.arange(len(ids)), config)
            for s, (ids, _) in seqs.items()}
    return max(np.abs(logits[s] - want[s][at]).max()
               for logits, last, _, _ in paged_steps(e, params, seqs)
               for s, at in last.items())


@pytest.mark.parametrize("held", [EXPERTS, 4], ids=["every-expert", "share-4"])
@pytest.mark.parametrize("kernels", [False, True], ids=["gather", "pallas"])
def test_served_path_matches_the_reference(monkeypatch, whole, reference,
                                           held, kernels):
    """Chunked prefill, then decode through the latent pages, against the
    reference's logits at every position that ends a step: with every expert
    held and with a share of 4 (what the other 12 would have added left out
    on both sides); on the gather path and through both latent kernels
    (interpreted). The routed block's counters: every live token makes
    ``moe_topk`` selections a layer, and each is a held expert's row, a zero
    expert's or an absent expert's."""
    if kernels:
        monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    model, params = whole
    params = share_of(params, 0, held)
    e = engine(tiny_longcat(held), params)
    seqs = sequences()
    want = {s: reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
            for s, (ids, _) in seqs.items()}
    assert max(np.abs(w).max() for w in want.values()) > 2.0
    worst, zero = 0.0, 0
    for logits, last, work, live in paged_steps(e, params, seqs):
        for s, at in last.items():
            worst = max(worst, np.abs(logits[s] - want[s][at]).max())
        rows, _, _, _, picked, zeros, absent = work
        assert picked == live * CONFIG["moe_topk"] * LAYERS
        assert rows + zeros + absent == picked
        assert (absent == 0) == (held == EXPERTS)
        zero += zeros
    assert zero > 0
    assert worst < LOGIT_TOL, worst


def test_the_shares_add_up(whole, reference):
    """The routed block over the 4 shares of 4 experts, the identity part
    counted once, is the uncut block: in the program (each share a model
    that holds experts ``first``..) and in the reference. Its output is
    compared relative to its own norm (a wide router: the top 4 carry most
    of the mass, so the block is no rounding beside the stream)."""
    _, params = whole
    rng = np.random.default_rng(7)
    m = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.float32)
    layer = 2
    moe = jax.tree.map(lambda w: w[layer], params["layers"]["moe"])
    uncut = np.asarray(reference.routed_block(m, params["layers"]["moe"],
                                              layer, CONFIG))
    weights, chosen, p = reference.route(
        m, moe["router"], moe["router_bias"], top_k=4, factor=6.0)
    # the top 4 carry most of the mass, and the bias changes some picks
    assert float(jnp.mean(jnp.sum(weights, -1))) / 6.0 > 0.5
    plain = np.asarray(jax.lax.top_k(p, 4)[1]).reshape(-1, 4)
    changed = np.mean([len(set(c) - set(q)) / 4 for c, q in
                       zip(np.asarray(chosen).reshape(-1, 4), plain)])
    assert 0.05 < changed < 0.5, changed
    identity = np.asarray(reference.identity_part(m, weights, CONFIG))
    assert np.linalg.norm(identity) > 0.05 * np.linalg.norm(uncut)
    assert np.linalg.norm(uncut - identity) > 0.05 * np.linalg.norm(uncut)
    total_prog, total_ref = -3 * identity, -3 * identity
    for first in range(0, EXPERTS, 4):
        cfg = tiny_longcat(4, first).cfg
        share = {n: w[first:first + 4] if n in L.EXPERT_MATRICES else w
                 for n, w in moe.items()}
        out, _ = L.apply_moe_grouped(share, m, cfg)
        total_prog = total_prog + np.asarray(out)
        stacked = jax.tree.map(lambda w: w[None], share)
        total_ref = total_ref + np.asarray(reference.routed_block(
            m, stacked, 0, CONFIG, first=first))
    scale = np.linalg.norm(uncut)
    assert np.linalg.norm(total_ref - uncut) / scale < 1e-5
    assert np.linalg.norm(total_prog - uncut) / scale < 1e-5
    # and the program's uncut block is the reference's
    out, _ = L.apply_moe_grouped(moe, m, tiny_longcat().cfg)
    assert np.linalg.norm(np.asarray(out) - uncut) / scale < 1e-5


def test_absorbed_attention_equals_the_expanded(whole, reference):
    """The program's absorbed form (W_UK folded into the query, W_UV into
    the output, scores and values over the one cached row) against the
    reference's expanded attention, to 1e-5 of its scale."""
    model, params = whole
    cfg = model.cfg
    attn = jax.tree.map(lambda w: w[1, 0], params["layers"]["attn"])
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 48, 64)), jnp.float32)
    want = np.asarray(reference.mla(attn, x, CONFIG))
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    q, row = L.mla_query_and_row(attn, x, pos, cfg, model._inv_freq)
    assert q.shape == (1, 48, 4, 128) and row.shape == (1, 48, 1, 128)
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert not np.asarray(row[..., used:]).any()
    s = jnp.einsum("bqhd,bkd->bhqk", q, row[:, :, 0]) * (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = jnp.where(pos[0][None, :] <= pos[0][:, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(s, -1),
                   row[:, :, 0, :cfg.kv_lora_rank])
    got = np.asarray(L.mla_output(attn, o, cfg))
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 1e-5


def _shortcut_before_the_second_attention(reference):
    def layer(x, layers, i, config, routing=None):
        eps = float(config["rms_norm_eps"])

        def attn(j):
            return jax.tree.map(lambda w: w[i, j], layers["attn"])

        def norm(name, j, h):
            return reference._rms_norm(h, layers[name]["scale"][i, j], eps)

        h1 = x + reference.mla(attn(0), norm("norm1", 0, x), config)
        m = norm("norm2", 0, h1)
        h2 = reference.add_dense(h1, layers["mlp"], (i, 0), m) \
            + reference.routed_block(m, layers["moe"], i, config)
        h3 = h2 + reference.mla(attn(1), norm("norm1", 1, h2), config)
        return reference.add_dense(h3, layers["mlp"], (i, 1),
                                   norm("norm2", 1, h3))
    return layer


FAULTS = ["no-s_kv", "no-rope-on-the-shared-key", "bias-in-the-weights",
          "renormalised-gate", "shortcut-joins-before-the-second-attention",
          "identity-part-dropped", "value-from-lanes-past-the-latent"]


@pytest.mark.parametrize("fault", FAULTS)
def test_tolerance_catches(monkeypatch, whole, reference, fault):
    """Each fault, planted in the program or in the reference, moves the
    compared logits by far more than the tolerance."""
    model, params = whole
    kw = {}
    if fault == "no-s_kv":
        s_q = L.mla_scales(model.cfg)[0]
        monkeypatch.setattr(L, "mla_scales", lambda cfg: (s_q, 1.0))
    elif fault == "no-rope-on-the-shared-key":
        rope = L.apply_rope
        monkeypatch.setattr(L, "apply_rope", lambda x, *a, **k: x
                            if x.shape[2] == 1 else rope(x, *a, **k))
    elif fault == "bias-in-the-weights":
        sound = sharded_moe.topk_gating_grouped

        def biased(logits, k=2, normalize=True, bias=None, scale=1.0,
                   score="softmax", eps=1e-20):
            idx, _, aux = sound(logits, k, normalize, bias, scale, score)
            gates = jax.nn.softmax(logits, -1) + bias[None]
            return idx, scale * jnp.take_along_axis(gates, idx, -1), aux
        monkeypatch.setattr(sharded_moe, "topk_gating_grouped", biased)
    elif fault == "renormalised-gate":
        kw["moe_norm_topk"] = True
    elif fault == "shortcut-joins-before-the-second-attention":
        monkeypatch.setattr(reference, "layer",
                            _shortcut_before_the_second_attention(reference))
    elif fault == "identity-part-dropped":
        monkeypatch.setattr(reference, "identity_part",
                            lambda m, weights, config: 0.0 * m)
    else:
        sound = model_runner._paged_attention
        rkv = model.cfg.kv_lora_rank

        def shifted(q, kpages, vpages, positions, cfg, *, chunk_k, chunk_v,
                    **rest):
            return sound(q, kpages, kpages[..., 2:2 + rkv], positions, cfg,
                         chunk_k=chunk_k, chunk_v=chunk_k[..., 2:2 + rkv],
                         **rest)
        monkeypatch.setattr(model_runner, "_paged_attention", shifted)
    e = engine(tiny_longcat(**kw), params)
    gap = worst_gap(e, params, reference, sequences(short=True))
    assert gap > FAULT_FLOOR, (fault, gap)


def test_sound_program_passes_where_the_faults_fail(whole, reference):
    """``test_tolerance_catches``'s own walk, with nothing planted."""
    model, params = whole
    gap = worst_gap(engine(model, params), params, reference,
                    sequences(short=True))
    assert gap < LOGIT_TOL, gap


@pytest.mark.parametrize("what,kw", [
    ("tp", dict(tp=2)), ("int8", dict(kv_dtype="int8")),
    ("prefix_cache", dict(prefix_cache=True)),
    ("swap", dict(kv_swap_dir="/tmp/longcat-swap")),
    ("handoff", dict(role="prefill"))])
def test_refused_loudly_for_a_latent_cache(whole, what, kw):
    model, params = whole
    with pytest.raises(NotImplementedError, match="latent cache"):
        InferenceEngineV2(
            model, RaggedInferenceEngineConfig(dtype="float32", **SHAPE, **kw),
            params=params, max_seq_len=256)


def test_a_draft_and_the_train_forward_are_refused(whole):
    model, params = whole
    e = engine(model, params)
    with pytest.raises(NotImplementedError, match="latent rows"):
        e._one_kind_only("attach_draft")
    with pytest.raises(NotImplementedError, match="paged serving path"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))


def test_serve_counts_selections_and_drains_clean(whole, reference):
    """The serve loop end to end: greedy tokens whose reference logit is the
    reference's own maximum (to the tolerance), the stat vector's new lanes
    against the host's arithmetic, the gauges by bytes, and a clean pool."""
    model, params = whole
    params = share_of(params, 0, 4)
    e = engine(tiny_longcat(4), params)
    rng = np.random.default_rng(3)
    prompts = {u: rng.integers(0, 256, n).tolist()
               for u, n in ((0, 90), (1, 17), (2, 130))}
    arrivals = [[(u, p, 6) for u, p in prompts.items()]]
    out = dict(e.serve(iter(arrivals)))
    assert set(out) == set(prompts)
    for u, toks in out.items():
        ids = prompts[u] + list(toks[:-1])
        rows = np.arange(len(prompts[u]) - 1, len(ids))
        want = reference.logits_rows(params, ids, rows, CONFIG)
        gaps = want.max(-1) - want[np.arange(len(toks)), np.asarray(toks)]
        assert gaps.max() < LOGIT_TOL, (u, gaps)
    c = e.telemetry.counters
    live = c["prefill_tokens"] + c["target_forwards"]
    assert live == sum(map(len, prompts.values())) + 3 * 5
    assert c["expert_selections"] == live * CONFIG["moe_topk"] * LAYERS
    assert c["expert_rows"] + c["zero_expert_selections"] \
        + c["absent_expert_selections"] == c["expert_selections"]
    assert c["absent_expert_selections"] > c["expert_rows"] > 0
    assert c["latent_positions_read_wide"] > 0
    assert c["latent_pairs_wide"] + c["latent_pairs_narrow"] == \
        2 * LAYERS * (c["attn_pairs_wide"] + c["attn_pairs_narrow"])
    # a row of 20 values in 128 lanes of float32, 8 attention layers
    assert e.kv.block_bytes == 8 * PAGE * 128 * 4
    assert c["kv_bytes_in_use_sum"] == \
        c["context_tokens_reserved_sum"] * 8 * 128 * 4
    assert e.kv.free_blocks == e.kv.num_blocks - 1 and not e.state.seqs
    text = e.telemetry.render_prometheus()
    for name in ("latent_positions_read_wide", "latent_pairs_narrow",
                 "expert_selections", "zero_expert_selections",
                 "absent_expert_selections", "kv_bytes_in_use"):
        assert f"ds_serving_{name}" in text, name
    assert 'ds_serving_kv_blocks_in_use{kind="latent"}' in text


def test_reference_against_transformers(whole, reference):
    """The plain reference against ``transformers``' LongcatFlash at the
    same small size, where it has the family: every expert held (it knows
    no share), the program's weights under its names."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import LongcatFlashConfig, LongcatFlashForCausalLM
    except ImportError:
        pytest.skip("transformers has no LongcatFlash")
    _, params = whole
    hf_config = LongcatFlashConfig(
        vocab_size=256, hidden_size=64, num_layers=LAYERS,
        num_hidden_layers=2 * LAYERS, num_attention_heads=4,
        ffn_hidden_size=96, expert_ffn_hidden_size=32, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, head_dim=4,
        v_head_dim=8, moe_topk=4, n_routed_experts=EXPERTS,
        zero_expert_num=8, routed_scaling_factor=6.0, rope_theta=1e7,
        rms_norm_eps=1e-5, max_position_embeddings=256,
        attn_implementation="eager")
    hf = LongcatFlashForCausalLM(hf_config).eval()

    def t(w):
        return torch.tensor(np.asarray(w, np.float32))

    lay = params["layers"]
    sd = {"model.embed_tokens.weight": t(params["embed"]["tok"]),
          "model.norm.weight": t(params["final_norm"]["scale"]),
          "lm_head.weight": t(params["embed"]["lm_head"]).T}
    for i in range(LAYERS):
        pre = f"model.layers.{i}."
        for j in (0, 1):
            a = jax.tree.map(lambda w: w[i, j], lay["attn"])
            at = f"{pre}self_attn.{j}."
            sd[at + "q_a_proj.weight"] = t(a["wq_a"]).T
            sd[at + "q_a_layernorm.weight"] = t(a["q_norm"]["scale"])
            sd[at + "q_b_proj.weight"] = t(a["wq_b"]).reshape(24, -1).T
            sd[at + "kv_a_proj_with_mqa.weight"] = t(a["wkv_a"]).T
            sd[at + "kv_a_layernorm.weight"] = t(a["kv_norm"]["scale"])
            sd[at + "kv_b_proj.weight"] = t(a["wkv_b"]).reshape(16, -1).T
            sd[at + "o_proj.weight"] = t(a["wo"]).reshape(-1, 64).T
            for ours, theirs in (("wi_gate", "gate_proj"), ("wi_up", "up_proj"),
                                 ("wo", "down_proj")):
                sd[f"{pre}mlps.{j}.{theirs}.weight"] = t(lay["mlp"][ours][i, j]).T
            sd[f"{pre}input_layernorm.{j}.weight"] = t(lay["norm1"]["scale"][i, j])
            sd[f"{pre}post_attention_layernorm.{j}.weight"] = \
                t(lay["norm2"]["scale"][i, j])
        sd[pre + "mlp.router.classifier.weight"] = t(lay["moe"]["router"][i]).T
        sd[pre + "mlp.router.e_score_correction_bias"] = \
            t(lay["moe"]["router_bias"][i])
        for x in range(EXPERTS):
            for ours, theirs in (("wi_gate", "gate_proj"), ("wi_up", "up_proj"),
                                 ("wo", "down_proj")):
                sd[f"{pre}mlp.experts.{x}.{theirs}.weight"] = \
                    t(lay["moe"][ours][i, x]).T
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not unexpected and not [m for m in missing if "rotary" not in m], (
        missing, unexpected)
    # and back: the checkpoint container gives the program's tree
    from deepspeed_tpu.inference.v2.model_implementations.archs import \
        resolve_container
    container = resolve_container(hf_config)
    assert container.__name__ == "LongcatFlashContainer"
    cfg = container.config(hf_config)
    assert cfg.replace(dtype="float32", max_seq_len=256,
                       moe_router_experts=EXPERTS) == tiny_longcat().cfg
    back = container.build_params(hf.state_dict(), cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        got = back
        for key in path:
            got = got[key.key]
        np.testing.assert_allclose(got, np.asarray(leaf), rtol=0, atol=1e-7,
                                   err_msg=str(path))
    ids = np.random.default_rng(9).integers(0, 256, 50)
    with torch.no_grad():
        want = hf(torch.tensor(ids[None])).logits[0].numpy()
    got = reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
    # another library's float32 products: measured 7e-4 on logits up to 4;
    # every planted fault above reads over 0.02
    assert np.abs(want).max() > 2.0
    assert np.abs(got - want).max() < 2e-3, np.abs(got - want).max()


def test_planned_frames_emit_the_tokens_of_whole_frames(
        whole, planned_against_whole):
    """A latent pool (``vpool`` None in the carry) in a frame whose step
    count is an operand: a one-chunk prompt's wide frame runs one of its two
    steps, a three-chunk prompt's both, and every request's tokens are
    those of whole frames."""
    model, params = whole
    e = engine(tiny_longcat(4), share_of(params, 0, 4))
    rng = np.random.default_rng(4)
    reqs = [(u, rng.integers(0, 256, n).tolist(), limit)
            for u, n, limit in ((0, 17, 7), (1, 130, 4), (2, 60, 6))]
    planned, hist = planned_against_whole(
        e, lambda: iter([[r] for r in reqs]))
    assert {u: len(t) for u, t in planned.items()} == {0: 7, 1: 4, 2: 6}
    assert set(hist) == {1, 2}
    assert e.kv.free_blocks == e.kv.num_blocks - 1 and not e.state.seqs
