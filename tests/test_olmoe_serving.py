"""OLMoE-1B-7B on the paged serving path, against its plain reference.

The preset (``models/config.py`` ``olmoe-1b-7b``) is served dropless: the
paged runner routes every live position to its top-k experts through
``apply_moe_grouped`` and no dead position (an idle slot, a packed buffer's
padding) reaches an expert. The reference is the benchmark's
(``perfbench/configs/olmoe_reference.py``: float32, every expert computed
for every token and weighted by the top-k mask), which shares no code with
the program. Sizes here are small; the widths' shape is OLMoE's (MHA, one
RMSNorm over the whole q / k projection, softmax over all experts then
top-k, weights not renormalized).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_model, get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (rows grouped by expert
#: against every expert dense and masked; pages against one softmax):
#: measured 3.5e-6 at most over every compared row, on logits up to 2.7.
#: Computing in bfloat16 reads 0.78 (a near-tie in the top-k flips an
#: expert) and a renormalized top-k 1.06 (``test_tolerance_catches``): both
#: are four orders of magnitude outside.
LOGIT_TOL = 1e-4

#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 4, "intermediate_size": 32,
          "vocab_size": 256, "num_experts": 16, "num_experts_per_tok": 4,
          "norm_topk_prob": False, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-5}
#: 8 slots x 64 positions packs (rungs 16, 144, 272, 512); 8 x 1 does not
SHAPE = dict(max_ragged_batch_size=8, prefill_chunk_size=64, kv_block_size=16,
             max_tokens_per_step=512, frame_steps=2)
SLOTS, WIDTH, PAGE = 8, 64, 16


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs", "olmoe_reference.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_olmoe(dtype="float32"):
    cfg = get_config(
        "olmoe-1b-7b", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"],
        num_layers=CONFIG["num_hidden_layers"],
        num_heads=CONFIG["num_attention_heads"],
        num_kv_heads=CONFIG["num_key_value_heads"],
        intermediate_size=CONFIG["intermediate_size"],
        num_experts=CONFIG["num_experts"],
        num_experts_per_tok=CONFIG["num_experts_per_tok"], max_seq_len=256,
        dtype=dtype)
    return build_model(cfg)


@pytest.fixture(scope="module")
def model_params():
    """Seeded float32 weights, the layers' matrices scaled up from their
    initial 0.02 so that attention, routing and the experts all move the
    logits (at the initial scale a layer adds a thousandth to the residual
    and any routing would pass)."""
    model = tiny_olmoe()
    params = model.init(jax.random.PRNGKey(26))
    layers = params["layers"]
    layers["attn"] = {n: w if n.endswith("_norm") else w * 4.0
                      for n, w in layers["attn"].items()}
    layers["mlp"] = {n: w * (10.0 if n == "router" else 8.0)
                     for n, w in layers["mlp"].items()}
    return model, params


def engine(model, params, dtype="float32"):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype=dtype, **SHAPE),
        params=params, max_seq_len=256)


@pytest.fixture(scope="module")
def eng(model_params):
    return engine(*model_params)


def sequences():
    """Two requests of different lengths: (prompt + forced continuation)."""
    rng = np.random.default_rng(126)
    return {0: (rng.integers(0, 256, 100 + 6).astype(np.int32), 100),
            3: (rng.integers(0, 256, 37 + 8).astype(np.int32), 37)}


def paged_steps(e, params, seqs, garbage_seed):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a frame does: prompts in chunks of ``WIDTH`` beside each other,
    then one position a step through the paged cache, the other slots idle
    with ``garbage_seed``'s ids under position -1. Yields per step (logits
    (slots, V), {slot: position of its last token}, moe work (3,), live
    tokens)."""
    rng = np.random.default_rng(garbage_seed)
    tables = np.zeros((SLOTS, 256 // PAGE), np.int32)
    for i, slot in enumerate(seqs):
        tables[slot] = 1 + i * tables.shape[1] + np.arange(tables.shape[1])
    pools = (jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v))
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
        logits, k, v, work = fwd(params, ids, positions, tables, valid, *pools)
        pools = (k, v)
        yield (np.asarray(logits),
               {s: done[s] - 1 for s in seqs if valid[s]},
               np.asarray(work), int(valid.sum()))


@pytest.fixture(scope="module")
def walked(eng, model_params):
    return list(paged_steps(eng, model_params[1], sequences(), 1))


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_paged_logits_match_reference(walked, model_params, reference, phase):
    """Prefill in chunks, then decode through the paged cache, two requests
    of different lengths beside six idle slots: every step's last-token
    logits against the reference's full forward pass."""
    seqs = sequences()
    ref = {s: reference.logits_rows(model_params[1], ids, np.arange(len(ids)),
                                    CONFIG) for s, (ids, _) in seqs.items()}
    compared, worst = 0, 0.0
    for logits, last, _, live in walked:
        if (phase == "prefill") != (live > len(last)):
            continue
        for slot, pos in last.items():
            worst = max(worst, float(np.abs(logits[slot] - ref[slot][pos]).max()))
            compared += 1
    assert compared >= 4
    assert worst <= LOGIT_TOL, worst


@pytest.mark.parametrize("wrong", ["bfloat16", "renormalized-top-k"])
def test_tolerance_catches(walked, model_params, reference, wrong):
    """The tolerance is tight: the served path computed in bfloat16, and
    the right path against a reference that renormalizes its top-k weights
    (Mixtral's convention, not OLMoE's), both land far outside it."""
    params = model_params[1]
    seqs = {0: sequences()[0]}
    config = dict(CONFIG)
    steps = walked
    if wrong == "bfloat16":
        steps = paged_steps(engine(tiny_olmoe("bfloat16"), params, "bfloat16"),
                            params, seqs, 1)
    else:
        config["norm_topk_prob"] = True
    ids = seqs[0][0]
    ref = reference.logits_rows(params, ids, np.arange(len(ids)), config)
    worst = max(float(np.abs(logits[0] - ref[last[0]]).max())
                for logits, last, _, _ in steps if 0 in last)
    assert worst > 20 * LOGIT_TOL, worst


@pytest.mark.parametrize("garbage_seed", [2, 3])
def test_dead_positions_reach_no_expert(walked, eng, model_params,
                                        garbage_seed):
    """Other garbage in the dead positions (idle slots, a chunk's tail, a
    rung's padding) leaves every live row's logits bit-identical, at the
    packed width and the narrow one, and the experts' rows are the live
    tokens x k x layers: no dead position took a row."""
    again = list(paged_steps(eng, model_params[1], sequences(), garbage_seed))
    k, layers = CONFIG["num_experts_per_tok"], CONFIG["num_hidden_layers"]
    widths = set()
    for (a, last, work, live), (b, _, work_b, _) in zip(walked, again):
        for slot in last:
            assert np.array_equal(a[slot], b[slot])
        assert work[0] == work_b[0] == live * k * layers
        # experts touched and the largest group, bounded by what is live
        assert layers <= work[1] <= min(CONFIG["num_experts"], live * k) * layers
        assert -(-live * k // CONFIG["num_experts"]) * layers <= work[2] \
            <= live * layers
        widths.add(live > len(last))
    assert widths == {True, False}


def test_gating_is_softmax_then_top_k(model_params, reference):
    """``topk_gating_grouped`` on the reference's own router logits picks
    the reference's experts with the reference's weights: softmax over all
    experts, then the k largest, not renormalized. Near-ties that flip a
    choice are counted, not hidden: none at float32 on these weights."""
    from deepspeed_tpu.moe.sharded_moe import topk_gating_grouped
    routing = []
    ids = sequences()[0][0]
    reference.logits_rows(model_params[1], ids, [len(ids) - 1], CONFIG,
                          routing)
    assert len(routing) == CONFIG["num_hidden_layers"]
    flips = 0
    for chosen, logits in routing:
        chosen, logits = np.asarray(chosen[0]), logits[0]
        idx, w, _ = topk_gating_grouped(logits, k=4, normalize=False)
        p = np.asarray(jax.nn.softmax(logits, axis=-1))
        for t in range(len(ids)):
            if set(np.asarray(idx[t])) != set(chosen[t]):
                flips += 1
                continue
            np.testing.assert_allclose(np.asarray(w[t]),
                                       p[t][np.asarray(idx[t])], rtol=1e-6)
            assert w[t].sum() < 0.99       # raw softmax mass, not 1
    assert flips == 0


def test_served_requests_follow_reference(model_params, reference):
    """Through ``InferenceEngineV2.serve``: each generated token's
    reference logit against the reference's maximum at that position
    (teacher-forced, as the benchmark's check), the in-graph expert
    counters against the tokens the frames forwarded, and the export."""
    model, params = model_params
    e = engine(model, params)
    prompts = {u: ids[:plen] for u, (ids, plen) in sequences().items()}
    outs = dict(e.serve(iter([list(prompts.items())]), max_new_tokens=10))
    assert e.kv.free_blocks == e.kv.num_blocks - 1
    for u, generated in outs.items():
        generated = np.asarray(generated)
        assert len(generated) == 10
        ids = np.concatenate([prompts[u], generated[:-1]])
        rows = np.arange(len(prompts[u]) - 1, len(ids))
        logits = reference.logits_rows(params, ids, rows, CONFIG)
        gaps = logits.max(-1) - logits[np.arange(10), generated]
        assert gaps.max() <= LOGIT_TOL, gaps
    c = e.telemetry.counters
    forwarded = c["prefill_tokens"] + c["target_forwards"]
    assert forwarded == 137 + 2 * 9
    assert c["expert_rows"] == forwarded * 4 * 2
    assert 0 < c["experts_touched"] <= c["expert_rows"]
    assert 0 < c["expert_rows_max"] <= forwarded * 2
    assert c["rung_steps"] > 0          # the wide frames packed
    text = e.telemetry.render_prometheus()
    for name in ("expert_rows", "experts_touched", "expert_rows_max"):
        assert f"ds_serving_{name}_total" in text


def test_dense_model_counts_no_expert_work():
    model = build_model("tiny")
    e = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype="float32", **SHAPE),
        params=model.init(jax.random.PRNGKey(0)), max_seq_len=128)
    dict(e.serve(iter([[(0, np.arange(40, dtype=np.int32))]]),
                 max_new_tokens=4))
    c = e.telemetry.counters
    assert c["expert_rows"] == c["experts_touched"] == c["expert_rows_max"] == 0
    assert c["positions_computed"] > 0


def test_expert_lanes_ride_only_a_model_that_routes(model_params):
    """The routed experts' lanes lie behind the rung lanes of a model with
    routed experts. A dense model's stat vector is what it was without
    them and its forward hands the serving loops nothing to put there: its
    frame programs keep the HLO they had (mistral-7b's, compiled for the
    chip, equal the parent's but for source locations: PERF.md, PR 26)."""
    from deepspeed_tpu.inference.v2 import telemetry as T
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    assert T.STAT_EXPERT_ROWS == T.N_STATS == T.STAT_RUNG0 + T.MAX_RUNGS
    dense = build_model("tiny")
    for model, params, lanes in (
            (dense, dense.init(jax.random.PRNGKey(0)), 0),
            (*model_params,
             len(T.MOE_STAT_NAMES) + len(T.MOVED_STAT_NAMES))):
        runner = PagedModelRunner(model, 16, 8)
        assert runner.n_stats == T.N_STATS + lanes
        cfg = runner.cfg
        ids = jnp.zeros((2, 4), jnp.int32)
        pool = jnp.zeros((cfg.num_layers, cfg.kv_heads, 4, 16,
                          cfg.dims_per_head), jnp.float32)
        work = runner._forward(params, ids, ids, ids[:, :2],
                               jnp.ones((2,), jnp.int32), pool, pool,
                               moe_work=True)[3]
        assert (work is None) if not lanes else work.shape == (lanes,)


PRESET = {"vocab_size": 50304, "hidden_size": 2048, "num_layers": 16,
          "num_heads": 16, "kv_heads": 16, "dims_per_head": 128,
          "ffn_size": 1024, "moe_ffn_size": 1024, "num_experts": 64,
          "num_experts_per_tok": 8, "moe_norm_topk": False,
          "moe_shared_expert_size": 0, "moe_impl": "grouped",
          "qk_norm": "full", "norm": "rmsnorm", "norm_eps": 1e-5,
          "activation": "swiglu", "rope_theta": 10000.0, "rotary_pct": 1.0,
          "rope_interleaved": False, "tie_embeddings": False,
          "max_seq_len": 4096, "sliding_window": None, "use_bias": False}


@pytest.mark.parametrize("field", sorted(PRESET))
def test_preset_matches_the_catalog_row(field):
    """allenai/OLMoE-1B-7B-0125-Instruct config.json, as the model-configs
    catalog has it."""
    assert getattr(get_config("olmoe-1b-7b"), field) == PRESET[field]


def test_preset_parameter_count():
    """6.92 B in all: 419.6 M a layer (16.8 M attention, 402.7 M experts,
    0.13 M router), two embeddings of 103.0 M."""
    model = build_model("olmoe-1b-7b")
    shapes = model.abstract_params()
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    mlp = shapes["layers"]["mlp"]
    experts = sum(int(np.prod(mlp[n].shape[1:]))
                  for n in ("wi_gate", "wi_up", "wo"))
    assert experts == 64 * 3 * 2048 * 1024 == 402_653_184
    assert int(np.prod(mlp["router"].shape[1:])) == 131_072
    assert shapes["layers"]["attn"]["q_norm"]["scale"].shape == (16, 2048)
    assert "bias" not in shapes["layers"]["attn"]["q_norm"]
    assert total == 6_919_161_856

