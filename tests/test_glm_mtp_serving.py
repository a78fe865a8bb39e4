"""GLM-4.7-Flash on the paged serving path with its prediction module as
the draft, against its plain reference.

The preset (``models/config.py`` ``glm-4.7-flash``) is served through
latent pages: one leading dense layer, routed layers with a sigmoid router
beside an ungated shared expert, and ONE multi-token-prediction module
whose layer keeps its rows in one more layer of the same pool and drafts a
token a step for the stack to verify two positions wide. The reference is
the benchmark's (``perfbench/configs/glm4_moe_lite_reference.py``: float32,
the EXPANDED attention, every expert computed for every token, no
speculation), which shares no code with the program. Sizes here are small;
the shape is GLM-4.7-Flash's.
"""

import functools
import importlib.util
import os
import types

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
#: masked, pages against one softmax): measured 3e-5 at most over every
#: compared row, on logits up to 6. A wrong position, page, mask, expert or
#: hidden state moves logits by 0.02 and more (``test_tolerance_catches``).
LOGIT_TOL = 1e-4
FAULT_FLOOR = 0.02

#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_attention_heads": 5, "q_lora_rank": 24,
          "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
          "v_head_dim": 16, "num_experts_per_tok": 4, "n_routed_experts": 16,
          "routed_scaling_factor": 1.8, "norm_topk_prob": True,
          "rope_theta": 1e6, "rms_norm_eps": 1e-5, "vocab_size": 256}
LAYERS = 3          # one dense, two routed
SHAPE = dict(max_ragged_batch_size=4, prefill_chunk_size=16, kv_block_size=8,
             frame_steps=4)
SLOTS, WIDTH, PAGE = 4, 16, 8


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs",
                        "glm4_moe_lite_reference.py")
    spec = importlib.util.spec_from_file_location("glm_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the benchmark's blocks are sized for 4k tokens and the whole vocabulary
    mod.TOKEN_BLOCK, mod.Q_BLOCK, mod.WIDTH_BLOCK = 32, 16, 32
    mod.VOCAB_BLOCK, mod.ROW_BLOCK = 96, 8
    # the plain logits: what float32 against float32 compares. The
    # benchmark's default holds a row to the nearest of its candidate
    # routings (``test_a_near_tie_row_is_held_to_its_nearest_candidate``)
    mod.tie_aware_rows = mod.logits_rows
    mod.logits_rows = functools.partial(mod.logits_rows, tie_margin=0.0)
    return mod


def tiny_glm(**kw):
    cfg = get_config(
        "glm-4.7-flash", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"], num_layers=LAYERS,
        num_heads=CONFIG["num_attention_heads"], intermediate_size=96,
        moe_intermediate_size=32, moe_shared_expert_size=32,
        num_experts=CONFIG["n_routed_experts"],
        q_lora_rank=CONFIG["q_lora_rank"],
        kv_lora_rank=CONFIG["kv_lora_rank"],
        qk_nope_head_dim=CONFIG["qk_nope_head_dim"],
        qk_rope_head_dim=CONFIG["qk_rope_head_dim"],
        v_head_dim=CONFIG["v_head_dim"], max_seq_len=256, dtype="float32",
        **kw)
    return build_model(cfg)


def scaled(layer):
    """A layer's matrices scaled up from their initial 0.02 so that
    attention, routing, the experts and the shared expert all move the
    logits; the router 10 x wider (sigmoid scores then spread over 0.2 ..
    0.8) and its bias as drawn."""
    out = dict(layer)
    out["attn"] = {n: w if n.endswith("_norm") else w * 4.0
                   for n, w in layer["attn"].items()}
    out["mlp"] = {n: w * {"router": 10.0, "router_bias": 1.0}.get(n, 6.0)
                  for n, w in layer["mlp"].items()}
    return out


@pytest.fixture(scope="module")
def glm():
    model = tiny_glm()
    params = model.init(jax.random.PRNGKey(39))
    params["layers"] = {g: scaled(t) for g, t in params["layers"].items()}
    params["mtp"] = {**params["mtp"], "layer": scaled(params["mtp"]["layer"]),
                     "eh_proj": params["mtp"]["eh_proj"] * 6.0}
    return model, params


def engine(model, params, **kw):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype="float32", **{**SHAPE, **kw}),
        params=params, max_seq_len=256)


def tables_for(slots):
    tables = np.zeros((SLOTS, 256 // PAGE), np.int32)
    for i, slot in enumerate(slots):
        tables[slot] = 1 + i * tables.shape[1] + np.arange(tables.shape[1])
    return tables


def walk(e, params, seqs, two_wide=True):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a self-speculative frame does: prompts in chunks of ``WIDTH``,
    the module's rows beside them one position back, then steps that draft
    with the module at ``cached - 1``, run the stack TWO positions wide over
    the true next tokens (a verify whose draft is accepted) and commit the
    module's two rows. Yields per step ({slot: [(position, stack logits)]},
    {slot: (position, module logits)})."""
    r = e.runner
    tables = tables_for(seqs)
    pool = jnp.zeros_like(e.kv.k)
    assert e.kv.v is None and pool.shape[0] == LAYERS + 1
    e_dim = CONFIG["hidden_size"]
    hidden = np.zeros((SLOTS, e_dim), np.float32)
    done = {slot: 0 for slot in seqs}

    def module(ids, pos, hid, valid, pool, rows_only):
        return r._forward(params, ids, pos, tables, valid, pool, None,
                          mtp=(jnp.asarray(hid), rows_only))

    def commit(pool, rows, pos):
        return model_runner._page_commit(pool)(
            pool, None, rows, None, block_tables=jnp.asarray(tables),
            positions=jnp.asarray(pos), layer0=LAYERS)[0]

    while any(done[s] < len(ids) - 1 for s, (ids, _) in seqs.items()):
        prefilling = any(done[s] < plen for s, (_, plen) in seqs.items())
        width = WIDTH if prefilling else (2 if two_wide else 1)
        ids = np.zeros((SLOTS, width), np.int32)
        pos = np.full((SLOTS, width), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        for slot, (seq, plen) in seqs.items():
            at = done[slot]
            n = min(width, plen - at) if at < plen else \
                min(width, len(seq) - 1 - at)
            ids[slot, :n], pos[slot, :n] = seq[at:at + n], at + np.arange(n)
            valid[slot] = n
        stack, drafts = {}, {}
        if not prefilling:
            # draft: the module whole at cached - 1 over (hidden, last token)
            live = valid > 0
            pos_d = np.where(live, pos[:, 0] - 1, -1)[:, None].astype(np.int32)
            dlog, row_d, work = module(ids[:, :1], pos_d, hidden[:, None],
                                       live.astype(np.int32), pool, False)
            assert int(work[0]) == live.sum() * CONFIG["num_experts_per_tok"]
            drafts = {s: (int(pos_d[s, 0]), np.asarray(dlog)[s])
                      for s in seqs if live[s]}
        logits, pool, _, _, h = r._forward(
            params, ids, pos, tables, valid, pool, None, all_logits=True,
            moe_work=True, hidden=True)
        logits, h = np.asarray(logits), np.asarray(h)
        if prefilling:
            behind = np.concatenate([hidden[:, None], h[:, :-1]], axis=1)
            pos_m = np.where(pos > 0, pos - 1, -1).astype(np.int32)
            pool = commit(pool, module(ids, pos_m, behind, valid, pool, True),
                          pos_m)
        else:
            # heal: the module's row at cached from the verify's first
            # hidden state and the (accepted) second token
            second = np.where(valid > 1, pos[:, 0], -1)[:, None].astype(np.int32)
            row_h = module(ids[:, 1:2] if width > 1 else ids[:, :1], second,
                           h[:, :1], (valid > 1).astype(np.int32), pool, True)
            pool = commit(pool, jnp.concatenate([row_d, row_h], axis=2),
                          np.concatenate([pos_d, second], axis=1))
        for slot in seqs:
            n = valid[slot]
            if n:
                stack[slot] = [(int(pos[slot, j]), logits[slot, j])
                               for j in range(n)]
                hidden[slot] = h[slot, n - 1]
                done[slot] += n
        yield stack, drafts


def sequences():
    rng = np.random.default_rng(139)
    return {0: (rng.integers(0, 256, 37 + 9).astype(np.int32), 37),
            2: (rng.integers(0, 256, 33 + 12).astype(np.int32), 33)}


@pytest.mark.parametrize("kernels", [False, True], ids=["gather", "pallas"])
def test_served_path_matches_the_reference(monkeypatch, glm, reference,
                                           kernels):
    """Chunked prefill, then speculative decode (the module's draft at
    ``cached - 1``, a two-wide verify, the healed row) through the latent
    pages, across pages of 8: the stack's logits at every live position and
    the module's at every drafted one, against the reference's; on the
    gather path and through the latent kernels and the page commit
    (interpreted)."""
    if kernels:
        monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    model, params = glm
    e = engine(model, params)
    seqs = sequences()
    want = {s: reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
            for s, (ids, _) in seqs.items()}
    want_m = {s: reference.mtp_logits_rows(
        params, ids, np.arange(len(ids) - 1), CONFIG)
        for s, (ids, _) in seqs.items()}
    assert min(np.abs(w).max() for w in (*want.values(), *want_m.values())) > 2
    worst = worst_m = 0.0
    drafted = 0
    for stack, drafts in walk(e, params, seqs):
        for s, rows in stack.items():
            for at, logits in rows:
                worst = max(worst, np.abs(logits - want[s][at]).max())
        for s, (at, logits) in drafts.items():
            worst_m = max(worst_m, np.abs(logits - want_m[s][at]).max())
            drafted += 1
    assert drafted >= 8
    assert worst < LOGIT_TOL and worst_m < LOGIT_TOL, (worst, worst_m)


FAULTS = ["eh-proj-halves-swapped", "module-one-position-on",
          "no-shared-expert", "softmax-router"]


@pytest.mark.parametrize("fault", FAULTS)
def test_tolerance_catches(monkeypatch, glm, reference, fault):
    """A planted fault of the kind a wrong implementation makes moves some
    compared logit by ``FAULT_FLOOR`` or more: two hundred times the
    tolerance."""
    model, params = glm
    seqs = {1: sequences()[0]}
    if fault == "no-shared-expert":
        monkeypatch.setattr(L, "_apply_shared_expert",
                            lambda p, x, cfg: jnp.zeros_like(x))
    if fault == "softmax-router":
        model = tiny_glm(moe_router_score="softmax")
    e = engine(model, params)
    ids = seqs[1][0]
    want = reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
    want_m = reference.mtp_logits_rows(params, ids, np.arange(len(ids) - 1),
                                       CONFIG)
    served = params
    if fault == "eh-proj-halves-swapped":
        served = {**params, "mtp": {**params["mtp"], "eh_proj": jnp.roll(
            params["mtp"]["eh_proj"], CONFIG["hidden_size"], axis=1)}}
    if fault == "module-one-position-on":
        want_m = np.roll(want_m, -1, axis=0)
    worst = 0.0
    for stack, drafts in walk(e, served, seqs):
        for at, logits in stack.get(1, []):
            worst = max(worst, np.abs(logits - want[at]).max())
        for at, logits in (drafts[1],) if 1 in drafts else ():
            if at < len(want_m) - 1:
                worst = max(worst, np.abs(logits - want_m[at]).max())
    assert worst > FAULT_FLOOR, (fault, worst)


def serve(e, prompts, limits, **kw):
    arrivals = [[(u, p, limits[u]) for u, p in prompts.items()]]
    return {u: np.asarray(t) for u, t in e.serve(iter(arrivals), **kw)}


def prompts_of(lens, seed=3):
    rng = np.random.default_rng(seed)
    return {u: rng.integers(0, 256, n).tolist() for u, n in enumerate(lens)}


def test_greedy_equals_the_module_off_run_at_acceptance_zero(glm, reference):
    """With seeded weights the module's choice misses the stack's: every
    step drafts 1, verifies 2, emits 1 and rolls one position back, across
    page boundaries (pages of 8), more requests than slots. The tokens are
    the module-off run's, each the reference's own choice to the tolerance,
    and the counters say what happened."""
    model, params = glm
    prompts = prompts_of((5, 33, 16, 1, 40, 23))
    limits = {u: 9 + u for u in prompts}
    e = engine(model, params)
    assert e.self_draft and e.kv.k.shape[0] == LAYERS + 1
    spec = serve(e, prompts, limits)
    c = dict(e.telemetry.counters)
    plain = serve(engine(model, params), prompts, limits, speculate=False)
    assert set(spec) == set(prompts)
    for u, toks in spec.items():
        assert len(toks) == limits[u]
        np.testing.assert_array_equal(toks, plain[u])
        ids = prompts[u] + list(toks[:-1])
        rows = np.arange(len(prompts[u]) - 1, len(ids))
        want = reference.logits_rows(params, ids, rows, CONFIG)
        gaps = want.max(-1) - want[np.arange(len(toks)), toks]
        assert gaps.max() < LOGIT_TOL, (u, gaps)
    assert c["drafted_tokens"] == c["target_forwards"] > 0
    assert c["accepted_draft_tokens"] <= 0.1 * c["drafted_tokens"]
    assert c["mtp_expert_rows"] == 4 * c["drafted_tokens"]
    assert 0 < c["mtp_experts_touched"] <= c["mtp_expert_rows"]
    assert c["mtp_latent_positions_read"] > 0
    assert e.serve_stats["spec"]["gamma"] == 1
    assert e.kv.free_blocks == e.kv.num_blocks - 1 and not e.state.seqs
    text = e.telemetry.render_prometheus()
    for name in ("mtp_latent_positions_read", "mtp_expert_rows",
                 "mtp_experts_touched", "drafted_tokens",
                 "accepted_draft_tokens", "target_forwards"):
        assert f"ds_serving_{name}" in text, name


@pytest.fixture(scope="module")
def echo():
    """Weights under which the module is RIGHT by construction: every output
    projection zeroed (the stream stays the embedding, so the next token is
    a function of the last alone) and ``eh_proj`` = [I ; 0] (the module's
    stream is the next token's embedding): its choice is the stack's."""
    model, params = tiny_glm(), None
    params = model.init(jax.random.PRNGKey(7))

    def silent(layer):
        out = dict(layer)
        out["attn"] = {**layer["attn"], "wo": layer["attn"]["wo"] * 0}
        out["mlp"] = {n: w * 0 if n in ("wo", "shared_wo") else w
                      for n, w in layer["mlp"].items()}
        return out

    e_dim = CONFIG["hidden_size"]
    params["layers"] = {g: silent(t) for g, t in params["layers"].items()}
    params["mtp"] = {
        **params["mtp"], "layer": silent(params["mtp"]["layer"]),
        "eh_proj": jnp.concatenate(
            [jnp.eye(e_dim), jnp.zeros((e_dim, e_dim))])[None]}
    return model, params


def generate(e, prompts, limits, **kw):
    """``serve`` above as the closed batch ``generate()`` makes of it (one
    budget for all: generate() takes no limit a row)."""
    limit, = set(limits.values())
    return dict(enumerate(e.generate(list(prompts.values()),
                                     max_new_tokens=limit, **kw)))


@pytest.mark.parametrize("limit,run", [(8, serve), (9, serve), (8, generate)],
                         ids=["even", "odd", "generate"])
def test_acceptance_one_emits_two_tokens_a_step(echo, reference, limit, run):
    """Every draft accepted: two tokens a verify, half the verify forwards,
    the tokens still the module-off run's and the reference's; a budget
    that the second token of a step would overshoot is met exactly.
    ``generate()`` self-drafts by serve()'s default and counts the same."""
    model, params = echo
    # one chunk each and frames of one step: every row leaves its prefill
    # in the first frame, and every later token comes from a verify
    prompts = prompts_of((7, 15, 3), seed=5)
    limits = {u: limit for u in prompts}
    e = engine(model, params, frame_steps=1)
    spec = run(e, prompts, limits)
    c = e.telemetry.counters
    plain = run(engine(model, params), prompts, limits, speculate=False)
    for u, toks in spec.items():
        assert len(toks) == limit
        np.testing.assert_array_equal(toks, plain[u])
        want = reference.logits_rows(
            params, prompts[u] + list(toks[:-1]),
            np.arange(len(prompts[u]) - 1, len(prompts[u]) + limit - 1),
            CONFIG)
        np.testing.assert_array_equal(toks, want.argmax(-1))
    # the first token comes from the prefill; the verifies emit the rest
    decoded = 3 * (limit - 1)
    assert c["target_forwards"] + c["accepted_draft_tokens"] == decoded
    assert c["accepted_draft_tokens"] == 3 * ((limit - 1) // 2)
    assert e.serve_stats["spec"]["tokens_per_target_forward"] > 1.7


def test_later_logits_are_right_after_accepted_drafts(glm, reference):
    """``walk``'s verify steps take both positions (an accepted draft): the
    module's healed row and the stack's second row feed every later step,
    whose logits ``test_served_path_matches_the_reference`` compares. Here
    the same walk ONE position wide (every draft rejected, the healed row
    dead and overwritten) reaches the same logits."""
    model, params = glm
    seqs = {0: sequences()[0]}
    one = {at: logits for stack, _ in walk(engine(model, params), params,
                                           seqs, two_wide=False)
           for at, logits in stack[0]}
    two = {at: logits for stack, _ in walk(engine(model, params), params,
                                           seqs)
           for at, logits in stack[0]}
    assert set(one) == set(two) and len(one) == len(seqs[0][0]) - 1
    assert max(np.abs(one[at] - two[at]).max() for at in one) < LOGIT_TOL


def test_sampled_serving_keeps_lengths_and_counts(glm):
    """Temperature 1: rejection sampling against the module's distribution.
    Lengths are exact, every emitted token is a verify's or an accepted
    draft, some drafts are accepted and some are not, the pool drains."""
    model, params = glm
    prompts = prompts_of((12, 30, 4, 21), seed=11)
    limits = {u: 24 for u in prompts}
    e = engine(model, params)
    out = serve(e, prompts, limits, temperature=1.0, rng=5)
    c = e.telemetry.counters
    assert all(len(out[u]) == 24 and (out[u] >= 0).all() for u in prompts)
    assert c["tokens_emitted"] == 4 * 24
    # a row decoding beside a prefilling one rides the wide frame undrafted
    assert 4 * 12 < c["target_forwards"] + c["accepted_draft_tokens"] \
        <= c["tokens_emitted"] - 4
    assert 0 < c["accepted_draft_tokens"] < c["drafted_tokens"]
    assert e.kv.free_blocks == e.kv.num_blocks - 1


def test_candidate_sets_by_hand(reference):
    """The sets of 4 a perturbation under the margin could make the
    largest: min over the set + margin > max over the rest; the plain
    choice first."""
    v = np.array([
        [.9, .8, .7, .6, .5, .4, .3, .2, .1, .0],        # no near-tie
        [.9, .8, .7, .6, .595, .4, .3, .2, .1, .0],      # 4th ~ 5th
        [.9, .8, .7, .6, .595, .592, .3, .2, .1, .0],    # 4th ~ 5th ~ 6th
        [.9, .8, .605, .6, .598, .4, .3, .2, .1, .0],    # 3rd ~ 4th ~ 5th
        [.0, .1, .2, .3, .4, .592, .595, .6, .7, .8]])   # unsorted
    which, sets = reference.candidate_sets(v, 4, 0.01)
    got = [sorted(map(tuple, np.sort(sets[which == i], -1).tolist()))
           for i in range(len(v))]
    assert got[0] == [(0, 1, 2, 3)]
    assert got[1] == [(0, 1, 2, 3), (0, 1, 2, 4)]
    assert got[2] == [(0, 1, 2, 3), (0, 1, 2, 4), (0, 1, 2, 5)]
    assert got[3] == [(0, 1, 2, 3), (0, 1, 2, 4), (0, 1, 3, 4)]
    assert got[4] == [(5, 6, 8, 9), (5, 7, 8, 9), (6, 7, 8, 9)]
    first = [sets[which == i][0].tolist() for i in range(len(v))]
    assert sorted(first[2]) == [0, 1, 2, 3] and sorted(first[4]) == [6, 7, 8, 9]
    # no margin, even an exact tie: the plain choice alone
    which, sets = reference.candidate_sets(
        np.array([[.9, .8, .7, .6, .6, .1]]), 4, 0.0)
    assert sets.tolist() == [[0, 1, 2, 3]]


def test_a_near_tie_row_is_held_to_its_nearest_candidate(monkeypatch, glm,
                                                         reference):
    """The benchmark's default ``logits_rows``: every row is compared. A
    row comes back as the upper envelope of its candidate routings' logits,
    each relative to its own maximum: a row without a near-tie of the router
    is its plain logits (the walk of single tokens against the plain context
    is the plain forward again); a row served under the OTHER expert of a
    near-tie (what a bfloat16 system may do) reads a large gap plainly and
    none against its nearest candidate."""
    model, params = glm
    ids = sequences()[0][0]
    rows = np.arange(20, len(ids))
    plain = reference.logits_rows(params, ids, rows, CONFIG)
    relative = plain - plain.max(-1, keepdims=True)
    one = reference.tie_aware_rows(params, ids, rows, CONFIG,
                                   tie_margin=1e-12)
    assert np.abs(one - relative).max() < 1e-5
    # this small router's scores lie further apart than GLM's: a wider
    # margin meets as many near-ties
    margin = 0.02
    kind = reference.tie_aware_rows(params, ids, rows, CONFIG,
                                    tie_margin=margin)
    routing = []
    reference.logits_rows(params, ids, rows, CONFIG, routing)
    apart = []
    for (_, scores, margin_of), bias in zip(
            routing, params["layers"]["g1"]["mlp"]["router_bias"]):
        top = np.sort(np.asarray(scores[0] + bias), -1)[:, ::-1]
        apart.append(top[rows, 3] - top[rows, 4])
        np.testing.assert_allclose(np.asarray(margin_of[0])[rows], apart[-1],
                                   atol=1e-6)
    split = (np.stack(apart) < margin).any(0)
    assert 2 <= split.sum() < len(rows)
    assert (kind.max(-1) == 0).all() and (kind >= relative - 1e-5).all()
    assert np.abs(kind[~split] - relative[~split]).max() < 1e-5
    assert all(np.abs(kind[r] - relative[r]).max() > FAULT_FLOOR
               for r in np.nonzero(split)[0])
    # serve ONE split row with the marginal expert of its near-ties given up
    # for the next one (what a bfloat16 system may do): its plain gap is
    # large, its gap to the nearest candidate the float32 tolerance
    sound = sharded_moe.topk_gating_grouped
    swapping = []

    def other(logits, k=2, normalize=True, bias=None, scale=1.0,
              score="softmax", eps=1e-20):
        if not swapping:
            return sound(logits, k, normalize, bias, scale, score)
        top, order = jax.lax.top_k(jax.nn.sigmoid(logits) + bias[None], k + 1)
        swap = (top[:, k - 1] - top[:, k] < margin)[:, None]
        nudge = jnp.zeros_like(logits).at[
            jnp.arange(logits.shape[0]), order[:, k - 1]].set(-1.0)
        return sound(logits + jnp.where(swap, nudge, 0.0), k, normalize,
                     bias, scale, score)

    target = int(rows[split][len(rows[split]) // 2])
    monkeypatch.setattr(sharded_moe, "topk_gating_grouped", other)
    steps = walk(engine(model, params), params, {0: (ids, 20)},
                 two_wide=False)
    upcoming, served = 0, None
    while served is None:
        swapping[:] = [1] * (upcoming == target)
        stack, _ = next(steps)
        upcoming = stack[0][-1][0] + 1
        served = dict(stack[0]).get(target)
    tok, row = served.argmax(), target - rows[0]
    assert plain[row].max() - plain[row, tok] > FAULT_FLOOR
    assert -kind[row, tok] < LOGIT_TOL
    # a margin under which most sets are candidates compares nothing
    with pytest.raises(ValueError, match="candidate routings"):
        reference.tie_aware_rows(params, ids, rows, CONFIG, tie_margin=1.0)


def _low_mantissa(reference):
    """``_rms_norm`` with float8 e4m3's 3 bits of mantissa on every
    normalised activation: the nearest precision below bfloat16's."""
    sound = reference._rms_norm

    def low(x, scale, eps):
        bits = jax.lax.bitcast_convert_type(sound(x, scale, eps), jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    return low


@pytest.fixture(scope="module")
def served_tokens(glm, reference):
    """Greedy tokens of four prompts: the engine's, a planted fault's (no
    shared expert; a softmax router), and for one prompt the reference's own
    under float8's mantissa."""
    model, params = glm
    prompts = prompts_of((5, 33, 16, 40))
    limits = {u: 30 for u in prompts}
    out = {"sound": serve(engine(model, params), prompts, limits),
           "softmax-router": serve(
               engine(tiny_glm(moe_router_score="softmax"), params), prompts,
               limits)}
    with pytest.MonkeyPatch.context() as planted:
        planted.setattr(L, "_apply_shared_expert",
                        lambda p, x, cfg: jnp.zeros_like(x))
        out["no-shared-expert"] = serve(engine(model, params), prompts,
                                        limits)
        planted.undo()
        planted.setattr(reference, "_rms_norm", _low_mantissa(reference))
        jax.clear_caches()
        ids = list(prompts[1])
        for _ in range(limits[1]):
            ids.append(int(reference.logits_rows(
                params, ids, [len(ids) - 1], CONFIG)[0].argmax()))
    jax.clear_caches()
    out["float8"] = {1: ids[len(prompts[1]):]}
    return prompts, out


@pytest.mark.parametrize("margin", [None, 0.02], ids=["default", "wide"])
def test_the_harness_comparison_passes_sound_tokens_and_no_others(
        glm, reference, served_tokens, margin):
    """``perfbench.reference_check.check`` (the comparison that decides
    ``correct``, its 0.25 as it stands) over the reference's DEFAULT
    ``logits_rows``, candidates and all, and over a margin wide enough that
    many of these rows have several: served greedy tokens pass; a planted
    fault's do not, nor do the reference's own greedy tokens under float8's
    mantissa."""
    from perfbench import reference_check
    kw = {} if margin is None else {"tie_margin": margin}
    held = types.SimpleNamespace(logits_rows=functools.partial(
        reference.tie_aware_rows, **kw))
    prompts, served = served_tokens

    def check(out):
        ok, worst = reference_check.check(
            held, glm[1], CONFIG,
            [(str(u), prompts[u], list(map(int, out[u]))) for u in out])
        return ok, max(worst.values())

    ok, worst = check(served["sound"])
    assert ok and worst < LOGIT_TOL
    for fault in ("no-shared-expert", "softmax-router"):
        ok, worst = check(served[fault])
        assert not ok and worst > 1.0, fault
    ok, worst = check(served["float8"])
    assert not ok, worst


def test_a_layout_is_stated_once():
    """``moe_first_dense`` is a rule that holds at any depth (the preset cut
    to 3 layers keeps its leading dense layer); beside ``layer_types`` it
    would state the layout twice, and is refused."""
    cfg = get_config("glm-4.7-flash", num_layers=3)
    assert cfg.layer_tags == ("dense", "moe", "moe")
    with pytest.raises(AssertionError, match="both state the layout"):
        cfg.replace(layer_types=("dense", "moe", "moe")).layer_tags


def test_sigmoid_router_against_numpy():
    """Sigmoid scores, the top 4 of score + bias, weights the chosen scores
    over their sum x 1.8."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(32, 16)).astype(np.float32)
    bias = rng.normal(size=(16,)).astype(np.float32) * 0.3
    idx, w, _ = sharded_moe.topk_gating_grouped(
        jnp.asarray(logits), k=4, normalize=True, bias=jnp.asarray(bias),
        scale=1.8, score="sigmoid")
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    want_idx = np.argsort(-(s + bias), axis=-1)[:, :4]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    assert (np.sort(np.asarray(idx), -1)
            != np.sort(np.argsort(-s, axis=-1)[:, :4], -1)).any(), \
        "the bias changes no choice"
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), 1.8 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    with pytest.raises(AssertionError):
        sharded_moe.topk_gating_grouped(jnp.asarray(logits), score="tanh")


def test_routed_block_with_ungated_shared_expert(glm, reference):
    """``apply_moe_grouped`` over a layer's routed block against the
    reference's (every expert dense and masked, the shared expert added as
    it is); Qwen2-MoE's gate still weighs its shared expert."""
    model, params = glm
    mlp = jax.tree.map(lambda w: w[0], params["layers"]["g1"]["mlp"])
    assert "shared_gate" not in mlp and "router_bias" in mlp
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 64), jnp.float32)
    got, _ = L.apply_moe_grouped(mlp, x, model.cfg)
    routing = []
    want = reference.routed_block(x, mlp, CONFIG, routing)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    chosen, scores, _ = routing[0]
    unbiased = jax.lax.top_k(scores, 4)[1]
    changed = np.mean(np.sort(np.asarray(chosen), -1)
                      != np.sort(np.asarray(unbiased), -1))
    assert 0 < changed < 0.5, changed
    gated = get_config("tiny-moe", moe_shared_expert_size=32,
                       moe_impl="grouped")
    p, _ = L.init_moe_mlp(jax.random.PRNGKey(2), gated)
    assert "shared_gate" in p
    y = L._apply_shared_expert(p, x, gated)
    ungated = L._apply_shared_expert(
        {n: w for n, w in p.items() if n != "shared_gate"}, x, gated)
    gate = jax.nn.sigmoid(x @ p["shared_gate"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(gate * ungated),
                               rtol=1e-5, atol=1e-7)


def test_drafts_that_are_refused(glm):
    """An external draft over a latent pool stays refused; so do a second
    module, a width-1 prefill chunk and a gamma the model has no modules
    for. The weights of the stack do not depend on the module."""
    model, params = glm
    with pytest.raises(NotImplementedError, match="a draft model"):
        InferenceEngineV2(
            model, RaggedInferenceEngineConfig(dtype="float32", **SHAPE),
            params=params, max_seq_len=256, draft_model=tiny_glm())
    e = engine(model, params)
    with pytest.raises(NotImplementedError, match="latent rows"):
        e.attach_draft(tiny_glm())
    with pytest.raises(ValueError, match="one token a module"):
        e.serve(iter([[]]), gamma=2)
    two = tiny_glm(num_nextn_predict_layers=2)
    with pytest.raises(NotImplementedError, match="one module drafts"):
        engine(two, two.init(jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match="prefill_chunk_size"):
        engine(model, params, prefill_chunk_size=1)
    bare = tiny_glm(num_nextn_predict_layers=0)
    plain = bare.init(jax.random.PRNGKey(39))
    again = model.init(jax.random.PRNGKey(39))
    assert "mtp" not in plain and not engine(bare, plain).self_draft
    for a, b in zip(jax.tree.leaves(plain),
                    jax.tree.leaves({k: v for k, v in again.items()
                                     if k != "mtp"})):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="no draft model"):
        engine(bare, plain).serve(iter([[]]), speculate=True)


def test_reference_against_transformers(glm, reference):
    """The plain reference's stack against ``transformers``' DeepSeek-V3
    (the family ``glm4_moe_lite`` takes its layer from) at the same small
    size, the program's weights under its names."""
    torch = pytest.importorskip("torch")
    try:
        from transformers import DeepseekV3Config, DeepseekV3ForCausalLM
    except ImportError:
        pytest.skip("transformers has no DeepseekV3")
    _, params = glm
    hf_config = DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=LAYERS,
        num_attention_heads=5, num_key_value_heads=5, n_shared_experts=1,
        n_routed_experts=16, routed_scaling_factor=1.8, kv_lora_rank=16,
        q_lora_rank=24, qk_rope_head_dim=4, v_head_dim=16,
        qk_nope_head_dim=12, n_group=1, topk_group=1, num_experts_per_tok=4,
        first_k_dense_replace=1, norm_topk_prob=True, rope_theta=1e6,
        rms_norm_eps=1e-5, max_position_embeddings=256, rope_scaling=None,
        rope_interleave=True, attention_bias=False, tie_word_embeddings=False,
        attn_implementation="eager")
    hf = DeepseekV3ForCausalLM(hf_config).eval()

    def t(w):
        return torch.tensor(np.asarray(w, np.float32))

    sd = {"model.embed_tokens.weight": t(params["embed"]["tok"]),
          "model.norm.weight": t(params["final_norm"]["scale"]),
          "lm_head.weight": t(params["embed"]["lm_head"]).T}
    names = (("wi_gate", "gate_proj"), ("wi_up", "up_proj"),
             ("wo", "down_proj"))
    stack = [(params["layers"]["g0"], 0), (params["layers"]["g1"], 0),
             (params["layers"]["g1"], 1)]
    for i, (group, at) in enumerate(stack):
        lay = jax.tree.map(lambda w: w[at], group)
        pre, a = f"model.layers.{i}.", lay["attn"]
        sd[pre + "self_attn.q_a_proj.weight"] = t(a["wq_a"]).T
        sd[pre + "self_attn.q_a_layernorm.weight"] = t(a["q_norm"]["scale"])
        sd[pre + "self_attn.q_b_proj.weight"] = t(a["wq_b"]).reshape(24, -1).T
        sd[pre + "self_attn.kv_a_proj_with_mqa.weight"] = t(a["wkv_a"]).T
        sd[pre + "self_attn.kv_a_layernorm.weight"] = t(a["kv_norm"]["scale"])
        sd[pre + "self_attn.kv_b_proj.weight"] = \
            t(a["wkv_b"]).reshape(16, -1).T
        sd[pre + "self_attn.o_proj.weight"] = t(a["wo"]).reshape(-1, 64).T
        sd[pre + "input_layernorm.weight"] = t(lay["norm1"]["scale"])
        sd[pre + "post_attention_layernorm.weight"] = t(lay["norm2"]["scale"])
        mlp = lay["mlp"]
        if "router" not in mlp:
            for ours, theirs in names:
                sd[f"{pre}mlp.{theirs}.weight"] = t(mlp[ours]).T
            continue
        sd[pre + "mlp.gate.weight"] = t(mlp["router"]).T
        sd[pre + "mlp.gate.e_score_correction_bias"] = t(mlp["router_bias"])
        for ours, theirs in names:
            sd[f"{pre}mlp.shared_experts.{theirs}.weight"] = \
                t(mlp["shared_" + ours]).T
            for x in range(16):
                sd[f"{pre}mlp.experts.{x}.{theirs}.weight"] = t(mlp[ours][x]).T
    missing, unexpected = hf.load_state_dict(sd, strict=False)
    assert not unexpected and not [m for m in missing if "rotary" not in m], (
        missing, unexpected)
    ids = np.random.default_rng(9).integers(0, 256, 50)
    with torch.no_grad():
        want = hf(torch.tensor(ids[None])).logits[0].numpy()
    got = reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
    # another library's float32 products: measured under 1e-3 on logits up
    # to 6; every planted fault above reads over 0.02
    assert np.abs(want).max() > 2.0
    assert np.abs(got - want).max() < 2e-3, np.abs(got - want).max()


def test_planned_frames_emit_the_tokens_of_whole_frames(
        glm, planned_against_whole):
    """``hidden`` in the carry and two columns a step in a frame whose step
    count is an operand: the model drafting for itself gives every request
    the tokens of a run whose frames all run their 4 steps (the wide frames
    through ``_serving_scan_body``, the narrow ones through
    ``_self_spec_scan_body``), through the same two programs."""
    model, params = glm
    e = engine(model, params)
    prompts = prompts_of((33, 5, 16, 1, 40, 23), seed=7)
    reqs = [(u, p, 7 + u) for u, p in prompts.items()]
    planned, hist = planned_against_whole(
        e, lambda: iter([reqs[:1], [], reqs[1:3], [], [], reqs[3:]]))
    assert {u: len(t) for u, t in planned.items()} \
        == {u: 7 + u for u in prompts}
    assert {2, 3} & set(hist) and max(hist) == 4
    assert e.runner.compile_count() == {"frame": 2}
    assert e.kv.free_blocks == e.kv.num_blocks - 1 and not e.state.seqs
