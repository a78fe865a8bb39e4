"""LFM2-24B-A2B on the paged serving path, against its plain reference.

The preset (``models/config.py`` ``lfm2-24b-a2b``) is served with a
convolution tail a slot beside a page pool: gated short convolutions (three
taps, no activation, the tail carried: a conv layer keeps two rows of its
convolution's input and nothing else) to one layer of softmax attention (one
RMSNorm a q and k head before RoPE), one leading dense layer, then routed
layers (sigmoid scores, the top k of score + bias, weights the scores over
their sum + 1e-6, no shared expert). The reference is the benchmark's
(``perfbench/configs/lfm2_moe_reference.py``: float32, the convolution as
three shifted copies, every expert computed for every token), which shares
no code with the program. Sizes here are small and keep the layout: mixers
``conv | full conv conv conv`` (a pattern as long as the stack, which no
period of 4 tiles), 4 / 2 heads of 16, 8 experts top 2.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
from deepspeed_tpu.models import build_model, get_config
from deepspeed_tpu.moe.sharded_moe import topk_gating_grouped

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (rows grouped by expert
#: against every expert dense and masked, pages against one softmax, the
#: convolution behind a carried tail against three shifted copies): measured
#: 3e-7 .. 8e-7 over every compared row, on logits up to 0.5. The same walk
#: with the activations in bfloat16 (``test_bfloat16_is_outside_the_
#: tolerance``) reads 1.6e-2: the tolerance lies three orders under the
#: nearest precision below the one stated, and a lost tail reads as bfloat16
#: does or worse (``test_a_lost_tail_shows_in_the_logits``)
LOGIT_TOL = 1e-5
BF16_FLOOR = 1e-2

#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "norm_eps": 1e-5, "vocab_size": 256,
          "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
          "conv_L_cache": 3, "num_experts": 8, "num_experts_per_tok": 2,
          "norm_topk_prob": True, "routed_scaling_factor": 1.0,
          "intermediate_size": 96, "moe_intermediate_size": 32}
MIXERS = ("conv", "full", "conv", "conv", "conv")
SLOTS, WIDTH, PAGE, SEQ = 8, 128, 8, 320
SHAPE = dict(max_ragged_batch_size=SLOTS, prefill_chunk_size=WIDTH,
             kv_block_size=PAGE, max_tokens_per_step=2048, frame_steps=2)
#: prompt lengths that straddle the chunk (128) and the tail (2 rows)
LENGTHS = (1, 2, 3, 127, 128, 129, 257)
DECODE = 4


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs", "lfm2_moe_reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the benchmark's blocks are sized for 3k tokens and 65,536 words: here
    # every context pads to ONE shape
    mod.TOKEN_BLOCK, mod.Q_BLOCK, mod.VOCAB_BLOCK = SEQ, 32, 256
    return mod


def tiny_lfm2(**kw):
    cfg = get_config(
        "lfm2-24b-a2b", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"], num_layers=len(MIXERS),
        num_heads=CONFIG["num_attention_heads"],
        num_kv_heads=CONFIG["num_key_value_heads"],
        intermediate_size=CONFIG["intermediate_size"],
        moe_intermediate_size=CONFIG["moe_intermediate_size"],
        num_experts=CONFIG["num_experts"],
        num_experts_per_tok=CONFIG["num_experts_per_tok"],
        mixer_pattern=MIXERS, moe_first_dense=1, max_seq_len=SEQ,
        **{"dtype": "float32", **kw})
    return build_model(cfg)


@pytest.fixture(scope="module")
def whole():
    """Seeded float32 weights, the layers' matrices scaled up from their
    initial 0.02 so that the mixers, the routing and the experts all move
    the logits, the router wider still so that the top 2 of 8 carry most of
    the mass, the norms' weights drawn around 1, the expert bias wide enough
    to change picks, the taps (drawn like the matrices) at 0.5 so that the
    conv mixers carry as much of the stream as the others and the tail as
    much as the position's own input."""
    model = tiny_lfm2()
    params = model.init(jax.random.PRNGKey(51))
    rng = np.random.default_rng(51)

    def widen(path, w):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "router":
            return w * 40.0
        if name == "router_bias":
            return w * 5.0
        if name == "conv":
            return w * 25.0
        if name == "scale":
            return w * jnp.asarray(rng.uniform(0.7, 1.3, w.shape), w.dtype)
        return w * 6.0

    params["layers"] = jax.tree_util.tree_map_with_path(widen,
                                                        params["layers"])
    return model, params


_RUNNERS, _FORWARDS = {}, {}


def engine(model, params, **kw):
    """An engine over ``model``; engines of one model and page geometry
    share one runner, so each program compiles once a module."""
    e = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            **{"dtype": model.cfg.dtype, **SHAPE, **kw}),
        params=params, max_seq_len=SEQ)
    key = (id(model), e.runner.block_size, e.runner.max_blocks)
    e.runner = _RUNNERS.setdefault(key, e.runner)
    return e


def forward_of(runner):
    if id(runner) not in _FORWARDS:
        _FORWARDS[id(runner)] = jax.jit(
            lambda *a, recurrent: runner._forward(*a, recurrent=recurrent,
                                                  moe_work=True))
    return _FORWARDS[id(runner)]


def sequences():
    """One request a slot: (prompt + forced continuation, prompt length)."""
    rng = np.random.default_rng(151)
    return {slot: (rng.integers(0, 256, n + DECODE).astype(np.int32), n)
            for slot, n in enumerate(LENGTHS)}


def paged_walk(e, params, seqs, planted=None):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a frame does: prompts in chunks of ``WIDTH`` beside each other
    (a row past its prompt rides the chunk with one position), then one
    position a step through the pages and the carried tails, the idle slot
    with garbage ids under position -1 and, ``planted``, a tail of its own.
    Returns ({slot: {position: logits}}, the tails after each step)."""
    rng = np.random.default_rng(5)
    dt = jnp.dtype(e.model.cfg.dtype)
    tables = np.zeros((SLOTS, SEQ // PAGE), np.int32)
    for i, slot in enumerate(seqs):
        tables[slot] = 1 + i * tables.shape[1] + np.arange(tables.shape[1])
    kpool, vpool = jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v)
    assert kpool.shape[:2] == (1, 2)        # the ONE full layer's pages
    recurrent = tuple(jnp.zeros(shape, dtype) for shape, dtype in
                      e.runner.recurrent_shapes(SLOTS))
    if planted is not None:
        recurrent = (recurrent[0].at[:, :, SLOTS - 1].set(
            jnp.asarray(planted, dt)),)
    fwd = forward_of(e.runner)
    done = {slot: 0 for slot in seqs}
    got, tails = {slot: {} for slot in seqs}, []
    while any(done[s] < len(ids) for s, (ids, _) in seqs.items()):
        prefilling = any(done[s] < plen for s, (_, plen) in seqs.items())
        w = WIDTH if prefilling else 1
        ids = rng.integers(0, 256, (SLOTS, w)).astype(np.int32)
        positions = np.full((SLOTS, w), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        for slot, (seq, plen) in seqs.items():
            at = done[slot]
            n = min(w, plen - at) if at < plen else min(1, len(seq) - at)
            ids[slot, :n] = seq[at:at + n]
            positions[slot, :n] = at + np.arange(n)
            valid[slot], done[slot] = n, at + n
        logits, kpool, vpool, _, recurrent = fwd(
            params, ids, positions, tables, valid, kpool, vpool,
            recurrent=recurrent)
        logits = np.asarray(logits)
        for slot in seqs:
            if valid[slot]:
                got[slot][done[slot] - 1] = logits[slot]
        tails.append(np.asarray(recurrent[0].astype(jnp.float32)))
    return got, tails


@pytest.fixture(scope="module")
def walked(whole):
    model, params = whole
    planted = np.random.default_rng(7).standard_normal((4, 2, 64))
    return paged_walk(engine(model, params), params, sequences(), planted) \
        + (planted,)


@pytest.fixture(scope="module")
def wanted(whole, reference):
    """The reference's plain logits of every sequence at the rows the walk
    reads: the prompt's last position and each forced token's."""
    _, params = whole
    out = {}
    for slot, (ids, plen) in sequences().items():
        rows = np.arange(plen - 1, len(ids))
        out[slot] = dict(zip(rows, reference.logits_rows(
            params, ids, rows, CONFIG, tie_margin=0)))
    return out


# ---- (a) prefill, then decode through the cache -----------------------------


@pytest.mark.parametrize("slot", range(len(LENGTHS)),
                         ids=[f"prompt{n}" for n in LENGTHS])
def test_prefill_then_decode_matches_the_reference(walked, wanted, slot):
    """A prompt in chunks of 128 (its tail carried from chunk to chunk, its
    keys through the pages), then one position a step: the logits at the
    prompt's last position and at every forced token against the
    reference's one-shot forward, float32 to rounding. The lengths straddle
    the chunk and the two-row tail; the rows past their prompt ride the
    longest prompt's chunks with one live position."""
    got = walked[0][slot]
    plen = LENGTHS[slot]
    assert sorted(wanted[slot]) == list(range(plen - 1, plen + DECODE))
    worst = 0.0
    for pos, want in wanted[slot].items():
        assert np.abs(want).max() > 0.2
        worst = max(worst, np.abs(got[pos] - want).max())
    print(f"prompt {plen}: worst gap {worst:.2e}")
    assert worst < LOGIT_TOL, plen


def test_bfloat16_is_outside_the_tolerance(whole, wanted):
    """The same walk with bfloat16 activations (the nearest precision below
    the float32 this comparison states) is not correct by ``LOGIT_TOL``."""
    model, params = whole
    ids, plen = sequences()[5]
    low = tiny_lfm2(dtype="bfloat16")
    # (the prompt alone: two wide steps, one program)
    got, _ = paged_walk(engine(low, params), params, {5: (ids[:plen], plen)})
    worst = np.abs(got[5][plen - 1].astype(np.float32)
                   - wanted[5][plen - 1]).max()
    print(f"bfloat16 activations: worst gap {worst:.2e}")
    assert worst > BF16_FLOOR > LOGIT_TOL


# ---- (b) the tail on the carry ----------------------------------------------


def test_a_row_that_sits_out_keeps_its_tail_to_the_bit(walked):
    """The idle slot's planted tail (garbage ids under position -1 in every
    step, wide and narrow) is what it was, bit for bit, in all four conv
    layers; a live slot's moved."""
    _, tails, planted = walked
    for tail in tails:
        assert np.array_equal(tail[:, :, SLOTS - 1],
                              planted.astype(np.float32))
    assert np.abs(tails[-1][:, :, 0]).max() > 0.01
    assert tails[0].shape == (4, 2, SLOTS, 64)


def test_a_new_tenant_of_a_slot_starts_from_zeros(whole):
    """Admission zeroes the new tenant's rows of every conv layer's tail
    and no other row's: what the slot's last tenant left is gone, what the
    other slots hold is theirs to the bit."""
    model, params = whole
    e = engine(model, params)
    slots = DeviceSlotTable(
        SLOTS, prompt_width=WIDTH, table_width=1, rng=jax.random.PRNGKey(0),
        n_stats=e.runner.n_stats,
        recurrent=e.runner.recurrent_shapes(SLOTS))
    assert [a.shape for a in slots.recurrent] == [(4, 2, SLOTS, 64)]
    left = np.random.default_rng(3).standard_normal(
        (4, 2, SLOTS, 64)).astype(np.float32)
    slots.recurrent = (jnp.asarray(left),)
    seq = e.state.get_or_create_sequence(9)
    assert e.state.ensure_capacity(seq, 12)
    slots.ensure_widths(10, len(seq.blocks), SEQ, SEQ // PAGE)
    slots.admit([(9, seq, np.arange(10, dtype=np.int32), 2, 0.0, None)])
    at = slots.slot_of_uid[9]
    tail = np.asarray(slots.recurrent[0])
    assert not tail[:, :, at].any()
    keep = np.arange(SLOTS) != at
    assert np.array_equal(tail[:, :, keep], left[:, :, keep])
    slots.retire(9)
    e.state.flush_sequence(9)


def test_a_preempted_sequence_recomputes_to_the_same_logits(whole, walked):
    """A sequence cut off after two generated tokens (a preemption: pages
    back, queued again with its prompt and the tokens it had emitted) starts
    from a zero tail, prefills the folded tokens in other chunks than the
    first time and reads, at its next positions, the logits of the
    uninterrupted walk."""
    model, params = whole
    ids, plen = sequences()[5]
    folded = plen + 2
    got, _ = paged_walk(engine(model, params), params, {2: (ids, folded)})
    for pos in range(folded - 1, len(ids)):
        assert np.abs(got[2][pos] - walked[0][5][pos]).max() < LOGIT_TOL


def test_a_lost_tail_shows_in_the_logits(whole, walked):
    """The same sequence walked with its tails zeroed between the prompt's
    two chunks reads far outside the tolerance at the prompt's end: the
    comparison sees a tail that is not carried."""
    model, params = whole
    ids, plen = sequences()[5]
    e = engine(model, params)
    fwd = forward_of(e.runner)
    tables = np.zeros((SLOTS, SEQ // PAGE), np.int32)
    tables[0] = 1 + np.arange(tables.shape[1])
    kpool, vpool = jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v)
    zeros = tuple(jnp.zeros(shape, dtype) for shape, dtype in
                  e.runner.recurrent_shapes(SLOTS))
    for at in range(0, plen, WIDTH):
        n = min(WIDTH, plen - at)
        toks = np.zeros((SLOTS, WIDTH), np.int32)
        positions = np.full((SLOTS, WIDTH), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        toks[0, :n], positions[0, :n], valid[0] = ids[at:at + n], \
            at + np.arange(n), n
        # the fault: every chunk begins from a zero tail
        logits, kpool, vpool, _, _ = fwd(params, toks, positions, tables,
                                         valid, kpool, vpool, recurrent=zeros)
    gap = np.abs(np.asarray(logits)[0] - walked[0][5][plen - 1]).max()
    assert gap > BF16_FLOOR, gap


# ---- (c) the router ----------------------------------------------------------


def test_router_chooses_by_score_plus_bias_and_weighs_by_score(reference):
    """Sigmoid scores; the top 2 of score + bias; weights the chosen SCORES
    over (their sum + 1e-6): the program's gating against the reference's on
    a batch with a forced near-tie that the bias decides, at scores small
    enough that the 1e-6 is no rounding (the two chosen sum to ~7e-4)."""
    rng = np.random.default_rng(11)
    logits = rng.uniform(-9.0, -7.0, (64, 8)).astype(np.float32)
    bias = rng.normal(0, 1e-4, (8,)).astype(np.float32)
    # row 0: experts 2 and 5 score alike to 1e-7 behind expert 0; the bias
    # puts 5 ahead of 2 though 2's score is the larger
    logits[0] = [-6.0, -12, -7.0, -12, -12, -7.0 - 1e-5, -12, -12]
    bias[[0, 2, 5]] = [0.0, 0.0, 1e-6]
    config = dict(CONFIG)
    eye = jnp.eye(8, dtype=jnp.float32)
    want_w, want_sets, s, _ = reference.route(jnp.asarray(logits), eye,
                                              jnp.asarray(bias), config)
    idx, w, _ = topk_gating_grouped(
        jnp.asarray(logits), k=2, normalize=True, bias=jnp.asarray(bias),
        scale=1.0, score="sigmoid", eps=1e-6)
    idx, w, s = np.asarray(idx), np.asarray(w), np.asarray(s)
    assert sorted(idx[0]) == [0, 5] and s[0, 2] > s[0, 5]
    assert np.array_equal(np.sort(idx, -1), np.sort(np.asarray(want_sets), -1))
    dense = np.zeros_like(s)
    np.put_along_axis(dense, idx, w, axis=-1)
    np.testing.assert_allclose(dense, np.asarray(want_w), rtol=2e-6)
    picked = np.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=2e-6)
    # the addend is seen: without it the weights sum to 1
    assert np.all(w.sum(-1) < 1 - 2e-4)
    assert get_config("lfm2-24b-a2b").moe_norm_eps == 1e-6


# ---- (d) what each kind keeps -----------------------------------------------


def test_recurrent_shapes_by_kind():
    """A Qwen3-Next stack carries what it carried (state float32 and tail,
    to the shape and dtype); an LFM2 stack its tails ALONE: no state of zero
    size rides beside them."""
    qwen = PagedModelRunner(build_model(get_config(
        "qwen3-next-80b-a3b", num_layers=8, num_experts=128,
        moe_router_experts=512)), 128, 128)
    assert qwen.recurrent_kinds == ("linear",)
    assert qwen.recurrent_shapes(16) == (
        ((6, 16, 32, 128, 128), jnp.float32),
        ((6, 3, 16, 8192), jnp.dtype("bfloat16")))
    assert qwen.recurrent_stat_names == (
        "gdn_positions", "gdn_state_rw", "gdn_positions_computed")
    cut = get_config("lfm2-24b-a2b", num_layers=9, moe_first_dense=1,
                     mixer_pattern=["conv", "full", "conv", "conv", "conv",
                                    "full", "conv", "conv", "conv"])
    lfm2 = PagedModelRunner(build_model(cut), 128, 32)
    assert lfm2.recurrent_kinds == ("conv",)
    assert lfm2.recurrent_shapes(16) == (
        ((7, 2, 16, 2048), jnp.dtype("bfloat16")),)
    assert (cut.conv_layers, cut.linear_layers, cut.cache_layers) == (7, 0, 2)
    assert lfm2.n_stats == qwen.n_stats - 3 - 3 + 1    # no share, no gdn
    dense = PagedModelRunner(build_model("tiny"), 8, 8)
    assert dense.recurrent_kinds == () and dense.recurrent_shapes(4) == ()


def test_the_preset_holds_the_published_sizes():
    cfg = get_config("lfm2-24b-a2b")
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dims_per_head,
            cfg.vocab_size) == (2048, 32, 8, 64, 65536)
    assert (cfg.ffn_size, cfg.moe_ffn_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.moe_first_dense) == (11776, 1536, 64,
                                                              4, 2)
    assert (cfg.conv_layers, cfg.cache_layers, cfg.conv_kernel) == (30, 10, 3)
    assert cfg.layer_mixers()[:7] == ("conv", "conv", "full", "conv", "conv",
                                      "conv", "full")
    assert cfg.tie_embeddings and cfg.moe_router_score == "sigmoid"
    with pytest.raises(AssertionError, match="leading dense"):
        build_model(cfg.replace(num_layers=8,
                                mixer_pattern=("full", "conv", "conv", "conv")))


# ---- the comparison that decides ``correct`` ---------------------------------


def greedy(e, params, prompt, new, fault=None):
    """Greedy tokens of one row through the walk's own two programs: the
    prompt as one chunk, then a position a step through the pages and the
    carried tails. ``fault``: "lost" hands every narrow step zero tails,
    "stale" the tails of the step before the last."""
    fwd = forward_of(e.runner)
    tables = np.zeros((SLOTS, SEQ // PAGE), np.int32)
    tables[0] = 1 + np.arange(tables.shape[1])
    kpool, vpool = jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v)
    rec = tuple(jnp.zeros(shape, dtype) for shape, dtype in
                e.runner.recurrent_shapes(SLOTS))
    zeros = before = rec
    out = list(prompt)
    for step in range(new):
        w = WIDTH if step == 0 else 1
        n = len(prompt) if step == 0 else 1
        ids = np.zeros((SLOTS, w), np.int32)
        positions = np.full((SLOTS, w), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        ids[0, :n] = out[-n:]
        positions[0, :n] = len(out) - n + np.arange(n)
        valid[0] = n
        given = {None: rec, "lost": zeros, "stale": before}[
            fault if step else None]
        before = rec
        logits, kpool, vpool, _, rec = fwd(
            params, ids, positions, tables, valid, kpool, vpool,
            recurrent=given)
        out.append(int(np.asarray(logits)[0].argmax()))
    return out[len(prompt):]


def test_the_harness_comparison_sees_a_tail_that_is_lost_or_stale(
        whole, reference):
    """``perfbench.reference_check.check`` (its 0.25 as it stands) over the
    reference's default ``logits_rows``, candidates and all: the served
    greedy tokens of a prompt pass; the tokens of the same programs with
    every narrow step's tails zeroed, or one position old, do not (what
    ``.chipcheck/pr51_controls.py`` reads at the cell's size on the chip:
    PERF.md, PR 51)."""
    from perfbench import reference_check
    model, params = whole
    e = engine(model, params)
    prompt = list(sequences()[4][0][:100])
    worst = {}
    for fault in (None, "lost", "stale"):
        ok, gaps = reference_check.check(
            reference, params, CONFIG,
            [("row", prompt, greedy(e, params, prompt, 40, fault))])
        worst[fault] = ok, gaps["row"]
    print(worst)
    assert worst[None][0] and worst[None][1] < 0.05
    assert not worst["lost"][0] and not worst["stale"][0]


# ---- the reference's own reading of near-ties -------------------------------


def test_the_envelope_holds_the_plain_logits_and_each_candidates(whole,
                                                                 reference):
    """``logits_rows`` under a margin: every row's envelope lies on or above
    its plain logits taken relative to their maximum (the plain choice is a
    candidate), is 0 at some word, and where the margin admits ONE routing
    it is those logits; a wide margin admits more candidates than a narrow
    one."""
    _, params = whole
    ids, plen = sequences()[3]
    rows = np.arange(plen - 1, len(ids))
    plain = reference.logits_rows(params, ids, rows, CONFIG, tie_margin=0)
    plain = plain - plain.max(-1, keepdims=True)
    narrow = reference.logits_rows(params, ids, rows, CONFIG,
                                   tie_margin=1e-9)
    np.testing.assert_allclose(narrow, plain, atol=2e-6)
    wide = reference.logits_rows(params, ids, rows, CONFIG, tie_margin=0.05)
    assert (wide >= plain - 2e-6).all() and np.abs(wide.max(-1)).max() < 1e-6
    assert (wide > plain + 1e-3).any()      # some row had a second routing
