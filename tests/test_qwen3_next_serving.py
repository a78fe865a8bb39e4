"""Qwen3-Next-80B-A3B on the paged serving path, against its plain reference.

The preset (``models/config.py`` ``qwen3-next-80b-a3b``) is served with a
recurrent state a slot beside a page pool: three Gated DeltaNet layers (a
causal depthwise convolution with a carried tail, the gated delta rule as a
chunked scan in a wide step and as the recurrence in a narrow one, a gated
norm) to one of gated softmax attention (a doubled q_proj, a quarter of the
head rotated), every layer a routed block beside a gated shared expert, every
norm 1 + w. The reference is the benchmark's
(``perfbench/configs/qwen3_next_reference.py``: float32, the delta rule as
the four-line recurrence, every held expert computed for every token), which
shares no code with the program. Sizes here are small and keep every ratio:
Hv = 2 Hk, a quarter rotary, period 4, two periods, a router wider than the
experts held.
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
from deepspeed_tpu.inference.v2.ragged_manager import DeviceSlotTable
from deepspeed_tpu.models import build_model, get_config
from deepspeed_tpu.models import layers as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 on both sides, summed in another order (the chunked rule's
#: triangular solve against the recurrence, rows grouped by expert against
#: every expert dense and masked, pages against one softmax): measured
#: under 3e-4 over every compared row, on logits up to 5. Every planted
#: fault below (a state or a tail not carried, w for 1 + w, an output gate
#: left open) reads over 1.4: more than three orders outside.
LOGIT_TOL = 3e-4
FAULT_FLOOR = 1.0

#: the public config.json's keys at a small size (what the reference reads)
CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16,
          "partial_rotary_factor": 0.25, "rope_theta": 1e7,
          "rms_norm_eps": 1e-6, "vocab_size": 256,
          "full_attention_interval": 4, "linear_num_key_heads": 2,
          "linear_num_value_heads": 4, "linear_key_head_dim": 8,
          "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
          "num_experts_per_tok": 4, "norm_topk_prob": True}
LAYERS, EXPERTS = 8, 16
#: 8 slots x 96 positions packs, and a chunk is a block of 64 of the chunked
#: rule and a part of a second; 8 x 1 does not pack
SHAPE = dict(max_ragged_batch_size=8, prefill_chunk_size=96, kv_block_size=8,
             max_tokens_per_step=1024, frame_steps=2)
SLOTS, WIDTH, PAGE, SEQ = 8, 96, 8, 512


@pytest.fixture(autouse=True)
def _mesh(mesh_8dp):
    yield


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "perfbench", "configs",
                        "qwen3_next_reference.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the benchmark's blocks are sized for 15k tokens and 151,936 words
    mod.TOKEN_BLOCK, mod.Q_BLOCK, mod.VOCAB_BLOCK = 64, 16, 128
    return mod


def tiny_qwen(held=EXPERTS, first=0, layers=LAYERS, **kw):
    cfg = get_config(
        "qwen3-next-80b-a3b", vocab_size=CONFIG["vocab_size"],
        hidden_size=CONFIG["hidden_size"], num_layers=layers,
        num_heads=CONFIG["num_attention_heads"],
        num_kv_heads=CONFIG["num_key_value_heads"],
        head_dim=CONFIG["head_dim"], moe_intermediate_size=32,
        moe_shared_expert_size=32, num_experts=held, moe_expert_first=first,
        moe_router_experts=EXPERTS,
        num_experts_per_tok=CONFIG["num_experts_per_tok"],
        linear_num_key_heads=CONFIG["linear_num_key_heads"],
        linear_num_value_heads=CONFIG["linear_num_value_heads"],
        linear_key_head_dim=CONFIG["linear_key_head_dim"],
        linear_value_head_dim=CONFIG["linear_value_head_dim"],
        max_seq_len=SEQ, dtype="float32", **kw)
    return build_model(cfg)


def share_of(params, first, held):
    """``params`` with experts [first, first + held) of every layer."""
    return {**params, "layers": {
        g: {**tree, "mlp": {n: w[:, first:first + held]
                            if n in L.EXPERT_MATRICES else w
                            for n, w in tree["mlp"].items()}}
        for g, tree in params["layers"].items()}}


@pytest.fixture(scope="module")
def whole():
    """Seeded float32 weights with every expert held, the layers' matrices
    scaled up from their initial 0.02 so that the mixers, the routing and
    the experts all move the logits (at E = 64 a projection drawn at 0.02
    gives outputs of ~0.16); the norms' w drawn at 0.3 so that 1 + w is no
    rounding beside 1; the router 20 x wider so that the top 4 of 16 carry
    most of the mass."""
    model = tiny_qwen()
    params = model.init(jax.random.PRNGKey(43))

    def widen(path, w):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "router":
            return w * 20.0
        if name in ("A_log", "dt_bias", "conv"):
            return w
        if name == "scale":
            norm = path[-2].key
            return w if norm == "norm" else w * 15.0    # 1 + w: w ~ 0.3
        return w * 6.0

    params["layers"] = jax.tree_util.tree_map_with_path(widen,
                                                        params["layers"])
    params["final_norm"] = jax.tree.map(lambda w: w * 15.0,
                                        params["final_norm"])
    return model, params


#: the runners the engines below share, and their jitted forwards
_RUNNERS, _FORWARDS = {}, {}


def engine(model, params, **kw):
    """An engine over ``model``. Engines of one model and one page geometry
    run the same programs (a runner holds no state but its compiled
    functions), so they share one runner and compile each program once a
    module, not once an engine."""
    e = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(dtype="float32",
                                           **{**SHAPE, **kw}),
        params=params, max_seq_len=SEQ)
    key = (id(model), e.runner.block_size, e.runner.max_blocks)
    e.runner = _RUNNERS.setdefault(key, e.runner)
    return e


def forward_of(runner, **options):
    """``runner._forward`` jitted, once a runner and set of options."""
    key = (id(runner), tuple(sorted(options.items())))
    if key not in _FORWARDS:
        _FORWARDS[key] = jax.jit(lambda *a, recurrent: runner._forward(
            *a, recurrent=recurrent, **options))
    return _FORWARDS[key]


def sequences():
    """Requests of different lengths: (prompt + forced continuation)."""
    rng = np.random.default_rng(143)
    return {0: (rng.integers(0, 256, 150 + 6).astype(np.int32), 150),
            3: (rng.integers(0, 256, 37 + 8).astype(np.int32), 37)}


def paged_steps(e, params, seqs, width=WIDTH, garbage_seed=5):
    """Walk ``seqs`` {slot: (ids, prompt_len)} through the runner's forward
    the way a frame does: prompts in chunks of ``width`` beside each other,
    then one position a step through the pages and the carried state, the
    other slots idle with garbage ids under position -1. Yields per step
    (logits (slots, V), {slot: position of its last token}, the routed
    block's work, live tokens, the (state, tail) after the step)."""
    rng = np.random.default_rng(garbage_seed)
    tables = np.zeros((SLOTS, SEQ // PAGE), np.int32)
    for i, slot in enumerate(seqs):
        tables[slot] = 1 + i * tables.shape[1] + np.arange(tables.shape[1])
    kpool, vpool = jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v)
    assert kpool.shape[:2] == (LAYERS // 4, 2)      # the full layers alone
    recurrent = tuple(jnp.zeros(shape, dtype) for shape, dtype in
                      e.runner.recurrent_shapes(SLOTS))
    fwd = forward_of(e.runner, moe_work=True)
    done = {slot: 0 for slot in seqs}
    while any(done[s] < len(ids) for s, (ids, _) in seqs.items()):
        prefilling = any(done[s] < plen for s, (_, plen) in seqs.items())
        w = width if prefilling else 1
        ids = rng.integers(0, 256, (SLOTS, w)).astype(np.int32)
        positions = np.full((SLOTS, w), -1, np.int32)
        valid = np.zeros((SLOTS,), np.int32)
        for slot, (seq, plen) in seqs.items():
            at = done[slot]
            n = min(w, plen - at) if at < plen else min(1, len(seq) - at)
            ids[slot, :n] = seq[at:at + n]
            positions[slot, :n] = at + np.arange(n)
            valid[slot], done[slot] = n, at + n
        logits, kpool, vpool, work, recurrent = fwd(
            params, ids, positions, tables, valid, kpool, vpool,
            recurrent=recurrent)
        yield (np.asarray(logits), {s: done[s] - 1 for s in seqs if valid[s]},
               np.asarray(work), int(valid.sum()), recurrent)


# ---- the mixer against the reference ---------------------------------------


@pytest.mark.parametrize("length", [1, 5, 37, 64, 100, 150])
def test_chunked_rule_is_the_recurrence(whole, reference, length):
    """The program's Gated DeltaNet mixer over one chunk of ``length``
    positions (the chunked rule: blocks of 64, a last block padded with dead
    positions; ``length`` 1 the recurrent update) against the reference's
    recurrence, in float32 to rounding: outputs, final state and the
    convolution's tail."""
    model, params = whole
    cfg = model.cfg
    mix = jax.tree.map(lambda w: w[1], params["layers"]["g1"]["attn"])
    rng = np.random.default_rng(length)
    x = jnp.asarray(rng.standard_normal((2, length, 64)), jnp.float32)
    want, want_state, want_tail = reference.gated_delta_net(
        mix, x, hk=2, hv=4, dk=8, dv=8, eps=1e-6, with_state=True)
    u, z, b, a = L.gdn_project(mix, x, cfg)
    live = jnp.ones((2, length), bool)
    u, tail = L.gdn_conv(mix, u, jnp.zeros((2, 3, cfg.linear_channels)),
                         jnp.full((2,), length, jnp.int32), cfg)
    q, k, v = L.gdn_split(u, cfg)
    out, state = L.gdn_rule(q, k, v, *L.gdn_gates(mix, b, a, live),
                            jnp.zeros((2, 4, 8, 8)))
    got = L.gdn_output(mix, out, z, cfg)
    scale = float(jnp.abs(want).max())
    assert scale > 0.1
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(state - want_state).max()) < 2e-5
    np.testing.assert_allclose(np.asarray(tail), np.asarray(want_tail),
                               atol=1e-6)
    # some heads forget within the chunk and some carry across it
    decay = np.exp(np.asarray(L.gdn_gates(mix, b, a, live)[1]).sum(1))
    if length >= 64:
        assert decay.min() < 1e-3 < 0.05 < decay.max() <= 1.0, decay


def test_dead_positions_leave_state_and_tail(whole):
    """A chunk whose row has ``n`` live positions moves state and tail by
    exactly those: the same chunk cut to ``n`` gives the same state (to
    rounding: another block structure) and the same tail (to the bit); a
    row with none keeps both to the bit, whatever garbage its positions
    hold."""
    model, params = whole
    cfg = model.cfg
    mix = jax.tree.map(lambda w: w[0], params["layers"]["g2"]["attn"])
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((3, 96, 64)), jnp.float32)
    n = jnp.asarray([96, 41, 0], jnp.int32)
    state0 = jnp.asarray(rng.standard_normal((3, 4, 8, 8)), jnp.float32)
    tail0 = jnp.asarray(rng.standard_normal((3, 3, cfg.linear_channels)),
                        jnp.float32)

    def run(x, n, state, tail):
        live = jnp.arange(x.shape[1])[None] < n[:, None]
        u, _, b, a = L.gdn_project(mix, x, cfg)
        u, tail = L.gdn_conv(mix, u, tail, n, cfg)
        q, k, v = L.gdn_split(u, cfg)
        return L.gdn_rule(q, k, v, *L.gdn_gates(mix, b, a, live), state) \
            + (tail,)

    _, state, tail = run(x, n, state0, tail0)
    _, cut_state, cut_tail = run(x[1:2, :41], n[1:2], state0[1:2],
                                 tail0[1:2])
    assert float(jnp.abs(state[1] - cut_state[0]).max()) < 1e-5
    # (the projection of another batch rounds elsewhere)
    np.testing.assert_allclose(np.asarray(tail[1]), np.asarray(cut_tail[0]),
                               atol=1e-5)
    assert float(jnp.abs(tail[1] - tail0[1]).max()) > 0.1
    assert np.array_equal(np.asarray(tail[2]), np.asarray(tail0[2]))
    # the rule itself multiplies a dead row's state by exp(0) and adds 0;
    # the runner's select makes it the bit
    assert float(jnp.abs(state[2] - state0[2]).max()) == 0.0
    assert float(jnp.abs(state[0] - state0[0]).max()) > 0.1


#: live positions a row of a wide step of 8 rows x 96, and the trips the
#: chunked form's loop must make, ``RULE_ROWS`` = 2 rows a trip: the count
#: of rows that hold more than one, even and odd, from none to every row.
#: Every mix but the last has a frozen row (0) and a rider (1)
ROW_MIXES = [
    ([0, 1, 0, 1, 1, 0, 0, 1], 0),         # nothing prefills
    ([1, 0, 1, 96, 1, 0, 0, 1], 1),        # one row: half a trip
    ([41, 1, 0, 1, 0, 96, 1, 1], 1),       # a trip full
    ([1, 2, 0, 96, 1, 95, 0, 1], 2),       # one row past it
    ([96, 64, 1, 0, 65, 1, 3, 0], 2),
    ([96, 5, 1, 37, 0, 96, 1, 2], 3),      # the LAST row half a trip
    ([96, 2, 95, 64, 65, 3, 96, 40], 4),   # every row prefills
]
_MIXER_STEPS = {}


def mixer_step(model, params, by_rows):
    """One linear mixer's wide step as ``linear_layer`` runs it, jitted once
    a module: the rule on the rows by what they hold (``by_rows``), or
    ``L.gdn_rule`` over the whole (B, C) chunk, what every wide step ran
    before rows were told apart. The rows' live counts are an operand."""
    if by_rows not in _MIXER_STEPS:
        cfg = model.cfg
        mix = jax.tree.map(lambda w: w[0], params["layers"]["g2"]["attn"])

        def rule(u, b, a, pad, state):
            q, k, v = L.gdn_split(u, cfg)
            return L.gdn_rule(q, k, v, *L.gdn_gates(mix, b, a, ~pad), state)

        def step(x, n, state, tail):
            at = jnp.arange(x.shape[1])[None]
            positions = jnp.where(at < n[:, None], at, -1)
            pad = positions < 0
            u, _, b, a = L.gdn_project(mix, x, cfg)
            u, new_tail = L.gdn_conv(mix, u, tail, n, cfg)
            plan = model_runner._row_plan(positions) if by_rows else None
            out, new = model_runner._rule_by_rows(plan, rule, u, b, a, pad,
                                                  state)
            moved = n > 0
            return (jnp.where(pad[:, :, None, None], 0.0, out),
                    jnp.where(moved[:, None, None, None], new, state),
                    jnp.where(moved[:, None, None], new_tail, tail),
                    -1 if plan is None else plan[0],
                    model_runner._rule_positions(n, x.shape[1]))
        _MIXER_STEPS[by_rows] = jax.jit(step)
    return _MIXER_STEPS[by_rows]


@pytest.mark.parametrize("n_live,trips", ROW_MIXES)
def test_a_wide_steps_rule_runs_the_rows_by_what_they_hold(whole, n_live,
                                                           trips):
    """A wide step whose rows mix the kinds (frozen, riding, part of a
    chunk, a full chunk): riders through the recurrence, the prefilling
    rows gathered two a trip into the chunked form, the trips counted in
    the graph, give the outputs, states and tails that the chunked form
    over every row gives, to float32 rounding (the tolerance of
    ``test_chunked_rule_is_the_recurrence``: a rider's update is the same
    algebra summed in another order); a row with no live position keeps
    state and tail to the bit. Over the mixes every trip count is made."""
    assert model_runner.RULE_ROWS == 2
    assert {t for _, t in ROW_MIXES} == set(range(SLOTS // 2 + 1))
    model, params = whole
    cfg = model.cfg
    rng = np.random.default_rng(sum(n_live))
    x = jnp.asarray(rng.standard_normal((SLOTS, WIDTH, 64)), jnp.float32)
    n = jnp.asarray(n_live, jnp.int32)
    state0 = jnp.asarray(rng.standard_normal((SLOTS, 4, 8, 8)), jnp.float32)
    tail0 = jnp.asarray(
        rng.standard_normal((SLOTS, 3, cfg.linear_channels)), jnp.float32)
    out, state, tail, chose, computed = mixer_step(model, params, True)(
        x, n, state0, tail0)
    want, want_state, want_tail, _, _ = mixer_step(model, params, False)(
        x, n, state0, tail0)
    assert int(chose) == trips
    assert int(computed) == trips * 2 * WIDTH + SLOTS
    scale = float(jnp.abs(want).max())
    assert scale > 0.1 or not any(n_live)
    assert float(jnp.abs(out - want).max()) < 2e-5 * max(scale, 1.0)
    assert float(jnp.abs(state - want_state).max()) < 2e-5
    assert np.array_equal(np.asarray(tail), np.asarray(want_tail))
    for row, live in enumerate(n_live):
        if live == 0:
            assert np.array_equal(np.asarray(state[row]),
                                  np.asarray(state0[row]))
            assert np.array_equal(np.asarray(tail[row]),
                                  np.asarray(tail0[row]))
        else:
            assert float(jnp.abs(state[row] - state0[row]).max()) > 1e-3


@pytest.mark.parametrize("n_live,trips", ROW_MIXES)
def test_the_kernels_rule_computes_live_blocks(n_live, trips):
    """What the counter ``gdn_positions_computed`` reads where the chip's
    kernel runs the rule (``_rule_positions(kernel=True)``): one position
    for a rider, a prefilling row's live blocks of 64 (a second block none
    of whose positions is live is not computed), nothing for a row that
    sits out; never more than ``_rule_by_rows`` computes, never fewer than
    the live positions. A narrow step is the recurrence on every row on
    either path."""
    n = jnp.asarray(n_live, jnp.int32)
    want = sum(1 if x == 1 else -(-x // 64) * 64 for x in n_live)
    got = int(model_runner._rule_positions(n, WIDTH, kernel=True))
    assert got == want
    assert sum(n_live) <= got <= int(model_runner._rule_positions(n, WIDTH))
    assert int(model_runner._rule_positions(n, WIDTH)) == \
        trips * 2 * WIDTH + SLOTS
    assert int(model_runner._rule_positions(n, 1, kernel=True)) == SLOTS


# ---- the served path against the reference ---------------------------------


def test_served_path_matches_the_reference(whole, reference):
    """Chunked prefill (a prompt of two chunks beside a short one), then
    decode through the pages and the carried state, against the reference's
    logits at every position that ends a step: logits, not tokens, through
    BOTH programs' bodies (the steps after the prompts are narrow). The
    routed block's counters: every live token makes ``num_experts_per_tok``
    selections a layer, each a held expert's row."""
    model, params = whole
    e = engine(model, params)
    seqs = sequences()
    want = {s: reference.logits_rows(params, ids, np.arange(len(ids)), CONFIG)
            for s, (ids, _) in seqs.items()}
    assert max(np.abs(w).max() for w in want.values()) > 2.0
    worst, narrow = 0.0, 0
    for logits, last, work, live, _ in paged_steps(e, params, seqs):
        for s, at in last.items():
            worst = max(worst, np.abs(logits[s] - want[s][at]).max())
        assert work[0] == live * CONFIG["num_experts_per_tok"] * LAYERS
        narrow += logits.shape[0] == SLOTS and live <= len(seqs)
    assert narrow >= 5
    assert worst < LOGIT_TOL, worst


@pytest.mark.parametrize("fault", ["carry", "tail", "offset", "gate"])
def test_a_planted_fault_shows_in_the_logits(whole, reference, fault):
    """What the tolerance is worth: a state that is not carried from chunk
    to chunk, a tail that is, weights w for 1 + w, an output gate left open
    each move the served logits by more than ``FAULT_FLOOR``."""
    model, params = whole
    e = engine(model, params)
    seqs = sequences()
    bad = dict(params)      # what the reference is given
    if fault == "offset":
        bad["final_norm"] = {"scale": params["final_norm"]["scale"] - 1.0}
    elif fault == "gate":
        g3 = params["layers"]["g3"]
        wq = g3["attn"]["wq"]
        shut = wq.at[..., wq.shape[-1] // 2:].set(0.0)
        bad["layers"] = {**params["layers"], "g3": {
            **g3, "attn": {**g3["attn"], "wq": shut}}}
    want = {s: reference.logits_rows(bad, ids, np.arange(len(ids)), CONFIG)
            for s, (ids, _) in seqs.items()}
    steps = paged_steps(e, params, seqs)
    worst = 0.0
    zeros = None
    for logits, last, _, _, recurrent in steps:
        for s, at in last.items():
            worst = max(worst, np.abs(logits[s] - want[s][at]).max())
        if fault in ("carry", "tail") and zeros is None:
            zeros = jax.tree.map(jnp.zeros_like, recurrent)
            # what a program that lost the carry would read after chunk 1
            lost = (zeros[0], recurrent[1]) if fault == "carry" \
                else (recurrent[0], zeros[1])
            fwd = forward_of(e.runner)
            ids, plen = seqs[0]
            n = plen - WIDTH
            chunk = np.zeros((SLOTS, WIDTH), np.int32)
            pos = np.full((SLOTS, WIDTH), -1, np.int32)
            chunk[0, :n], pos[0, :n] = ids[WIDTH:plen], WIDTH + np.arange(n)
            valid = np.zeros((SLOTS,), np.int32)
            valid[0] = n
            tables = np.zeros((SLOTS, SEQ // PAGE), np.int32)
            tables[0] = 1 + np.arange(tables.shape[1])
            got = fwd(params, chunk, pos, tables, valid,
                      jnp.zeros_like(e.kv.k), jnp.zeros_like(e.kv.v),
                      recurrent=lost)[0]
            worst = np.abs(np.asarray(got)[0] - want[0][plen - 1]).max()
            break
    assert worst > FAULT_FLOOR, worst


def test_one_prompt_however_it_is_cut(whole):
    """A prompt in one chunk, in chunks of 96 and in chunks of 40 gives the
    state the whole prompt gives at once and the same logits for its first
    token, to rounding: the chunked rule's blocks fall elsewhere, so the
    same sums are made in another order through 6 layers (measured 1.1e-3
    on logits of ~3, where a lost carry reads whole units)."""
    cut_tol = 3e-3
    model, params = whole
    rng = np.random.default_rng(21)
    ids = rng.integers(0, 256, 150).astype(np.int32)
    got = {}
    for width in (150, 96, 40):
        e = engine(model, params, prefill_chunk_size=width)
        for logits, last, _, _, recurrent in paged_steps(
                e, params, {2: (ids, 150)}, width=width):
            pass
        assert last == {2: 149}
        got[width] = (logits[2], np.asarray(recurrent[0][:, 2]),
                      np.asarray(recurrent[1][:, :, 2]))
    for width in (96, 40):
        assert np.abs(got[width][0] - got[150][0]).max() < cut_tol
        assert np.abs(got[width][1] - got[150][1]).max() < cut_tol
        np.testing.assert_allclose(got[width][2], got[150][2], atol=cut_tol)
    assert np.abs(got[150][1]).max() > 0.1


# ---- frames: the state on the donated carry --------------------------------


def frames(e, arrivals, plan, width=WIDTH):
    """Drive ``DeviceSlotTable`` by hand: admit ``arrivals`` [(uid, tokens,
    limit)] at once, dispatch the frames of ``plan`` [(width, steps)].
    Returns the table and the tokens each uid emitted."""
    slots = DeviceSlotTable(
        SLOTS, prompt_width=width, table_width=1, rng=jax.random.PRNGKey(0),
        n_stats=e.runner.n_stats,
        recurrent=e.runner.recurrent_shapes(SLOTS))
    out = {}

    def admit(batch):
        items = []
        for uid, toks, limit in batch:
            seq = e.state.get_or_create_sequence(uid)
            assert e.state.ensure_capacity(seq, len(toks) + limit + 1)
            items.append((uid, seq, toks, limit, 0.0, None))
            out[uid] = []
        slots.ensure_widths(max(len(t) for _, t, _ in batch),
                            max(len(i[1].blocks) for i in items), SEQ,
                            SEQ // PAGE)
        slots.admit(items)

    def run(w, steps):
        toks, emit = slots.dispatch_frame(e.runner, e.params, e.kv, w, steps,
                                          True)
        emitted, finished = slots.absorb(np.asarray(toks), np.asarray(emit),
                                         w)
        for uid, got in emitted.items():
            out[uid] += got
        for uid in finished:
            slots.retire(uid)
            e.state.flush_sequence(uid)

    admit(arrivals)
    for w, steps in plan:
        run(w, steps)
    return slots, out, admit, run


def test_state_rides_steps_and_frames(whole):
    """One prompt of three chunks in ONE frame of three steps, and in three
    frames of one step: the same state to the bit (the same program, the
    same steps), the same tokens afterwards; a rider beside it moves its
    state by one position a step and a frozen slot's does not move at all
    (bitwise)."""
    model, params = whole
    rng = np.random.default_rng(31)
    long = rng.integers(0, 256, 250).astype(np.int32)
    short = rng.integers(0, 256, 20).astype(np.int32)
    results = []
    for plan in ([(WIDTH, 3)], [(WIDTH, 1)] * 3):
        e = engine(model, params, frame_steps=4)
        slots, out, admit, run = frames(e, [(7, long, 4), (8, short, 6)],
                                        plan)
        state, tail = (np.asarray(a) for a in slots.recurrent)
        run(1, 2)
        results.append((state, tail, {u: list(t) for u, t in out.items()}))
    (s1, t1, o1), (s3, t3, o3) = results
    assert np.array_equal(s1, s3) and np.array_equal(t1, t3)
    assert o1 == o3 and len(o1[7]) == 3 and len(o1[8]) == 5
    # slot 0 holds the long prompt, slot 1 the rider, the rest never moved
    assert np.abs(s1[:, 0]).max() > 0.1 and np.abs(s1[:, 1]).max() > 0.01
    assert not s1[:, 2:].any() and not t1[:, :, 2:].any()


def test_a_rider_moves_by_one_position_and_a_frozen_slot_not_at_all(whole):
    """A wide step with a prefilling row, a rider and a frozen slot (done,
    its state left by a tenant that finished): the rider's state after the
    step is the state one recurrent update gives (the narrow program's: the
    wide step runs the same recurrence on it), the frozen slot's state and
    tail are what they were, to the bit."""
    model, params = whole
    rng = np.random.default_rng(41)
    first = rng.integers(0, 256, 30).astype(np.int32)
    rider = rng.integers(0, 256, 25).astype(np.int32)
    e = engine(model, params, frame_steps=4)
    # slot 0 finishes (limit 1) and freezes with its state in place
    slots, out, admit, run = frames(e, [(1, first, 1), (2, rider, 8)],
                                    [(WIDTH, 1)])
    assert len(out[1]) == 1 and slots.free_slots() == SLOTS - 1
    frozen = [np.asarray(a) for a in slots.recurrent]
    assert np.abs(frozen[0][:, 0]).max() > 0.01
    # a narrow step moves the rider alone: the reference for the wide one
    twin = engine(model, params, frame_steps=4)
    tslots, tout, tadmit, trun = frames(twin, [(1, first, 1), (2, rider, 8)],
                                        [(WIDTH, 1), (1, 1)])
    # here a new long prompt arrives in slot 2 beside the rider in slot 1:
    # slot 0 stays free (a frozen row) only if it is not handed out, so the
    # new tenant is admitted while slot 0 is still marked taken
    slots.uid_of_slot[0] = 99
    admit([(3, rng.integers(0, 256, 200).astype(np.int32), 2)])
    slots.uid_of_slot[0] = -1
    run(WIDTH, 1)
    after = [np.asarray(a) for a in slots.recurrent]
    narrow = [np.asarray(a) for a in tslots.recurrent]
    assert np.array_equal(after[0][:, 0], frozen[0][:, 0])
    assert np.array_equal(after[1][:, :, 0], frozen[1][:, :, 0])
    # both are the recurrence now, on inputs that differ in their last bit:
    # the wide step projects the rider's token in a packed buffer beside
    # the prompt's, the narrow one in a batch of its own, and the products
    # round elsewhere (the tails, plain copies of those inputs, differ by
    # as much). Measured 4.8e-7 through the six layers (4.5e-7 while the
    # wide step ran the chunked form on the rider: the inputs' last bits
    # set the gap, not the rule's form)
    assert np.abs(after[0][:, 1] - narrow[0][:, 1]).max() < 2e-6
    np.testing.assert_allclose(after[1][:, :, 1], narrow[1][:, :, 1],
                               atol=1e-5)
    assert np.abs(after[0][:, 1] - frozen[0][:, 1]).max() > 1e-3
    assert out[2][:2] == tout[2][:2]


def test_a_second_tenant_reads_a_fresh_slot(whole):
    """A slot reused by a second tenant gives the tokens a fresh engine
    gives: admission zeroes the row's state and tail in its one program."""
    model, params = whole
    rng = np.random.default_rng(51)
    a = rng.integers(0, 256, 120).astype(np.int32)
    b = rng.integers(0, 256, 70).astype(np.int32)
    e = engine(model, params, max_ragged_batch_size=1)
    both = dict(e.serve(iter([[(1, a)], [(2, b)]]), max_new_tokens=5,
                        frame_slots=1))
    fresh = dict(engine(model, params, max_ragged_batch_size=1).serve(
        iter([[(2, b)]]), max_new_tokens=5, frame_slots=1))
    assert list(both[2]) == list(fresh[2]) and len(both[1]) == 5
    c = e.telemetry.counters
    assert c["gdn_positions"] == 6 * (120 + 70 + 2 * 4)
    assert c["gdn_state_rw"] == 6 * e.telemetry.counters["active_row_steps"]
    # computed: the one row's chunk whole (one trip of one row: the table
    # has no second) in the three wide steps that took more than one prompt
    # token (96 + 24 of the first prompt, 70 of the second), and the
    # recurrence on it in every step of every frame
    assert c["wide_steps"] == 3
    assert c["gdn_positions_computed"] == 6 * (3 * 96 + c["frame_steps"])
    assert c["gdn_positions"] <= c["gdn_positions_computed"]
    assert c["recurrent_bytes_in_use_sum"] > 0
    assert c["kv_positions_read_layers_wide"] > 0
    text = e.telemetry.render_prometheus()
    for name in ("gdn_positions", "gdn_state_rw", "gdn_positions_computed",
                 "recurrent_bytes_in_use"):
        assert f"ds_serving_{name}" in text, name


@pytest.fixture(scope="module")
def served(whole):
    """Four prompts through serve() on two slots: (prompts, tokens by uid)."""
    model, params = whole
    rng = np.random.default_rng(61)
    prompts = {u: rng.integers(0, 256, n).astype(np.int32)
               for u, n in ((1, 150), (2, 30), (3, 97), (4, 64))}
    e = engine(model, params, max_ragged_batch_size=2)
    return prompts, dict(e.serve(iter([list(prompts.items())]),
                                 max_new_tokens=6, frame_slots=2))


def test_served_tokens_are_the_references(whole, reference, served):
    """serve() end to end (admission, wide frames with a rider, narrow
    frames, slots reused): every emitted token is the reference's greedy
    choice to within the tolerance."""
    model, params = whole
    prompts, out = served
    for u, p in prompts.items():
        gen = np.asarray(out[u])
        ids = list(p) + list(gen[:-1])
        rows = np.arange(len(p) - 1, len(p) - 1 + len(gen))
        lg = reference.logits_rows(params, ids, rows, CONFIG)
        assert (lg.max(-1) - lg[np.arange(len(gen)), gen]).max() < LOGIT_TOL


# ---- the share --------------------------------------------------------------


def test_the_shares_add_up(whole, reference):
    """The routed parts of the 4 shares of 4 experts (experts 0-3, 4-7,
    ...), with the shared expert counted once, add up to the uncut layer's
    block: in the program (each share a model that holds experts ``first``
    ..) and in the reference. The picks' weights are divided by the sum over
    ALL of a token's picks, held here or not."""
    model, params = whole
    rng = np.random.default_rng(7)
    m = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.float32)
    group, at = params["layers"]["g1"]["mlp"], 1
    mlp = jax.tree.map(lambda w: w[at], group)
    shared = np.asarray(reference.shared_expert(m, group, at))
    uncut = np.asarray(reference.routed_part(m, group, at, CONFIG)) + shared
    weights, _, _ = reference.route(m, mlp["router"], top_k=4)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)
    assert np.linalg.norm(shared) > 0.05 * np.linalg.norm(uncut)
    assert np.linalg.norm(uncut - shared) > 0.05 * np.linalg.norm(uncut)
    total_prog, total_ref = -3 * shared, shared.copy()
    for first in range(0, EXPERTS, 4):
        cfg = tiny_qwen(4, first).cfg
        share = {n: w[first:first + 4] if n in L.EXPERT_MATRICES else w
                 for n, w in mlp.items()}
        out, _ = L.apply_moe_grouped(share, m, cfg)     # its shared expert too
        total_prog = total_prog + np.asarray(out)
        stacked = jax.tree.map(lambda w: w[None], share)
        total_ref = total_ref + np.asarray(reference.routed_part(
            m, stacked, 0, CONFIG, first=first))
    scale = np.linalg.norm(uncut)
    assert np.linalg.norm(total_ref - uncut) / scale < 1e-5
    assert np.linalg.norm(total_prog - uncut) / scale < 1e-5
    out, _ = L.apply_moe_grouped(mlp, m, model.cfg)
    assert np.linalg.norm(np.asarray(out) - uncut) / scale < 1e-5


def test_a_share_is_served_as_the_reference_computes_it(whole, reference):
    """Experts 4..7 of 16 held: the served logits are the reference's over
    the same share."""
    _, params = whole
    params = share_of(params, 4, 4)
    e = engine(tiny_qwen(4, 4), params)
    seqs = {1: sequences()[3]}
    config = dict(CONFIG, experts_first=4)
    want = {s: reference.logits_rows(params, ids, np.arange(len(ids)), config)
            for s, (ids, _) in seqs.items()}
    worst, absent = 0.0, 0
    for logits, last, work, live, _ in paged_steps(e, params, seqs):
        for s, at in last.items():
            worst = max(worst, np.abs(logits[s] - want[s][at]).max())
        rows, _, _, _, picked, zeros, away = work
        assert picked == live * 4 * LAYERS and rows + away == picked
        assert zeros == 0
        absent += away
    assert absent > 0
    assert worst < LOGIT_TOL, worst


# ---- names: what the trace's reduction finds --------------------------------


@pytest.mark.parametrize("width", [WIDTH, 1])
def test_frame_lowering_names_the_mixers_scopes(width, whole, monkeypatch):
    """Both frame programs carry the linear mixer's scopes INSIDE ``attn``
    (``scope_reduce.scope_of`` takes the innermost scope it knows on an
    op's path, so ``scope_coverage`` counts them without knowing them, and
    ``work_gdn`` finds each by name), the output gate inside ``attn_out``,
    the kernels every model of K and V by head has, and the routed block's
    scopes."""
    import re
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    model, params = whole
    e = engine(model, params)
    slots = DeviceSlotTable(SLOTS, prompt_width=WIDTH, table_width=16,
                            rng=jax.random.PRNGKey(0),
                            n_stats=e.runner.n_stats,
                            recurrent=e.runner.recurrent_shapes(SLOTS))
    lowered = e.runner._build_frame_loop().lower(
        e.params, slots.prompts, slots.prompt_lens, slots.limits,
        slots.eos_ids, slots.temps, slots.tables, slots.cached,
        slots.produced, slots.last_tok, slots.done, slots.poison,
        slots.nonfinite, slots.stats, slots.rng, e.kv.k, e.kv.v,
        recurrent=slots.recurrent, width=width, steps=2, greedy=True)
    text = lowered.as_text(dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_norm_gate",
                  "gdn_out"):
        under = [n for n in names if f"/{scope}/" in "/" + n]
        assert under, scope
        assert all("/attn/" in "/" + n.split(f"/{scope}/")[0] + "/"
                   for n in under), scope
    assert any("attn_out/attn_gate/" in n for n in names)
    # a wide step's rule by rows: the loop, every op of its body (the
    # gathers, the chunked form's products, the writes) and the recurrence
    # that runs beside it sit under ``gdn_scan`` inside ``attn``, so
    # ``gdn_share`` and ``gdn_scan_roofline`` see all of it; a narrow step
    # has the recurrence and no loop
    mixer = [("/" + n).split("/attn/", 1)[1]
             for joined in names for n in joined.split(";")
             if "/attn/" in "/" + n]
    loops = [n for n in mixer if "while" in n.split("/")]
    chunked = [n for n in names if "bhnij,bhnjk->bhnik" in n]
    recurrence = [n for n in mixer if "bhkv,bhk->bhv" in n]
    assert bool(loops) == bool(chunked) == (width > 1)
    assert all((n + "/").startswith("gdn_scan/while/") for n in loops)
    assert all("/attn/gdn_scan/while/body/gdn_scan/" in "/" + n
               for n in chunked)
    assert recurrence and all(n.startswith("gdn_scan/")
                              and "while" not in n.split("/")
                              for n in recurrence)
    parts = {p for n in names for p in n.split("/")}
    assert {f"paged_attn_c{width}", f"kv_commit_c{width}", "moe_route",
            "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
            "frame_plan", "sample", "lm_head", "embed"} <= parts


# ---- the preset and the validator -------------------------------------------


@pytest.mark.parametrize("layers", [4, 8, 12])
def test_a_cut_in_depth_keeps_three_linear_layers_to_one_full(layers):
    cfg = get_config("qwen3-next-80b-a3b", num_layers=layers)
    mixers = cfg.layer_mixers()
    assert mixers == ("linear", "linear", "linear", "full") * (layers // 4)
    assert cfg.linear_layers == 3 * layers // 4
    assert cfg.cache_layers == layers // 4
    model = build_model(cfg)
    assert [tag for tag, _ in model._groups] == list(mixers[:4])
    assert [len(idx) for _, idx in model._groups] == [layers // 4] * 4
    with pytest.raises(ValueError, match="does not tile"):
        get_config("qwen3-next-80b-a3b", num_layers=layers + 1).layer_mixers()


def test_the_preset_holds_the_published_sizes():
    cfg = get_config("qwen3-next-80b-a3b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.vocab_size) == \
        (2048, 48, 151936)
    assert (cfg.num_heads, cfg.kv_heads, cfg.dims_per_head) == (16, 2, 256)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel) == (16, 32, 128, 128, 4)
    assert cfg.linear_channels == 8192
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_ffn_size,
            cfg.moe_shared_expert_size) == (512, 10, 512, 512)
    assert cfg.rotary_pct == 0.25 and cfg.rope_theta == 1e7
    assert L.rope_frequencies(cfg).shape == (32,)       # 64 lanes rotate
    assert cfg.norm_unit_offset and cfg.attn_output_gate
    shapes = jax.eval_shape(build_model(cfg.replace(num_layers=4)).init,
                            jax.random.PRNGKey(0))["layers"]
    assert shapes["g0"]["attn"]["w_qkvz"].shape == (1, 2048, 12288)
    assert shapes["g0"]["attn"]["w_ba"].shape == (1, 2048, 64)
    assert shapes["g3"]["attn"]["wq"].shape == (1, 2048, 16, 512)
    assert shapes["g3"]["mlp"]["router"].shape == (1, 2048, 512)


@pytest.mark.parametrize("option,match", [
    (dict(tp=2), "tp=2"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_swap_dir="/nonexistent"), "swap tier"),
    (dict(role="prefill"), "handoff"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(nonfinite_policy="repair"), "repair"),
    ("draft", "draft"),
    ("module", "prediction module"),
])
def test_the_validator_refuses(option, match):
    """What a recurrent state cannot be served with yet is refused at
    engine build, each with its reason."""
    kw, build = {}, {}
    if option == "draft":
        build["draft_model"] = build_model("tiny")
    elif option == "module":
        kw["num_nextn_predict_layers"] = 1
    else:
        build.update(option)
    model = tiny_qwen(layers=4, **kw)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                dtype="float32", **{**SHAPE, **{k: v for k, v in build.items()
                                                if k != "draft_model"}}),
            params=params, max_seq_len=SEQ,
            **{k: v for k, v in build.items() if k == "draft_model"})


def test_the_other_entry_points_say_where_the_state_lives(whole, served):
    """put() / step() walk the forward without the frame programs' carry:
    refused with the reason, not served wrongly. generate() is a closed
    batch through serve(), so it serves, and its tokens are the served
    run's (which are the reference's)."""
    model, params = whole
    e = engine(model, params, max_ragged_batch_size=2)
    e.put([0], [np.arange(10, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="serve\\(\\)"):
        e.step()
    e.flush([0])
    prompts, out = served
    got = e.generate(list(prompts.values()), max_new_tokens=6)
    for u, tokens in zip(prompts, got):
        np.testing.assert_array_equal(tokens, out[u])
    assert e.kv.free_blocks == e.kv.num_blocks - 1
