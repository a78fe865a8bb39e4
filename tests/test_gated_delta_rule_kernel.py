"""The gated delta rule's kernel (``ops/pallas/gated_delta_rule.py``),
interpreted, against the path every other backend runs.

The oracle is ``model_runner._rule_by_rows`` over ``layers.gdn_rule``: the
rows told apart in XLA, riders through the recurrence, prefilling rows two
a trip through the chunked form. The kernel takes the same operands (the
convolution's output, the gates' inputs, the states of every linear layer)
and must give the same outputs and states to float32 rounding (the
tolerance of ``test_qwen3_next_serving.test_chunked_rule_is_the_recurrence``:
another order of the same sums), zeros wherever no live position is, and
every state it was not asked to move TO THE BIT: it is written in place.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import layers as L
from deepspeed_tpu.ops.pallas import gated_delta_rule

C, D, LAYERS, LAYER = 128, 128, 2, 1
#: float32 on both sides, summed in another order
TOL = 2e-5

#: live positions a row (of C = 128: two blocks of 64), key heads, u's dtype
ROW_MIXES = {
    # frozen, a rider, and a prefilling row at every edge of a block
    "every kind": ([0, 1, 2, 37, 64, 65, 100, 128], 2, jnp.float32),
    # every grid step is past the list: one state block is held, none moved
    "nothing listed": ([0, 0, 0], 1, jnp.float32),
    "riders alone": ([1, 0, 1, 1], 1, jnp.float32),
    # two of five rows listed: three steps past the count, for rows ahead
    # of, between and behind the listed ones
    "a list shorter than the grid": ([0, 128, 0, 1, 0], 2, jnp.float32),
    "every row prefills": ([128, 65, 3], 1, jnp.float32),
    # the activations' dtype of the benchmark's cell
    "bfloat16 activations": ([1, 100, 0, 64], 2, jnp.bfloat16),
}


def operands(n_live, hk, dtype, seed):
    """A wide step's operands as ``linear_layer`` holds them behind the
    convolution, every row's live positions first; garbage (finite, as the
    convolution's is) at the dead ones."""
    hv, b = 2 * hk, len(n_live)
    cfg = types.SimpleNamespace(
        linear_num_key_heads=hk, linear_key_head_dim=D,
        linear_num_value_heads=hv, linear_value_head_dim=D)
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    u = normal(b, C, 2 * hk * D + hv * D).astype(dtype)
    b_in, a_in = normal(b, C, hv), normal(b, C, hv)
    # the DeltaNet paper's draw (``layers.init_gdn``): some heads forget
    # within a block and some carry across it
    small = {"A_log": jnp.log(jnp.asarray(rng.uniform(1e-3, 16.0, (hv,)),
                                          jnp.float32)),
             "dt_bias": jnp.asarray(rng.uniform(-6.0, -2.0, (hv,)),
                                    jnp.float32)}
    state = normal(LAYERS, b, hv, D, D)
    at = jnp.arange(C)[None]
    positions = jnp.where(at < jnp.asarray(n_live)[:, None], at + 11, -1)
    return cfg, small, u, b_in, a_in, state, positions


@pytest.mark.parametrize("mix", list(ROW_MIXES))
def test_kernel_is_the_rule_by_rows(mix):
    n_live, hk, dtype = ROW_MIXES[mix]
    cfg, small, u, b_in, a_in, state, positions = operands(
        n_live, hk, dtype, seed=len(mix))
    pad = positions < 0

    def rule(u, b_in, a_in, pad, state):
        q, k, v = L.gdn_split(u, cfg)
        return L.gdn_rule(q, k, v, *L.gdn_gates(small, b_in, a_in, ~pad),
                          state)

    @jax.jit
    def oracle(u, b_in, a_in, state):
        return model_runner._rule_by_rows(
            model_runner._row_plan(positions), rule, u, b_in, a_in, pad,
            state[LAYER])

    @jax.jit
    def kernel(u, b_in, a_in, state):
        beta, g = L.gdn_gates(small, b_in, a_in, ~pad)
        return gated_delta_rule.gdn_rule_rows(
            u, beta, g, state, jnp.asarray(LAYER),
            model_runner._row_plan(positions)[4], interpret=True)

    want, want_state = map(np.asarray, oracle(u, b_in, a_in, state))
    got, got_state = map(np.asarray, kernel(u, b_in, a_in, state))
    state = np.asarray(state)
    assert got.shape == want.shape and got_state.shape == state.shape
    # the other layer's states, and the rows that sat the step out: the bit
    assert np.array_equal(got_state[1 - LAYER], state[1 - LAYER])
    for row, n in enumerate(n_live):
        # zeros wherever no live position is, garbage nowhere
        assert np.array_equal(got[row, n:], np.zeros_like(got[row, n:])), row
        if n == 0:
            assert np.array_equal(got_state[LAYER, row], state[LAYER, row])
            continue
        scale = float(np.abs(want[row, :n]).max())
        assert scale > 0.05, (row, scale)
        assert np.abs(got[row, :n] - want[row, :n]).max() \
            < TOL * max(scale, 1.0), row
        assert np.abs(got_state[LAYER, row] - want_state[row]).max() < TOL, row
        assert np.abs(got_state[LAYER, row] - state[LAYER, row]).max() > 0.1
    # the oracle's own contract, which the kernel's is: zeros at the rows
    # that sat out and behind a rider's one position
    for row, n in enumerate(n_live):
        if n <= 1:
            assert not want[row, n:].any()


@pytest.mark.parametrize("n_live,rows,count", [
    ([0, 1, 0, 96, 1], [1, 3, 4, 0, 2], 3),
    ([0, 0, 0], [0, 1, 2], 0),
    ([5, 1, 128], [0, 1, 2], 3),
])
def test_row_list_names_the_rows_that_hold_a_position(n_live, rows, count):
    """Listed rows first, in slot order, then the others; each listed row's
    live count, 0 past them."""
    got_rows, got_n, chunk, got_count = gated_delta_rule.row_list(
        jnp.asarray(n_live, jnp.int32))
    assert got_rows.tolist() == rows and int(got_count) == count
    assert got_n.tolist() == [n_live[r] for r in rows]
    assert all(n > 0 for n in got_n.tolist()[:count])
    assert not any(got_n.tolist()[count:])
    # whose whole chunk a step holds: a prefilling row's own; else the one
    # before it in the list (at its LAST head group, where the walk left
    # it), or the first one's FIRST where none is before
    prefills = [i for i, n in enumerate(got_n.tolist()) if n > 1]
    for i, (row, last) in enumerate(zip(*chunk.tolist())):
        before = [p for p in prefills if p <= i]
        want = before[-1] if before else (prefills or [0])[0]
        assert (row, last) == (rows[want], int(bool(before))), i


@pytest.mark.parametrize("c,hk,hv,dk,dv,takes", [
    (128, 16, 32, 128, 128, True),      # the benchmark's cell
    (64, 1, 2, 128, 128, True),
    (256, 2, 4, 256, 256, True),
    (96, 16, 32, 128, 128, False),      # no whole blocks of 64
    (128, 2, 4, 8, 8, False),           # heads narrower than a lane tile
    (128, 16, 32, 128, 256, False),     # no square state
    (1, 16, 32, 128, 128, False),       # a narrow step: the recurrence
])
def test_kernel_takes_whole_blocks_of_square_lane_wide_heads(c, hk, hv, dk,
                                                             dv, takes):
    """What the runner asks before it traces the kernel; every other shape
    keeps ``_rule_by_rows``. Off the chip nothing does."""
    assert gated_delta_rule.supported(c, hk, hv, dk, dv) == takes
    cfg = types.SimpleNamespace(
        linear_layers=6, linear_num_key_heads=hk, linear_key_head_dim=dk,
        linear_num_value_heads=hv, linear_value_head_dim=dv)
    assert not model_runner._rule_kernel_runs(cfg, c)


def test_a_step_takes_as_many_heads_as_its_state_block_holds():
    assert gated_delta_rule.pairs_a_step(16, 32, 128, 128) == 4
    assert gated_delta_rule.pairs_a_step(2, 4, 128, 128) == 2
    assert gated_delta_rule.pairs_a_step(3, 6, 256, 256) == 1


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the programs it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_every_product_is_float32_at_the_highest_precision():
    """The configuration states float32: every ``dot_general`` the kernel
    traces (both branches, every block) takes float32 operands at
    ``Precision.HIGHEST``, which Mosaic lowers to
    ``contract_precision<fp32>``, and accumulates in float32; nothing in it
    is cast to a narrower float."""
    n_live, hk, _ = ROW_MIXES["every kind"]
    cfg, small, u, b_in, a_in, state, positions = operands(
        n_live, hk, jnp.bfloat16, seed=1)
    beta, g = L.gdn_gates(small, b_in, a_in, positions >= 0)
    jaxpr = jax.make_jaxpr(lambda *a: gated_delta_rule.gdn_rule_rows(
        *a, jnp.asarray(LAYER), model_runner._row_plan(positions)[4],
        interpret=True))(u, beta, g, state)
    calls = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and calls[0].params["name"] == f"gdn_rule_c{C}"

    eqns = list(_walk(calls[0].params["jaxpr"]))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) > 10
    for dot in dots:
        assert all(v.aval.dtype == jnp.float32 for v in dot.invars)
        assert dot.params["preferred_element_type"] == jnp.float32
        assert set(jax.tree.leaves(dot.params["precision"])) == \
            {jax.lax.Precision.HIGHEST}
    narrowed = [e for e in eqns if e.primitive.name == "convert_element_type"
                and e.params["new_dtype"] in (jnp.bfloat16, jnp.float16)]
    assert not narrowed


def test_a_wide_forward_runs_the_kernel_where_the_chip_would(mesh_8dp,
                                                             monkeypatch):
    """The runner's forward over a wide chunk of a small Qwen3-Next (one
    period: three linear layers of 128 x 128 states and one of attention)
    whose rows prefill, ride and sit out, with the chip's choice of kernels
    (``_use_pallas_paged``; off the chip they run interpreted): the delta
    rule's kernel is traced once a linear layer and nowhere in the narrow
    step, and logits, states and tails are the other path's to float32
    rounding, a frozen row's state and tail to the bit."""
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.models import build_model, get_config
    cfg = get_config(
        "qwen3-next-80b-a3b", vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
        moe_shared_expert_size=32, num_experts=8, moe_router_experts=8,
        num_experts_per_tok=2, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=D,
        linear_value_head_dim=D, max_seq_len=256, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    slots, width, page = 4, 64, 8
    runner = PagedModelRunner(model, page, 256 // page)
    rng = np.random.default_rng(3)
    n_live = [64, 1, 0, 23]
    ids = jnp.asarray(rng.integers(0, 256, (slots, width)), jnp.int32)
    at = np.arange(width)[None]
    positions = jnp.asarray(
        np.where(at < np.asarray(n_live)[:, None], at + 8, -1), jnp.int32)
    tables = jnp.asarray(1 + np.arange(slots * 32).reshape(slots, 32),
                         jnp.int32)
    pool = jnp.zeros((1, 2, 1 + slots * 32, page, 16), jnp.float32)
    recurrent = tuple(
        jnp.asarray(rng.standard_normal(shape), dtype)
        for shape, dtype in runner.recurrent_shapes(slots))

    def forward(ids, positions):
        return runner._forward(params, ids, positions, tables,
                               jnp.asarray(n_live, jnp.int32), pool, pool,
                               recurrent=recurrent)

    def traced(ids, positions):
        # (a function of its own a trace: JAX keeps a function's traces,
        # and the choice of kernels is no part of their key)
        return jax.make_jaxpr(lambda *a: forward(*a))(ids, positions)

    def kernels(closed):
        return sum(eqn.primitive.name == "pallas_call"
                   and eqn.params["name"].startswith("gdn_rule_c")
                   for eqn in _walk(closed.jaxpr))

    want = jax.jit(lambda *a: forward(*a))(ids, positions)
    assert kernels(traced(ids, positions)) == 0
    monkeypatch.setattr(model_runner, "_use_pallas_paged", lambda: True)
    assert model_runner._rule_kernel_runs(cfg, width)
    assert not model_runner._rule_kernel_runs(cfg, 1)
    assert kernels(traced(ids, positions)) == cfg.linear_layers == 3
    assert kernels(traced(ids[:, :1], positions[:, :1])) == 0
    got = jax.jit(lambda *a: forward(*a))(ids, positions)
    (logits, _, _, state, tail), (w_logits, _, _, w_state, w_tail) = \
        (x[:3] + tuple(x[-1]) for x in (got, want))
    for row, n in enumerate(n_live):
        if n:
            assert np.abs(logits[row] - w_logits[row]).max() < 1e-4, row
            assert np.abs(state[:, row] - recurrent[0][:, row]).max() > 1e-3
    assert np.abs(state - w_state).max() < TOL
    # (a later layer's inputs carry the first one's rounding)
    assert np.abs(tail - w_tail).max() < 1e-4
    assert np.array_equal(np.asarray(state[:, 2]),
                          np.asarray(recurrent[0][:, 2]))
    assert np.array_equal(np.asarray(tail[:, :, 2]),
                          np.asarray(recurrent[1][:, :, 2]))
