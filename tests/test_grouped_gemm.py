"""The grouped-product kernel (``ops/pallas/grouped_gemm.py``) in interpret
mode on the CPU, against ``jax.lax.ragged_dot``: the cells' (K, N) with the
rows cut, groups that are empty, one that holds half the rows, group edges off
every tile edge, rows past the groups, the stacked ``layer=`` form, the
derivative through ``moe_expert_ffn`` (one layer's experts and the stack),
the expert-parallel path inside its ``shard_map``, where the kernel runs and
where ``ragged_dot`` stays, and the tile rule at every shape the three routed
cells run."""


import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2.telemetry import pack_ladder
from deepspeed_tpu.ops.pallas import grouped_gemm as G


@pytest.fixture
def kernel_path(monkeypatch):
    """The chip's path on the CPU, through the file's one seam: ``_on_chip``
    says yes and ``grouped_mm`` is interpreted. Returns the list the calls
    are counted in."""
    from deepspeed_tpu.utils import groups
    groups.reset_mesh()
    calls, real = [], G.grouped_mm
    monkeypatch.setattr(G, "_on_chip", lambda: True)
    monkeypatch.setattr(G, "grouped_mm", lambda *a, **kw: (
        calls.append(1), real(*a, interpret=True, **kw))[1])
    yield calls
    groups.reset_mesh()


def _sizes(rng, rows, groups, draw):
    """Group sizes summing to ``rows``, no edge on a multiple of 8."""
    if draw == "half":          # one group holds half the rows: max / mean 5
        groups = 10
        rest = rng.multinomial(rows - rows // 2 - (groups - 1),
                               np.ones(groups - 1) / (groups - 1)) + 1
        sizes = np.insert(rest, 3, rows // 2)
    elif draw == "empty":       # every other group empty, the first and last too
        live = rng.multinomial(rows - groups // 2,
                               np.ones(groups // 2) / (groups // 2)) + 1
        sizes = np.zeros(groups, np.int64)
        sizes[1:-1:2] = live[:len(sizes[1:-1:2])]
        sizes[1] += rows - sizes.sum()
    else:                       # "odd": every group an odd size
        sizes = 2 * rng.multinomial((rows - groups) // 2,
                                    np.ones(groups) / groups) + 1
        sizes[0] += rows - sizes.sum()
    assert sizes.sum() == rows and (sizes >= 0).all()
    return sizes.astype(np.int32)


def _operands(rng, rows, k, n, groups, layers=None):
    tokens = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    shape = (groups, k, n) if layers is None else (layers, groups, k, n)
    weights = jnp.asarray(rng.normal(size=shape) / np.sqrt(k), jnp.bfloat16)
    return tokens, weights


def _gap(got, want, rows):
    """The largest gap over the rows in groups, in units of the result's
    largest value."""
    got = np.asarray(got[:rows], np.float32)
    want = np.asarray(want[:rows], np.float32)
    return np.abs(got - want).max() / np.abs(want).max()


# the cells' (K, N): Mellum2's gate / up and down, OLMoE's, LongCat's whose
# 25 MB matrix is tiled in K and N here (the budget is cut so the rule must)
@pytest.mark.parametrize("k,n,groups,budget", [
    (2304, 896, 8, None), (896, 2304, 8, None), (2048, 1024, 8, None),
    (6144, 2048, 4, 6 << 20)], ids=["mellum2-up", "mellum2-down", "olmoe-up",
                                    "longcat-up-tiled"])
@pytest.mark.parametrize("draw", ["odd", "half", "empty"])
def test_kernel_matches_ragged_dot(monkeypatch, k, n, groups, budget, draw):
    rng = np.random.default_rng(k + len(draw))
    rows, past = 296, 88            # 296 + 88 = 3 tiles of 128: one is never visited
    sizes = _sizes(rng, rows, groups, draw)
    if draw == "half":
        assert sizes.max() * len(sizes) / sizes.sum() == pytest.approx(5, abs=.1)
    if budget:
        monkeypatch.setattr(G, "_VMEM_BUDGET", budget)
        tm, tk, tn = G.tiles(rows + past, k, n)
        assert tk < k and tn < n, (tk, tn)
        monkeypatch.setattr(G, "_VMEM_BUDGET", 1 << 16)
        with pytest.raises(ValueError, match="fits"):
            G.tiles(rows + past, k, n)
        monkeypatch.setattr(G, "_VMEM_BUDGET", budget)
    tokens, weights = _operands(rng, rows + past, k, n, len(sizes))
    got = G.grouped_mm(tokens, weights, jnp.asarray(sizes), interpret=True)
    want = jax.lax.ragged_dot(tokens, weights, jnp.asarray(sizes))
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    # bf16 results of float32 sums: an ulp where the order of the sum differs
    assert _gap(got, want, rows) < 2 ** -7
    # a planted fault shows: the walk one group short
    short = G.grouped_mm(tokens, weights,
                         jnp.asarray(sizes).at[np.flatnonzero(sizes)[-1]].set(0),
                         interpret=True)
    assert _gap(short, want, rows) > 0.1 or not np.isfinite(_gap(short, want, rows))


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_stacked_weights_pick_the_layer(layer):
    rng = np.random.default_rng(layer)
    rows, k, n, groups = 200, 256, 384, 6
    sizes = jnp.asarray(_sizes(rng, rows - 40, groups, "odd"))
    tokens, stack = _operands(rng, rows, k, n, groups, layers=3)
    got = jax.jit(lambda l: G.grouped_mm(tokens, stack, sizes, l,
                                         tiling=(64, 128, 128),
                                         interpret=True))(layer)
    want = jax.lax.ragged_dot(tokens, stack[layer], sizes)
    assert _gap(got, want, rows - 40) < 2 ** -7
    others = [_gap(got, jax.lax.ragged_dot(tokens, stack[l], sizes), rows - 40)
              for l in range(3) if l != layer]
    assert min(others) > 0.1


def test_no_group_has_rows():
    """No visit at all (a step whose tokens all chose experts of other
    chips): the kernel runs to its end and nothing is claimed of the rows."""
    rng = np.random.default_rng(0)
    tokens, weights = _operands(rng, 144, 128, 128, 4)
    out = G.grouped_mm(tokens, weights, jnp.zeros((4,), jnp.int32),
                       interpret=True)
    assert out.shape == (144, 128)


@pytest.mark.parametrize("layer", [None, 1], ids=["one-layer", "stacked"])
def test_moe_ffn_and_its_gradient_match_the_ragged_dot_path(monkeypatch,
                                                            request, layer):
    """``moe_expert_ffn`` through the kernel against the path the CPU takes,
    values and ``jax.grad`` for the rows and all three matrices; with
    ``layer=`` the matrices are a stack of three layers and the other
    layers' derivative is zero."""
    rng = np.random.default_rng(1)
    rows, e, f, groups = 160, 128, 256, 5
    sizes = jnp.asarray(_sizes(rng, rows, groups, "odd"))
    tokens = jnp.asarray(rng.normal(size=(rows, e)), jnp.float32)
    stack = () if layer is None else (3,)
    mats = [jnp.asarray(rng.normal(size=stack + s) / np.sqrt(s[1]), jnp.float32)
            for s in ((groups, e, f), (groups, e, f), (groups, f, e))]

    def loss(tokens, *mats):
        return jnp.sum(G.moe_expert_ffn(tokens, *mats, sizes, layer) ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(tokens, *mats)
    calls = request.getfixturevalue("kernel_path")
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(tokens, *mats)
    assert len(calls) == 3
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4 * float(
            jnp.abs(b).max()))
    if layer is not None:
        assert all(np.asarray(g)[layer].any() and not np.asarray(g)[0].any()
                   for g in got[1][1:])


def test_dead_positions_rows_are_left_alone(request):
    """``apply_moe_grouped`` with dead positions, whose rows no group holds:
    the kernel leaves them alone and the combine's zeroing keeps the
    result."""
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=128, num_layers=1, num_heads=4,
        intermediate_size=128, moe_intermediate_size=128, num_experts=4,
        num_experts_per_tok=2, moe_impl="grouped", max_seq_len=64,
        dtype="float32")
    params, _ = L.init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 128), jnp.float32)
    live = jnp.arange(80).reshape(2, 40) % 3 != 1
    want = L.apply_moe_grouped(params, x, cfg, live=live)
    calls = request.getfixturevalue("kernel_path")
    got = L.apply_moe_grouped(params, x, cfg, live=live)
    assert len(calls) == 3
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got[2], want[2])
    assert not np.asarray(got[0])[~np.asarray(live)].any()


def _routed_block(held, dtype):
    """A routed block of top 4 over 16 outputs of which the chip holds
    ``held`` (16: all of them, no share), and 40 x 4 = 160 selection rows."""
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.models.config import TransformerConfig
    share = {} if held == 16 else dict(
        moe_router_experts=14, moe_expert_first=4, moe_zero_experts=2)
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
        intermediate_size=64, moe_intermediate_size=16, num_experts=held,
        num_experts_per_tok=4, moe_impl="grouped", max_seq_len=64,
        dtype=dtype, **share)
    params, _ = L.init_moe_mlp(jax.random.PRNGKey(0), cfg)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 10, 32),
                                  cfg.act_dtype)) + 0.1
    return cfg, params, x


def _poison_past_the_groups(monkeypatch):
    """The products' rows past the groups read NaN, as the chip may leave
    them (on the CPU ``ragged_dot`` writes zeros there)."""
    real = G.moe_expert_ffn

    def poisoned(tokens, wg, wu, wo, sizes, layer=None):
        rows = real(tokens, wg, wu, wo, sizes, layer)
        return jnp.where((jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None],
                         rows, jnp.nan)
    monkeypatch.setattr(G, "moe_expert_ffn", poisoned)


#: (experts held of 16 outputs, live mask over the 40 positions, rows a
#: trip): the rows in groups ``n`` beside the 160 selection rows
BOUNDED = {
    # every pick held and live: n = T x k, the last of 3 blocks of 64 ends
    # with the buffer, over 32 rows the second holds too
    "all-held": (16, "all", 64),
    "all-held-exact-blocks": (16, "all", 32),        # n = T x k = 5 x 32
    "all-held-dead": (16, "some", 64),               # 23 live: n = 92
    "all-held-multiple": (16, "24", 32),             # n = 96 = 3 x 32
    "quarter": (4, "all", 32),
    "quarter-dead": (4, "some", 32),
    "one-of-16": (1, "all", 32),                     # a few rows in 160
    "one-of-16-dead": (1, "some", 64),
    "none-picked": (2, "all", 32),                   # held share 0: n = 0
    "nothing-live": (16, "none", 32),                # n = 0
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(BOUNDED))
def test_bounded_dispatch_and_combine_move_the_groups_rows(monkeypatch, case,
                                                           dtype):
    """``apply_moe_grouped`` with its gather and scatter-add bounded by the
    rows in groups (a small block forced on every case, whatever the chip
    holds) against the unbounded lines, the gather's buffer and the
    product's result poisoned past what is written, as the chip leaves
    them: outputs within the rounding of a k-term sum, no NaN, the same
    group sizes and share counts, whole blocks counted."""
    from deepspeed_tpu.models import layers as L
    held, mask, block = BOUNDED[case]
    cfg, params, x = _routed_block(held, dtype)
    if case == "none-picked":
        # positive tokens against negative columns: no pick is a held expert
        first = cfg.moe_expert_first
        params["router"] = jnp.abs(params["router"]).at[
            :, first:first + held].multiply(-1)
    live = {"all": jnp.ones((4, 10), bool), "none": jnp.zeros((4, 10), bool),
            "24": jnp.arange(40).reshape(4, 10) < 24,
            "some": jnp.arange(40).reshape(4, 10) % 7 % 2 == 0}[mask]
    run = lambda: jax.jit(lambda p, x, l: L.apply_moe_grouped(
        p, x, cfg, live=l))(params, x, live)
    monkeypatch.setattr(L, "_move_block", lambda cfg, rows: None)
    want = run()
    n = int(want[2].sum())
    assert (n == 0) == (case in ("none-picked", "nothing-live"))
    assert n == {"all-held": 160, "all-held-exact-blocks": 160,
                 "all-held-multiple": 96}.get(case, n)
    _poison_past_the_groups(monkeypatch)
    monkeypatch.setattr(G, "unwritten", lambda rows, like: jnp.full(
        (rows, like.shape[1]), jnp.nan, like.dtype))
    monkeypatch.setattr(L, "_move_block", lambda cfg, rows: block)
    assert "while" in str(jax.make_jaxpr(lambda p, x, l: L.apply_moe_grouped(
        p, x, cfg, live=l))(params, x, live))
    got = run()
    assert not np.isnan(np.asarray(got[0], np.float32)).any()
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
        rtol=tol, atol=tol * float(jnp.abs(want[0]).max()))
    for a, b in zip(got[2:], want[2:]):
        np.testing.assert_array_equal(a, b)
    assert not np.asarray(got[0], np.float32)[~np.asarray(live)].any() \
        or cfg.moe_zero_experts        # a zero expert answers a dead token too
    assert int(L.moe_rows_moved(cfg, got[2], 160)) == -(-n // block) * block


def _scatter_add_reference(params, x, cfg, live):
    """A routed block written plainly, nothing of ``layers.py`` in it: every
    selection's expert applied to its token (the expert's matrices gathered
    a selection), the results weighted and scatter-added by token; a
    selection that is dead or not held adds nothing; a zero expert hands the
    token back under its weight."""
    from deepspeed_tpu.moe.sharded_moe import topk_gating_grouped
    k, n_exp = cfg.num_experts_per_tok, cfg.num_experts
    tokens = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    t = tokens.shape[0]
    idx, w, _ = topk_gating_grouped(
        tokens @ params["router"].astype(jnp.float32), k=k,
        normalize=cfg.moe_norm_topk)
    local = idx.reshape(-1) - cfg.moe_expert_first
    keep = (local >= 0) & (local < n_exp)
    if live is not None:
        keep &= jnp.repeat(live.reshape(-1), k)
    at = jnp.clip(local, 0, n_exp - 1)
    rows = jnp.repeat(tokens, k, axis=0)                       # (T*k, E)
    f32 = lambda name: params[name].astype(jnp.float32)[at]    # (T*k, ., .)
    h = jax.nn.silu(jnp.einsum("re,ref->rf", rows, f32("wi_gate"))) \
        * jnp.einsum("re,ref->rf", rows, f32("wi_up"))
    y = jnp.einsum("rf,rfe->re", h, f32("wo")) * w.reshape(-1, 1)
    out = jnp.zeros_like(tokens).at[jnp.arange(t * k) // k].add(
        jnp.where(keep[:, None], y, 0.0))
    if cfg.moe_zero_experts:
        is_zero = idx >= cfg.moe_router_width - cfg.moe_zero_experts
        out = out + tokens * jnp.sum(jnp.where(is_zero, w, 0.0), axis=-1,
                                     keepdims=True)
    return out.reshape(x.shape)


#: (experts held of 16 outputs, live mask, selections a token): every case
#: runs the gather by token (``_move_block`` is None at 160 rows)
BY_TOKEN = {
    "all-live": (16, "all", 4),
    "dead-poisoned": (16, "some", 4),
    "nothing-live": (16, "none", 4),
    "held-share-narrow": (4, "some", 4),     # zero experts on, most picks absent
    "top-1": (16, "some", 1),
    "training-no-live": (16, None, 4),
}


def _by_token_case(monkeypatch, case, dtype):
    from deepspeed_tpu.models import layers as L
    held, mask, k = BY_TOKEN[case]
    cfg, params, x = _routed_block(held, dtype)
    cfg = cfg.replace(num_experts_per_tok=k)
    assert L._move_block(cfg, 40 * k) is None
    live = {"all": jnp.ones((4, 10), bool), "none": jnp.zeros((4, 10), bool),
            "some": jnp.arange(40).reshape(4, 10) % 7 % 2 == 0,
            None: None}[mask]
    _poison_past_the_groups(monkeypatch)
    return L, cfg, params, x, live


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(BY_TOKEN))
def test_the_combine_by_token_is_the_scatter_add(monkeypatch, case, dtype):
    """Where ``_move_block`` is None the combine gathers a token's k rows
    over the inverse permutation and sums them under its weights: the
    outputs of a plain scatter-add over every selection, with the rows past
    the groups poisoned as the chip leaves them: finite, and a dead token's
    row zero (or what the zero experts answer)."""
    L, cfg, params, x, live = _by_token_case(monkeypatch, case, dtype)
    got = jax.jit(lambda p, x, l: L.apply_moe_grouped(p, x, cfg, live=l))(
        params, x, live)
    want = _scatter_add_reference(params, x, cfg, live)
    out = np.asarray(got[0], np.float32)
    assert np.isfinite(out).all()
    tol = 2e-5 if dtype == "float32" else 2.0 ** -6
    np.testing.assert_allclose(out, np.asarray(want), rtol=tol,
                               atol=tol * float(jnp.abs(want).max()))
    if live is not None:
        dead = ~np.asarray(live)
        assert not out[dead].any() or cfg.moe_zero_experts
        assert out[~dead].any() or case == "nothing-live"
        assert int(L.moe_rows_moved(cfg, got[2], 40 * cfg.num_experts_per_tok)
                   ) == 40 * cfg.num_experts_per_tok
    if case == "held-share-narrow":
        picked, zero, absent = (int(v) for v in got[3])
        assert absent > int(got[2].sum()) > 0 and zero > 0


@pytest.mark.parametrize("case", list(BY_TOKEN))
def test_the_combine_by_token_has_the_scatter_adds_gradient(monkeypatch,
                                                            case):
    """``jax.grad`` through the gather (a scatter over rows that are all
    different, and the weights in token order) against the reference's, for
    the tokens, the router and the three expert matrices."""
    L, cfg, params, x, live = _by_token_case(monkeypatch, case, "float32")
    cot = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)
    got = jax.grad(lambda p, x: jnp.sum(
        L.apply_moe_grouped(p, x, cfg, live=live)[0] * cot),
        argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(
        _scatter_add_reference(p, x, cfg, live) * cot),
        argnums=(0, 1))(params, x)
    for name in ("router",) + L.EXPERT_MATRICES:
        np.testing.assert_allclose(
            got[0][name], want[0][name], rtol=2e-4,
            atol=2e-5 * max(1e-3, float(jnp.abs(want[0][name]).max())),
            err_msg=name)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-5 * float(
        jnp.abs(want[1]).max() + 1e-3))
    if case != "nothing-live":
        assert float(jnp.abs(got[0]["wo"]).max()) > 0


def _primitives_under(jaxpr, scope, inside=False):
    """(primitive name, output shapes) of every equation under the named
    scope, through every nested jaxpr (a scan's body, a jitted helper)."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn.primitive.name, [v.aval.shape for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives_under(sub, scope, here)


@pytest.mark.parametrize("width", [1, 16], ids=["narrow", "wide"])
def test_a_served_program_that_holds_every_expert_has_no_scatter_add(width):
    """The frame programs of an all-held routed model (OLMoE's layers at a
    small size): under ``moe_combine`` there is a gather and a sum, no
    ``scatter-add`` and no (tokens, hidden) of zeros to add into; the loops
    of the bounded form are not there either."""
    from deepspeed_tpu.inference.v2.model_runner import PagedModelRunner
    from deepspeed_tpu.models import build_model, get_config
    cfg = get_config("olmoe-1b-7b", vocab_size=256, hidden_size=64,
                     max_seq_len=256, dtype="float32", num_layers=2,
                     num_heads=4, num_kv_heads=4, intermediate_size=32,
                     num_experts=8, num_experts_per_tok=2)
    model = build_model(cfg)
    runner = PagedModelRunner(model, 8, 32)
    slots, i32, sds = 4, jnp.int32, jax.ShapeDtypeStruct
    row, flag = sds((slots,), i32), sds((slots,), jnp.bool_)
    key = jax.random.PRNGKey(0)
    pool = sds((cfg.num_layers, cfg.kv_heads, 33, 8, cfg.dims_per_head),
               jnp.float32)
    jaxpr = runner._build_frame_loop().trace(
        model.abstract_params(), sds((slots, 256), i32), row, row, row,
        sds((slots,), jnp.float32), sds((slots, 32), i32), row, row, row,
        flag, flag, flag, sds((runner.n_stats,), i32),
        sds(key.shape, key.dtype), pool, pool, width=width, steps=2,
        greedy=True, n_steps=sds((), i32)).jaxpr
    found = list(_primitives_under(jaxpr.jaxpr, "moe_combine"))
    names = {name for name, _ in found}
    assert {"gather", "reduce_sum"} <= names, names
    assert not names & {"scatter-add", "scatter_add", "while"}, names
    tokens = slots * width
    assert not [shapes for name, shapes in found
                if name == "broadcast_in_dim"
                and shapes == [(tokens, cfg.hidden_size)]]
    # the reader reads something: the dispatch's gather is under its scope
    assert "gather" in {n for n, _ in _primitives_under(jaxpr.jaxpr,
                                                        "moe_dispatch")}


def test_a_block_where_every_row_is_in_a_group_keeps_the_unbounded_lines(
        monkeypatch):
    """Without ``live`` and with every expert held (training, the v1
    modules) every row is in a group by construction: the trace is the one
    it is when the bound cannot bind, with no loop in it."""
    from deepspeed_tpu.models import layers as L
    cfg, params, x = _routed_block(16, "float32")
    trace = lambda: str(jax.make_jaxpr(
        lambda p, x: L.apply_moe_grouped(p, x, cfg))(params, x))
    want = trace()
    monkeypatch.setattr(L, "MOVE_BYTES", 1)       # a row tile a trip
    assert L._move_block(cfg, 4096) is None
    assert L._move_block(_routed_block(4, "float32")[0], 4096) == 128
    assert trace() == want and "while" not in want
    grads = jax.grad(lambda p: jnp.sum(L.apply_moe_grouped(p, x, cfg)[0]))(
        params)
    assert float(jnp.abs(grads["wo"]).max()) > 0


@pytest.mark.parametrize("name,cut,rows,block", [
    # the wide rungs of the two cells whose chip holds a share, and a narrow
    # step's 16 slots x k: one block
    ("longcat-flash-omni", dict(num_experts=16, moe_router_experts=512),
     1040 * 12, 128),
    ("longcat-flash-omni", dict(num_experts=16, moe_router_experts=512),
     528 * 12, 128),
    ("longcat-flash-omni", dict(num_experts=16, moe_router_experts=512),
     16 * 12, None),
    ("qwen3-next-80b-a3b", dict(num_experts=128, moe_router_experts=512),
     1040 * 10, 256),
    ("qwen3-next-80b-a3b", dict(num_experts=128, moe_router_experts=512),
     16 * 10, None),
    # every expert held: the live positions' rows fill most of any rung
    ("mellum2-12b-a2.5b", {}, 528 * 8, None),
    ("olmoe-1b-7b", {}, 144 * 8, None),
    ("glm-4.7-flash", {}, 144 * 4, None)])
def test_the_bound_binds_where_the_chip_holds_a_share(name, cut, rows, block):
    """``_move_block`` from the served configurations' static shapes: about
    a MiB of bf16 rows a trip where the router is wider than the experts
    held, None (the unbounded lines) everywhere else; ``moe_rows_moved``
    counts whole blocks there and every row here."""
    from deepspeed_tpu.models import get_config, layers as L
    cfg = get_config(name, dtype="bfloat16", moe_impl="grouped", **cut)
    assert cfg.moe_is_share == bool(cut)
    assert L._move_block(cfg, rows) == block
    sizes = jnp.zeros((cfg.num_experts,), jnp.int32).at[3].set(130).at[0].set(7)
    assert int(L.moe_rows_moved(cfg, sizes, rows)) == (
        rows if block is None else -(-137 // block) * block)


def _tiny_moe_pair():
    from deepspeed_tpu.models import build_model, get_config
    cfg = get_config("tiny-moe").replace(moe_capacity_factor=8.0)
    me, mg = build_model(cfg), build_model(cfg.replace(moe_impl="grouped"))
    params = jax.jit(me.init)(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 32)))
    return me, mg, params, {"input_ids": ids, "labels": ids}


def test_expert_parallel_path_runs_the_kernel_in_its_shard_map(monkeypatch,
                                                               kernel_path):
    """``apply_moe_grouped_ep`` on a data x expert mesh with the kernel as
    its local product: the loss and every gradient of the capacity-einsum
    dispatch (nothing drops at this capacity). The region is manual over
    EVERY mesh axis, which is what lets Mosaic lower the call on the chip.
    The interpreter reads the prefetched scalars in a way ``check_vma``
    refuses, so the check is off HERE; with it on, the chip's own lowering
    is compiled in ``tests/test_chip_compile.py``."""
    from deepspeed_tpu.utils import groups
    mesh = groups.set_mesh(groups.build_mesh(expert=2, data=4))
    regions = []
    real = jax.shard_map

    def shard_map(f, **kw):
        regions.append(set(kw["axis_names"]))
        return real(f, **{**kw, "check_vma": False})

    monkeypatch.setattr(jax, "shard_map", shard_map)
    me, mg, params, batch = _tiny_moe_pair()
    le, ge = jax.jit(jax.value_and_grad(me.loss))(params, batch)
    lg, gg = jax.jit(jax.value_and_grad(mg.loss))(params, batch)
    assert len(kernel_path) == 3 * 2        # three products a layer
    assert regions and all(r == set(mesh.axis_names) for r in regions)
    np.testing.assert_allclose(float(le), float(lg), rtol=2e-5)
    for a, b in zip(jax.tree.leaves(ge), jax.tree.leaves(gg)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("axes", [dict(data=8), dict(expert=2, tensor=2,
                                                     data=2)],
                         ids=["data-parallel", "expert-and-tensor"])
def test_a_mesh_that_xla_partitions_keeps_ragged_dot(kernel_path, axes):
    """XLA's SPMD pass cannot partition a Mosaic call: where it partitions
    the routed block (data parallelism with the experts whole; an expert
    axis beside a tensor axis, whose ``shard_map`` is manual over part of
    the mesh) the product is ``ragged_dot`` as it always was, and the
    kernel is not called."""
    from deepspeed_tpu.utils import groups
    groups.set_mesh(groups.build_mesh(**axes))
    me, mg, params, batch = _tiny_moe_pair()
    le = jax.jit(me.loss)(params, batch)
    lg = jax.jit(mg.loss)(params, batch)
    assert not kernel_path
    np.testing.assert_allclose(float(le), float(lg), rtol=2e-5)


# (hidden, expert width, experts held, selections a token): the three routed
# cells; every cell serves 16 slots, narrow steps and chunks of 128
CELLS = {"olmoe": (2048, 1024, 64, 8), "mellum2": (2304, 896, 64, 8),
         "longcat": (6144, 2048, 16, 12)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_tile_rule_fits_every_shape_the_cells_run(cell):
    hidden, width, groups, top = CELLS[cell]
    rungs = sorted({t for w in (1, 128) for t in pack_ladder(16, w)})
    assert rungs == [16, 144, 272, 528, 1040, 2048]
    for tokens in rungs:
        rows = tokens * top
        for k, n in ((hidden, width), (width, hidden)):
            tm, tk, tn = G.tiles(rows, k, n)
            assert G._vmem_bytes(tm, tk, tn, k, 2) <= G._VMEM_BUDGET
            assert k % tk == 0 and n % tn == 0
            assert tk == k or tk % 128 == 0
            assert tn == n or tn % 128 == 0
            assert tm % 16 == 0 and tm <= rows
            # the whole expert matrix: a product reads an expert once
            assert (tk, tn) == (k, n)
