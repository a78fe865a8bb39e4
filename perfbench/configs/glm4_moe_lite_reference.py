"""Plain reference of GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``:
DeepSeek-V3's layer) and of its multi-token-prediction module:
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no pages, no absorbed products, no grouped product, no
sort, no speculation. Independent of the program's model code: it takes
only the program's WEIGHTS (the pytree ``models.CausalLM.init`` makes for a
stack with leading dense layers: ``embed.tok``, ``embed.lm_head``,
``final_norm``, ``layers.g0`` the dense layers and ``layers.g1`` the routed
ones, each leaf stacked over the group's layers, with
``attn.{wq_a,q_norm,wq_b,wkv_a,kv_norm,wkv_b,wo}``, ``norm1``, ``norm2`` and
``mlp.{wi_gate,wi_up,wo}``, a routed layer's stacked over its experts
beside ``mlp.{router,router_bias,shared_wi_gate,shared_wi_up,shared_wo}``;
``mtp.{enorm,hnorm,eh_proj,layer,norm}`` stacked over the modules, of which
the first is read) and the sizes from the configuration file.

Written from the published ``config.json``
(https://huggingface.co/zai-org/GLM-4.7-Flash) and ISSUE 39's equations. E
hidden, H heads, ranks r_q / r_kv, head widths d_n (nope), d_r (rope), d_v
(value), N = RMSNorm.

MLA(a), the EXPANDED form, for normalised input ``a`` at position t:

    c_q = rms(a W_qa);  [q_nope_h | q_rope_h] = (c_q W_qb)_h
    [u | r] = a W_kva;  c = rms(u);  k_rope = RoPE(r, t), one head shared by
    every query head;  q_rope_h = RoPE(q_rope_h, t)
    [k_nope_h | v_h] = (c W_kvb)_h
    score (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(d_n + d_r),
    causal, softmax in float32;  y = concat_h(P_h v_h) W_o

Layers [0, ``first_k_dense_replace``): x + MLA(N_a x), then + F(N_b .), F a
gated SiLU FFN of ``intermediate_size``. The others, with m = N_b(.):

    s = sigmoid(m W_r);  S = the ``num_experts_per_tok`` largest of s + b
    (``router_bias``, for the choice only);  w_i = f s_i / (sum_S s + 1e-20)
    (``norm_topk_prob``; f = ``routed_scaling_factor``)
    y = sum_{i in S} w_i FFN_i(m) + FFN_shared(m), each a gated SiLU FFN of
    ``moe_intermediate_size`` (the shared one x ``n_shared_experts``)

then a last RMSNorm and an untied head. The prediction module, for the
stack's hidden state h_i BEFORE the last norm and the next token t_{i+1}:

    h'_i = [N_e(Emb(t_{i+1})) ; N_h(h_i)] W_eh;  one routed layer over h'
    (its own MLA over the h' of positions <= i, at those positions);
    logits = Head(N_s(.)): the token at i + 2

Assumed (the config gives the sizes, not these): RoPE on interleaved pairs
(x_2i, x_2i+1) of the 64 rope lanes, angle t theta^(-2i / d_r) (the
DeepSeek-V3 family's layout; with seeded weights another layout is a
permutation of lanes); h_i is taken before the stack's last norm; the
module shares the model's embedding and head; ``n_group`` = ``topk_group``
= 1, so the choice is over all experts at once.

Departures, same mathematics: attention runs a block of queries at a time
against every key (the mask is the same); every expert is computed for
every token, its gated product weighted by w where the expert is among the
token's choices and by 0 where it is not, before the down projection, a
block of tokens at a time; the dense FFN runs a block of its width at a
time; the head runs a block of rows and of the vocabulary at a time;
weights are upcast a matrix, an expert or a block at a time.

NEAR-TIES OF THE ROUTER (``logits_rows``' ``tie_margin``, on by default;
0 gives every row's plain logits, which the tests compare). The choice of a
token's 4 experts is a discontinuity, and this router makes it a large one:
with sigmoid scores renormalised over the chosen 4 and scaled by 1.8 the
marginal expert carries a FULL quarter of the layer's routed output
(w ~ 0.45; under a softmax router, OLMoE's or Mellum2's, the marginal expert
is the lightest, w ~ 0.02 .. 0.09). Where the 4th and the 5th of s + b lie
closer than the precision the configuration states resolves, a bfloat16
system and this float32 reference may each take another expert, neither is
wrong, and the logits move by whole units: on the chip 5.3% of a request's
rows read over the harness's 0.25 against the plain logits, every one of
them at a near-tie (PERF.md, PR 39). No reference that is handed token ids
alone can tell which expert a sound system took. So EVERY row is compared,
and a row at a near-tie is held to the nearest of the routings a sound
system may have taken (``candidate_hidden``):

- the context (every token's keys and values in every layer) is the plain
  forward's, whatever its own near-ties: one flipped token among hundreds of
  keys moves a later row's logits by a few hundredths (PERF.md);
- the row's own token is walked through the stack once more, a query
  against that context. In a routed layer every set S of 4 experts that a
  perturbation of s + b under ``tie_margin`` could make the top 4
  (min over S + margin > max over the others; the plain choice is one of
  them) continues as a candidate of its own, with the weights
  w_i = f s_i / sum_S s of ITS set, and meets the next layers' near-ties at
  its own hidden state;
- the row comes back as the upper envelope of its candidates' logits, each
  taken relative to its own maximum: max_c (L_c - max L_c), <= 0 with a 0 at
  every candidate's greedy choice. The harness reads ``max - picked``: the
  SMALLEST gap the served token has under any candidate. A row without a
  near-tie has one candidate, its plain logits (minus their maximum).

A fault of the mask, the position, the page, a kernel or an expert moves
the logits of the plain choice and of every other candidate alike; what the
margin lets through is measured (PERF.md: the reference's own greedy tokens
under float8's 3 bits of mantissa are not correct). About ten candidates a
row at these widths, so the walk is kept cheap beside a server that fills
the chip: candidates travel in blocks of ``TOKEN_BLOCK`` (one program a
step whatever their number, the memory of one block), and the envelope is
made on the device, a block of candidates and of the vocabulary at a time,
so that only a block's ROWS come to the host.
"""

import functools
import itertools
import sys

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128
TOKEN_BLOCK = 1024       # contexts are padded to a multiple of it;
                         # candidates travel in blocks of it
WIDTH_BLOCK = 2048       # of the dense FFN's width at a time
VOCAB_BLOCK = 16384      # of the head's outputs at a time
ROW_BLOCK = 256          # of the rows whose logits are asked for
#: a set of experts within this of being the top of s + b is a candidate
#: (the docstring). PERF.md, PR 39: a sound bfloat16 run's scores differ from
#: this reference's by about 0.0015 (one standard deviation; at 2^-8 a served
#: row still read 0.21 of the harness's 0.25, at 2^-7 none over 0.06), and
#: at 2^-7 the reference's own greedy tokens under float8's mantissa read
#: over 0.25 on one row in eleven
TIE_MARGIN = 2.0 ** -7
#: candidates a row may have before the walk gives up (a margin so wide
#: that most sets are candidates compares nothing). Far above what a row
#: meets at the default margin: the most among 12,000 rows was 136, and a
#: run that stops here is a run lost
MAX_CANDIDATES = 4096
#: the candidates' sets are looked for among this many of the largest s + b
TIE_POOL = 8
F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, theta, positions=None):
    """x: (B, T, heads, d_r) at ``positions`` (T,), 0..T-1 where none are
    given; interleaved pairs."""
    d = x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    angles = positions.astype(F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v):
    """q, k: (B, T, H, D); v: (B, T, H, Dv), T a multiple of ``Q_BLOCK``.
    Causal softmax attention, a block of queries at a time; q is scaled."""
    t = q.shape[1]

    def block(a):
        qb = jax.lax.dynamic_slice_in_dim(q, a, Q_BLOCK, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k)
        mask = jnp.arange(t)[None, :] <= a + jnp.arange(Q_BLOCK)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[:3] + v.shape[-1:])


@functools.partial(jax.jit, static_argnames=("eps", "theta", "d_n", "d_r"))
def mla(attn, x, *, eps, theta, d_n, d_r):
    """MLA(x) of normalised input x (B, T, E), expanded form."""
    with jax.default_matmul_precision("highest"):
        c_q = _rms_norm(jnp.einsum("bte,er->btr", x, attn["wq_a"].astype(F32)),
                        attn["q_norm"]["scale"], eps)
        kv_a = jnp.einsum("bte,er->btr", x, attn["wkv_a"].astype(F32))
        c = _rms_norm(kv_a[..., :-d_r], attn["kv_norm"]["scale"], eps)
        k_rope = rope(kv_a[..., None, -d_r:], theta)
        q = jnp.einsum("btr,rhd->bthd", c_q, attn["wq_b"].astype(F32))
        kv = jnp.einsum("btr,rhd->bthd", c, attn["wkv_b"].astype(F32))
        q = jnp.concatenate([q[..., :d_n], rope(q[..., d_n:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :d_n],
             jnp.broadcast_to(k_rope, kv.shape[:3] + (d_r,))], -1)
        a = _attention(q / np.sqrt(d_n + d_r), k, kv[..., d_n:])
        return jnp.einsum("bthd,hde->bte", a, attn["wo"].astype(F32))


@functools.partial(jax.jit, static_argnames=("eps", "theta", "d_n", "d_r"))
def keys_values(attn, x, positions, *, eps, theta, d_n, d_r):
    """The expanded keys (T, H, d_n + d_r) and values (T, H, d_v) of
    normalised inputs ``x`` (T, E) at ``positions`` (T,)."""
    with jax.default_matmul_precision("highest"):
        kv_a = jnp.einsum("te,er->tr", x, attn["wkv_a"].astype(F32))
        c = _rms_norm(kv_a[:, :-d_r], attn["kv_norm"]["scale"], eps)
        k_rope = rope(kv_a[None, :, None, -d_r:], theta, positions)[0]
        kv = jnp.einsum("tr,rhd->thd", c, attn["wkv_b"].astype(F32))
        k = jnp.concatenate(
            [kv[..., :d_n],
             jnp.broadcast_to(k_rope, kv.shape[:2] + (d_r,))], -1)
        return k, kv[..., d_n:]


@functools.partial(jax.jit, static_argnames=("eps", "theta", "d_n", "d_r"))
def attend(lp, h, pos, k_ctx, v_ctx, *, eps, theta, d_n, d_r):
    """A layer's attention half for single tokens against a given context:
    row i, hidden state ``h[i]`` (n, E) at position ``pos[i]``, attends the
    context's keys and values (``keys_values`` of the layer's normalised
    plain input) at the positions before its own, and its own. Returns
    (h + MLA(N_a h), N_b of that). n a multiple of ``Q_BLOCK``."""
    attn = lp["attn"]
    a = _rms_norm(h, lp["norm1"]["scale"], eps)
    k_own, v_own = keys_values(attn, a, pos, eps=eps, theta=theta, d_n=d_n,
                               d_r=d_r)
    with jax.default_matmul_precision("highest"):
        c_q = _rms_norm(jnp.einsum("ne,er->nr", a, attn["wq_a"].astype(F32)),
                        attn["q_norm"]["scale"], eps)
        q = jnp.einsum("nr,rhd->nhd", c_q, attn["wq_b"].astype(F32))
        q = jnp.concatenate(
            [q[..., :d_n], rope(q[None, ..., d_n:], theta, pos)[0]], -1) \
            / np.sqrt(d_n + d_r)

        def block(i):
            def cut(x):
                return jax.lax.dynamic_slice_in_dim(x, i, Q_BLOCK, axis=0)
            qb = cut(q)
            before = jnp.arange(k_ctx.shape[0])[None, :] < cut(pos)[:, None]
            s_ctx = jnp.where(before[:, None, :],
                              jnp.einsum("qhd,thd->qht", qb, k_ctx), -jnp.inf)
            s_own = jnp.sum(qb * cut(k_own), -1, keepdims=True)
            p = jax.nn.softmax(jnp.concatenate([s_ctx, s_own], -1), axis=-1)
            return jnp.einsum("qht,thd->qhd", p[..., :-1], v_ctx) \
                + p[..., -1:] * cut(v_own)

        out = jax.lax.map(block, jnp.arange(0, h.shape[0], Q_BLOCK))
        h = h + jnp.einsum("nhd,hde->ne",
                           out.reshape((-1,) + out.shape[2:]),
                           attn["wo"].astype(F32))
    return h, _rms_norm(h, lp["norm2"]["scale"], eps)


@jax.jit
def _ffn(x, wi_gate, wi_up, wo):
    """(silu(x Wg) * (x Wu)) Wd."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,ef->btf", x, wi_gate.astype(F32)))
        up = jnp.einsum("bte,ef->btf", x, wi_up.astype(F32))
        return jnp.einsum("btf,fe->bte", gate * up, wo.astype(F32))


def dense_ffn(mlp, x):
    """F(x), a block of the width at a time."""
    out = jnp.zeros_like(x)
    for f0 in range(0, mlp["wo"].shape[0], WIDTH_BLOCK):
        f1 = f0 + WIDTH_BLOCK
        out = out + _ffn(x, mlp["wi_gate"][:, f0:f1], mlp["wi_up"][:, f0:f1],
                         mlp["wo"][f0:f1])
    return out


@jax.jit
def scores(m, router):
    """s = sigmoid(m W_r), float32."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(jnp.einsum("...e,ex->...x", m,
                                         router.astype(F32)))


@functools.partial(jax.jit, static_argnames=("factor", "normalise"))
def set_weights(s, chosen, *, factor, normalise):
    """(..., X) float32: w_i at the experts ``chosen`` (..., k) and 0
    elsewhere."""
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32),
                     axis=-2) * s
    if normalise:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked * factor


def _routing_sizes(config):
    return dict(factor=float(config["routed_scaling_factor"]),
                normalise=bool(config["norm_topk_prob"]))


def route(m, router, bias, config):
    """(weights (B, T, X) float32 with w_i at the token's
    ``num_experts_per_tok`` choices and 0 elsewhere, the choices (B, T, k),
    the scores s, and how far the last choice stands above the first expert
    left out, in s + b)."""
    top_k = int(config["num_experts_per_tok"])
    s = scores(m, router)
    biased, chosen = jax.lax.top_k(s + bias.astype(F32), top_k + 1)
    apart, chosen = biased[..., -2] - biased[..., -1], chosen[..., :top_k]
    return set_weights(s, chosen, **_routing_sizes(config)), chosen, s, apart


@jax.jit
def _expert(x, w, wi_gate, wi_up, wo):
    """Wd(w * silu(Wg x) * Wu x) of one expert over every token."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,ef->btf", x, wi_gate.astype(F32)))
        up = jnp.einsum("bte,ef->btf", x, wi_up.astype(F32))
        return jnp.einsum("btf,fe->bte", gate * up * w[..., None],
                          wo.astype(F32))


def experts_out(m, weights, mlp):
    """sum_i w_i FFN_i(m) + FFN_shared(m) for given weights (B, T, X), T a
    multiple of ``TOKEN_BLOCK``."""
    out = _ffn(m, mlp["shared_wi_gate"], mlp["shared_wi_up"],
               mlp["shared_wo"])
    for i in range(mlp["wo"].shape[0]):
        w = [mlp[name][i] for name in ("wi_gate", "wi_up", "wo")]
        parts = [_expert(m[:, t0:t0 + TOKEN_BLOCK],
                         weights[:, t0:t0 + TOKEN_BLOCK, i], *w)
                 for t0 in range(0, m.shape[1], TOKEN_BLOCK)]
        out = jax.block_until_ready(out + jnp.concatenate(parts, axis=1))
    return out


def routed_block(m, mlp, config, routing=None):
    """sum_{i in S} w_i FFN_i(m) + FFN_shared(m). ``routing``, a list,
    receives (choices, scores, the margin of the choice)."""
    weights, chosen, s, apart = route(m, mlp["router"], mlp["router_bias"],
                                      config)
    if routing is not None:
        routing.append((chosen, s, apart))
    return experts_out(m, weights, mlp)


def _mla_sizes(config):
    return dict(eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_theta"]),
                d_n=int(config["qk_nope_head_dim"]),
                d_r=int(config["qk_rope_head_dim"]))


def layer(x, lp, config, routing=None):
    """One layer of weights ``lp`` (a group's layer, sliced): dense where
    it has no router."""
    eps = float(config["rms_norm_eps"])
    h = x + mla(lp["attn"], _rms_norm(x, lp["norm1"]["scale"], eps),
                **_mla_sizes(config))
    m = _rms_norm(h, lp["norm2"]["scale"], eps)
    if "router" in lp["mlp"]:
        return h + routed_block(m, lp["mlp"], config, routing)
    return h + dense_ffn(lp["mlp"], m)


def _layers(params):
    """Every layer of the stack in order, as (group's tree, index)."""
    groups = params["layers"]
    if "attn" in groups:        # a stack without leading dense layers
        groups = {"g0": groups}
    for name in sorted(groups, key=lambda g: int(g[1:])):
        for i in range(jax.tree.leaves(groups[name])[0].shape[0]):
            yield groups[name], i


def hidden(params, ids, config, routing=None, inputs=None):
    """(B, T) token ids -> (B, T, E) float32, before the last norm.
    ``inputs``, a list, receives every layer's input."""
    h = params["embed"]["tok"][ids].astype(F32)
    for group, i in _layers(params):
        if inputs is not None:
            inputs.append(h)
        h = layer(h, jax.tree.map(lambda w: w[i], group), config, routing)
    return h


def mtp_hidden(params, h, ids, config, routing=None):
    """The module's output before its last norm, (B, T, E): position i
    pairs ``h[:, i]`` (the stack's ``hidden``) with token ``ids[:, i + 1]``;
    the last position pairs with token 0 and is no prediction."""
    module = jax.tree.map(lambda w: w[0], params["mtp"])
    eps = float(config["rms_norm_eps"])
    nxt = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    both = jnp.concatenate(
        [_rms_norm(params["embed"]["tok"][nxt].astype(F32),
                   module["enorm"]["scale"], eps),
         _rms_norm(h, module["hnorm"]["scale"], eps)], axis=-1)
    with jax.default_matmul_precision("highest"):
        joined = jnp.einsum("btf,fe->bte", both, module["eh_proj"].astype(F32))
    return layer(joined, module["layer"], config, routing)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(h, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bte,ev->btv", _rms_norm(h, scale, eps),
                          lm_head.astype(F32))


def _head(h, scale, lm_head, eps):
    """Head(N(h)) of picked rows h (1, R, E) -> (R, V) on the host, a block
    of rows and of the vocabulary at a time (2,048 rows x 154,880 float32
    logits are 1.27 GB beside the system under test)."""
    return np.concatenate([
        np.concatenate([
            np.asarray(_head_block(h[:, r0:r0 + ROW_BLOCK], scale,
                                   lm_head[:, v0:v0 + VOCAB_BLOCK],
                                   eps=eps)[0])
            for v0 in range(0, lm_head.shape[1], VOCAB_BLOCK)], axis=-1)
        for r0 in range(0, h.shape[1], ROW_BLOCK)], axis=0)


def _padded(ids):
    """(1, T') int32, T' the next multiple of ``TOKEN_BLOCK``: causal, so a
    zero tail changes nothing before it; few distinct shapes."""
    ids = np.asarray(ids, np.int32)
    padded = np.zeros((1, -(-len(ids) // TOKEN_BLOCK) * TOKEN_BLOCK), np.int32)
    padded[0, :len(ids)] = ids
    return jnp.asarray(padded)


def candidate_sets(biased, top_k, margin):
    """The sets of ``top_k`` experts that a perturbation of ``biased`` (n, X)
    under ``margin`` could make a row's largest: (row of each set (m,), the
    sets (m, top_k)), a row's plain choice first. S is one iff
    min over S + margin > max over the others; looked for among the
    ``TIE_POOL`` largest."""
    pool = min(TIE_POOL, biased.shape[1])
    order = np.argsort(-biased, axis=1, kind="stable")[:, :pool]
    v = np.take_along_axis(biased, order, axis=1)
    combos = np.array(list(itertools.combinations(range(pool), top_k)))
    inside = np.zeros((len(combos), pool), bool)
    inside[np.arange(len(combos))[:, None], combos] = True
    low = np.where(inside[None], v[:, None, :], np.inf).min(-1)
    high = np.where(inside[None], -np.inf, v[:, None, :]).max(-1)
    ok = low + margin > high
    ok[:, 0] = True                                  # the largest themselves
    which, c = np.nonzero(ok)
    return which, np.take_along_axis(order[which], combos[c], axis=1)


def _padded_block(x, fill=0):
    """(k, ...) numpy -> (``TOKEN_BLOCK``, ...), ``fill`` behind."""
    out = np.full((TOKEN_BLOCK,) + x.shape[1:], fill, x.dtype)
    out[:len(x)] = x
    return out


@functools.partial(jax.jit, static_argnames=("factor", "normalise"))
def _children(h, m, s, parent, chosen, *, factor, normalise):
    """The candidates that go on from a block: (h, m) of their parents and
    the weights of their own sets."""
    return h[parent], m[parent], set_weights(
        s[parent], chosen, factor=factor, normalise=normalise)


def _bounded(src, tie_margin):
    most = np.bincount(src).max()
    if most > MAX_CANDIDATES:
        raise ValueError(f"{most} candidate routings of one row within "
                         f"{tie_margin}: a margin that compares nothing")


def candidate_hidden(params, inputs, rows, config, tie_margin):
    """The tokens at ``rows`` walked through the stack once more, each a
    query against the plain forward's context (``inputs``: ``hidden``'s,
    every layer's input (1, T, E)); in a routed layer every set of
    ``candidate_sets`` goes on as a candidate of its own. Candidates travel
    in blocks of ``TOKEN_BLOCK`` (fixed shapes: one program a step whatever
    their number, and the memory of one block), in the order of their rows.
    Returns the blocks: [(the rows of its candidates (k,), their hidden
    states before the last norm (``TOKEN_BLOCK``, E), zeros behind k)]."""
    top_k, sizes = int(config["num_experts_per_tok"]), _mla_sizes(config)
    rows = np.asarray(rows)
    blocks = [(src, jnp.asarray(_padded_block(np.asarray(
        inputs[0][0, rows[src]]))))
        for src in np.array_split(np.arange(len(rows)),
                                  -(-len(rows) // TOKEN_BLOCK))]
    for (group, i), x in zip(_layers(params), inputs):
        lp = jax.tree.map(lambda w: w[i], group)
        k_ctx, v_ctx = keys_values(
            lp["attn"], _rms_norm(x[0], lp["norm1"]["scale"], sizes["eps"]),
            jnp.arange(x.shape[1]), **sizes)
        halves = [attend(lp, h, jnp.asarray(_padded_block(
            rows[src].astype(np.int32))), k_ctx, v_ctx, **sizes)
            for src, h in blocks]
        if "router" not in lp["mlp"]:
            blocks = [(src, h + dense_ffn(lp["mlp"], m[None])[0])
                      for (src, _), (h, m) in zip(blocks, halves)]
            continue
        # every block's candidates that go on, as indices into the blocks
        # laid end to end, then cut into full blocks again
        scored = [scores(m, lp["mlp"]["router"]) for _, m in halves]
        bias = np.asarray(lp["mlp"]["router_bias"], np.float32)
        parent, chosen = zip(*(
            candidate_sets(np.asarray(s)[:len(src)] + bias, top_k, tie_margin)
            for (src, _), s in zip(blocks, scored)))
        src = np.concatenate([src[at] for (src, _), at in zip(blocks, parent)])
        _bounded(src, tie_margin)
        parent = np.concatenate([at + j * TOKEN_BLOCK
                                 for j, at in enumerate(parent)])
        chosen = np.concatenate(chosen)
        h, m = (jnp.concatenate(part) for part in zip(*halves))
        s = jnp.concatenate(scored)
        blocks = []
        for c0 in range(0, len(src), TOKEN_BLOCK):
            cut = slice(c0, c0 + TOKEN_BLOCK)
            h2, m2, w = _children(h, m, s, _padded_block(parent[cut]),
                                  _padded_block(chosen[cut]),
                                  **_routing_sizes(config))
            blocks.append((src[cut], h2 + experts_out(m2[None], w[None],
                                                      lp["mlp"])[0]))
    return blocks


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head_at(h, scale, lm_head, v0, *, eps, size):
    """Head(N(h)) of rows h (n, E) at the vocabulary's [v0, v0 + size)."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(
            "ne,ev->nv", _rms_norm(h, scale, eps),
            jax.lax.dynamic_slice_in_dim(lm_head, v0, size,
                                         axis=1).astype(F32))


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _envelope_at(h, top, row, scale, lm_head, v0, *, eps, size):
    """max over the candidates of each row of (their logits - their own
    maximum ``top``), at the vocabulary's [v0, v0 + size): (n + 1, size),
    row ``row[i]`` of candidate i; the padding's row is n."""
    logits = _head_at(h, scale, lm_head, v0, eps=eps, size=size)
    return jax.ops.segment_max(logits - top[:, None], row,
                               num_segments=h.shape[0] + 1,
                               indices_are_sorted=True)


def envelope(blocks, n_rows, scale, lm_head, eps):
    """(n_rows, V) float32 on the host: each row the upper envelope of its
    candidates' logits, each relative to its own maximum. Made on the device
    a block of candidates and of the vocabulary at a time; only a block's
    rows come to the host."""
    vocab = lm_head.shape[1]
    size = min(VOCAB_BLOCK, vocab)
    starts = [min(v0, vocab - size) for v0 in range(0, vocab, size)]
    out = np.full((n_rows, vocab), -np.inf, np.float32)
    for src, h in blocks:
        top = functools.reduce(jnp.maximum, (
            _head_at(h, scale, lm_head, v0, eps=eps, size=size).max(-1)
            for v0 in starts))
        first, held = src[0], src[-1] - src[0] + 1
        row = jnp.asarray(_padded_block((src - first).astype(np.int32),
                                        fill=TOKEN_BLOCK))
        for v0 in starts:
            part = np.asarray(_envelope_at(
                h, top, row, scale, lm_head, v0, eps=eps,
                size=size)[:-(-held // ROW_BLOCK) * ROW_BLOCK])[:held]
            np.maximum(out[first:first + held, v0:v0 + size], part,
                       out=out[first:first + held, v0:v0 + size])
    return out


def logits_rows(params, ids, rows, config, routing=None,
                tie_margin=TIE_MARGIN):
    """Reference logits (float32, (len(rows), V)) of one sequence at the
    given positions only; the whole context is read. With ``tie_margin``
    (the default: this module's docstring) a row is the upper envelope of
    its candidate routings' logits, each relative to its own maximum, so
    that ``max - picked`` is the served token's smallest gap under any of
    them; 0 gives every row's plain logits."""
    inputs = []
    h = hidden(params, _padded(ids), config, routing, inputs)
    scale, eps = params["final_norm"]["scale"], float(config["rms_norm_eps"])
    if not tie_margin:
        return _head(h[:, np.asarray(rows)], scale,
                     params["embed"]["lm_head"], eps)
    blocks = candidate_hidden(params, inputs, rows, config, tie_margin)
    counts = np.bincount(np.concatenate([src for src, _ in blocks]))
    print(f"glm4_moe_lite_reference: {len(rows)} rows compared under "
          f"{counts.sum()} candidate routings (at most {counts.max()} a row, "
          f"{len(blocks)} blocks) within {tie_margin}", file=sys.stderr,
          flush=True)
    return envelope(blocks, len(rows), scale, params["embed"]["lm_head"],
                    eps)


def mtp_logits_rows(params, ids, rows, config, routing=None):
    """The prediction module's logits (float32, (len(rows), V)) at the
    given positions: row i, which reads ``ids[: i + 2]``, scores the token
    at i + 2. Positions up to ``len(ids) - 2``."""
    assert max(rows) <= len(ids) - 2, (max(rows), len(ids))
    padded = _padded(ids)
    h = mtp_hidden(params, hidden(params, padded, config), padded, config,
                   routing)
    module_norm = params["mtp"]["norm"]["scale"][0]
    return _head(h[:, np.asarray(rows)], module_norm,
                 params["embed"]["lm_head"], float(config["rms_norm_eps"]))
