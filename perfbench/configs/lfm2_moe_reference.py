"""Plain reference of LFM2-24B-A2B (``model_type`` ``lfm2_moe``):
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no pages, no carried tail, no grouped product, no sort.
Independent of the program's model code: it takes only the program's
WEIGHTS (the pytree ``models.CausalLM.init`` makes for a stack whose
``mixer_pattern`` is as long as the stack: ``embed.tok`` (the head is tied
to it), ``final_norm``, ``layers.g{j}`` layer j, each leaf with a leading
axis of 1; a conv layer's ``attn.{w_in,conv,w_out}``, an attention layer's
``attn.{wq,wk,wv,wo,q_norm,k_norm}``, ``norm1``, ``norm2``, a dense
layer's ``mlp.{wi_gate,wi_up,wo}``, a routed layer's stacked over its
experts beside ``mlp.{router,router_bias}``) and the sizes from the
configuration file. Which mixer and which FFN a layer has is read off its
weights' names.

Written from the published ``config.json``
(https://huggingface.co/LiquidAI/LFM2-24B-A2B) and ISSUE 51's equations
(the ``lfm2_moe`` family's). E hidden, N = RMSNorm with a plain weight, eps
``norm_eps``. Layer i: h = x + Mixer_i(N_op(x)); y = h + FFN_i(N_ffn(h)).
After the last layer one RMSNorm (the checkpoint's ``embedding_norm``),
then the head, tied to the embedding.

Gated short convolution (``layer_types[i] == "conv"``), input u:

    [B | C | X] = u W_in  (E -> 3 E, no bias, that order);  g = B * X
    c_t = sum_{j=0..K-1} w[j] * g_{t-(K-1)+j}  (depthwise, causal,
    ``conv_L_cache`` K = 3 taps, no bias, NO activation, zeros before the
    first token);  out = (C * c) W_out

Attention (``"full_attention"``): q, k, v without bias, H / KVH heads of
D = E / H (64); RMSNorm over the D lanes of every q and k head (one weight
of D each, shared by the heads) BEFORE RoPE; RoPE theta on all D lanes,
split halves (x1 = lanes [0, D/2), x2 = the rest: x1 cos - x2 sin | x2 cos
+ x1 sin, angle t theta^(-2i / D)); scale 1 / sqrt(D); causal; softmax in
float32; ``out_proj``. Query head h reads KV head h // (H / KVH).

FFN: a layer whose ``mlp`` has no router: W2(silu(W1 m) * W3 m) of
``intermediate_size``. The others:

    s = sigmoid(m W_r);  S = the ``num_experts_per_tok`` largest of s + b
    (``expert_bias``, for the choice only);
    w_i = f s_i / (sum_S s + 1e-6)  (``norm_topk_prob``; f =
    ``routed_scaling_factor`` = 1);  y = sum_{i in S} w_i FFN_i(m), each a
    gated SiLU FFN of ``moe_intermediate_size``; no shared expert.

Departures, same mathematics: attention runs a block of queries at a time
against every key (the mask is the same); every expert is computed for
every token, its gated product weighted by w where the expert is among the
token's choices and by 0 where it is not, before the down projection, a
block of tokens at a time; the dense FFN runs a block of its width at a
time; the head runs a block of rows and of the vocabulary at a time;
weights are upcast a matrix, an expert or a block at a time; the
convolution is K shifted copies of g summed.

NEAR-TIES OF THE ROUTER (``logits_rows``' ``tie_margin``, on by default; 0
gives every row's plain logits, which the tests compare). The reading is
the one ``glm4_moe_lite_reference.py`` documents, in this file's own code:
with sigmoid scores renormalised over the chosen 4 the marginal expert
carries a quarter of the layer's routed output; where the 4th and the 5th
of s + b lie closer than the precision the configuration states resolves,
a bfloat16 system and this float32 reference may each take another expert,
neither is wrong, and the logits move by whole units. So EVERY row is
compared, and a row at a near-tie is held to the nearest of the routings a
sound system may have taken (``candidate_hidden``):

- the context is the plain forward's: every token's keys and values in an
  attention layer, and every token's convolution input g in a conv layer,
  whatever its own near-ties;
- the row's own token is walked through the stack once more: in an
  attention layer a query against that context, in a conv layer its own g
  behind the context's g at the K - 1 positions before it. In a routed
  layer every set S of 4 experts that a perturbation of s + b under
  ``tie_margin`` could make the top 4 (min over S + margin > max over the
  others; the plain choice is one of them) continues as a candidate of its
  own, with the weights of ITS set, and meets the next layers' near-ties at
  its own hidden state;
- the row comes back as the upper envelope of its candidates' logits, each
  taken relative to its own maximum: max_c (L_c - max L_c). The harness
  reads ``max - picked``: the SMALLEST gap the served token has under any
  candidate. A row without a near-tie has one candidate, its plain logits
  (minus their maximum).

A fault of the mask, the position, the page, the tail, a kernel or an
expert moves the logits of the plain choice and of every other candidate
alike. Candidates travel in blocks of ``TOKEN_BLOCK`` (one program a step
whatever their number, every expert for every candidate under the
candidate's own weights: the plain forward's programs at its shapes) and
lie on the host between layers, and the envelope is made on the device, a
block of candidates and of the vocabulary at a time: beside a server that
fills the chip the walk holds one block.
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
#: (the docstring). GLM-4.7-Flash's file chose 2^-7 for the same router at
#: the same widths (a sound bfloat16 run's scores differ from a float32
#: reference's by about 0.0015 before any routing differs; measured here:
#: 0.0011 at the first routed layer, PERF.md PR 51). What a conv layer hands
#: a row from the two positions before it (a NEIGHBOUR's near-tie taken the
#: other way) no candidate of the row's own covers: with the taps drawn as
#: the family draws them (std 0.02) sound runs read at most 0.02 of the
#: harness's 0.25 under this margin, and a lost or a stale tail 1.4-3
#: (PERF.md PR 51: the controls)
TIE_MARGIN = 2.0 ** -7
#: candidates a row may have before the walk gives up (a margin so wide
#: that most sets are candidates compares nothing)
MAX_CANDIDATES = 4096
#: the candidates' sets are looked for among this many of the largest s + b
TIE_POOL = 8
#: added to the sum the chosen scores are renormalised by (``lfm2_moe``)
NORM_EPS = 1e-6
F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, theta, positions):
    """x: (T, heads, D) at ``positions`` (T,); split halves, all D lanes."""
    d = x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = positions.astype(F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]
    sin = jnp.sin(angles)[:, None, :]
    cos = jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sizes(config):
    theta = config.get("rope_theta") \
        or config["rope_parameters"]["rope_theta"]
    return dict(eps=float(config["norm_eps"]), theta=float(theta))


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def queries_keys_values(attn, a, positions, *, eps, theta):
    """Normalised inputs ``a`` (T, E) at ``positions`` (T,) -> q (T, H, D)
    scaled, k (T, KVH, D), v (T, KVH, D): the head norms, then RoPE."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("te,ehd->thd", a, attn["wq"].astype(F32))
        k = jnp.einsum("te,ehd->thd", a, attn["wk"].astype(F32))
        v = jnp.einsum("te,ehd->thd", a, attn["wv"].astype(F32))
    q = rope(_rms_norm(q, attn["q_norm"]["scale"], eps), theta, positions)
    k = rope(_rms_norm(k, attn["k_norm"]["scale"], eps), theta, positions)
    return q / np.sqrt(q.shape[-1]), k, v


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def attention_mixer(attn, a, *, eps, theta):
    """The attention mixer of normalised inputs ``a`` (T, E), T a multiple
    of ``Q_BLOCK``: causal, a block of queries at a time."""
    t = a.shape[0]
    q, k, v = queries_keys_values(attn, a, jnp.arange(t), eps=eps,
                                  theta=theta)
    kvh = k.shape[1]
    q = q.reshape(t, kvh, -1, q.shape[-1])          # (T, KVH, G, D)

    def block(at):
        qb = jax.lax.dynamic_slice_in_dim(q, at, Q_BLOCK, axis=0)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("qkgd,tkd->kgqt", qb, k)
            mask = jnp.arange(t)[None, :] <= at + jnp.arange(Q_BLOCK)[:, None]
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    out = out.reshape((t, -1, out.shape[-1]))       # (T, H, D)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thd,hde->te", out, attn["wo"].astype(F32))


@jax.jit
def conv_gates(attn, a):
    """Normalised inputs ``a`` (T, E) -> the convolution's input g = B * X
    and the output gate C, both (T, E)."""
    e = a.shape[-1]
    with jax.default_matmul_precision("highest"):
        bcx = jnp.einsum("te,ef->tf", a, attn["w_in"].astype(F32))
    return bcx[:, :e] * bcx[:, 2 * e:], bcx[:, e:2 * e]


@jax.jit
def conv_mixer(attn, a):
    """The gated short convolution of normalised inputs ``a`` (T, E)."""
    g, gate = conv_gates(attn, a)
    taps = attn["conv"].astype(F32)                 # (K, E)
    k, t = taps.shape[0], a.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, g.shape[1]), F32), g])
    c = sum(taps[j][None, :] * ext[j:j + t] for j in range(k))
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("tf,fe->te", gate * c, attn["w_out"].astype(F32))


@jax.jit
def _ffn(x, wi_gate, wi_up, wo):
    """(silu(x Wg) * (x Wu)) Wd."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("te,ef->tf", x, wi_gate.astype(F32)))
        up = jnp.einsum("te,ef->tf", x, wi_up.astype(F32))
        return jnp.einsum("tf,fe->te", gate * up, wo.astype(F32))


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
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + NORM_EPS)
    return picked * factor


def _routing_sizes(config):
    return dict(factor=float(config["routed_scaling_factor"]),
                normalise=bool(config["norm_topk_prob"]))


def route(m, router, bias, config):
    """(weights (T, X) float32 with w_i at the token's
    ``num_experts_per_tok`` choices and 0 elsewhere, the choices (T, k),
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
        gate = jax.nn.silu(jnp.einsum("te,ef->tf", x, wi_gate.astype(F32)))
        up = jnp.einsum("te,ef->tf", x, wi_up.astype(F32))
        return jnp.einsum("tf,fe->te", gate * up * w[:, None],
                          wo.astype(F32))


def _experts(mlp):
    """How many experts a routed layer's ``mlp`` holds."""
    stacks, _ = mlp["experts"]
    return stacks["wo"].shape[1]


def _expert_weights(mlp, i):
    """Expert i's three matrices, cut out of the stacks where they lie
    (``_layers``)."""
    stacks, layer = mlp["experts"]
    return [stacks[name][layer, i] for name in ("wi_gate", "wi_up", "wo")]


def experts_out(m, weights, mlp):
    """sum_i w_i FFN_i(m) for given weights (T, X), T a multiple of
    ``TOKEN_BLOCK``."""
    out = jnp.zeros_like(m)
    for i in range(_experts(mlp)):
        w = _expert_weights(mlp, i)
        parts = [_expert(m[t0:t0 + TOKEN_BLOCK],
                         weights[t0:t0 + TOKEN_BLOCK, i], *w)
                 for t0 in range(0, m.shape[0], TOKEN_BLOCK)]
        out = jax.block_until_ready(out + jnp.concatenate(parts))
    return out


def ffn(m, mlp, config, routing=None):
    """FFN_i(m): routed where the layer has a router. ``routing``, a list,
    receives (choices, scores, the margin of the choice)."""
    if "router" not in mlp:
        return dense_ffn(mlp, m)
    weights, chosen, s, apart = route(m, mlp["router"], mlp["router_bias"],
                                      config)
    if routing is not None:
        routing.append((chosen, s, apart))
    return experts_out(m, weights, mlp)


def layer(x, lp, config, routing=None):
    """One layer of weights ``lp`` over x (T, E)."""
    sizes = _sizes(config)
    a = _rms_norm(x, lp["norm1"]["scale"], sizes["eps"])
    if "w_in" in lp["attn"]:
        h = x + conv_mixer(lp["attn"], a)
    else:
        h = x + attention_mixer(lp["attn"], a, **sizes)
    return h + ffn(_rms_norm(h, lp["norm2"]["scale"], sizes["eps"]),
                   lp["mlp"], config, routing)


def _layers(params):
    """Every layer of the stack in order, its leaves sliced, but for a
    routed layer's stacked experts: a slice of those is a copy of 1.2 GB
    beside a server that fills the chip, so ``mlp["experts"]`` is (the
    group's stacks as they lie, the layer's index in them) and an expert is
    cut out when it is computed (``_expert_weights``)."""
    groups = params["layers"]
    for name in sorted(groups, key=lambda g: int(g[1:])):
        group = groups[name]
        for i in range(jax.tree.leaves(group)[0].shape[0]):
            mlp = group["mlp"]
            stacks = {n: mlp[n] for n in mlp if "router" in mlp
                      and not n.startswith("router")}
            lp = jax.tree.map(lambda w: w[i], {
                **group, "mlp": {n: mlp[n] for n in mlp if n not in stacks}})
            if stacks:
                lp["mlp"]["experts"] = (stacks, i)
            yield lp


def hidden(params, ids, config, routing=None, inputs=None):
    """(T,) token ids -> (T, E) float32, before the last norm. ``inputs``,
    a list, receives every layer's input."""
    h = params["embed"]["tok"][ids].astype(F32)
    for lp in _layers(params):
        if inputs is not None:
            inputs.append(h)
        h = layer(h, lp, config, routing)
    return h


def _padded(ids):
    """(T',) int32, T' the next multiple of ``TOKEN_BLOCK``: causal, so a
    zero tail changes nothing before it; few distinct shapes."""
    ids = np.asarray(ids, np.int32)
    padded = np.zeros((-(-len(ids) // TOKEN_BLOCK) * TOKEN_BLOCK,), np.int32)
    padded[:len(ids)] = ids
    return jnp.asarray(padded)


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _head_at(h, scale, tok, v0, *, eps, size):
    """Head(N(h)) of rows h (n, E) at the vocabulary's [v0, v0 + size); the
    head is the embedding."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(
            "ne,ve->nv", _rms_norm(h, scale, eps),
            jax.lax.dynamic_slice_in_dim(tok, v0, size, axis=0).astype(F32))


def _vocab_blocks(vocab):
    size = min(VOCAB_BLOCK, vocab)
    return size, [min(v0, vocab - size) for v0 in range(0, vocab, size)]


def _head(h, scale, tok, eps):
    """Head(N(h)) of picked rows h (R, E) -> (R, V) on the host, a block of
    rows and of the vocabulary at a time."""
    size, starts = _vocab_blocks(tok.shape[0])
    out = np.empty((h.shape[0], tok.shape[0]), np.float32)
    for r0 in range(0, h.shape[0], ROW_BLOCK):
        for v0 in starts:
            out[r0:r0 + ROW_BLOCK, v0:v0 + size] = np.asarray(_head_at(
                h[r0:r0 + ROW_BLOCK], scale, tok, v0, eps=eps, size=size))
    return out


# ---- a row's candidate routings -------------------------------------------

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


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def attend(lp, h, pos, k_ctx, v_ctx, *, eps, theta):
    """An attention layer's mixer half for single tokens against a given
    context: row i, hidden state ``h[i]`` (n, E) at position ``pos[i]``,
    attends the context's keys and values (``queries_keys_values`` of the
    layer's normalised plain input) at the positions before its own, and
    its own. Returns h + Mixer(N_op h). n a multiple of ``Q_BLOCK``."""
    attn = lp["attn"]
    a = _rms_norm(h, lp["norm1"]["scale"], eps)
    q, k_own, v_own = queries_keys_values(attn, a, pos, eps=eps, theta=theta)
    kvh = k_own.shape[1]
    q = q.reshape(q.shape[0], kvh, -1, q.shape[-1])     # (n, KVH, G, D)

    def block(i):
        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, i, Q_BLOCK, axis=0)
        qb = cut(q)
        before = jnp.arange(k_ctx.shape[0])[None, :] < cut(pos)[:, None]
        with jax.default_matmul_precision("highest"):
            s_ctx = jnp.where(before[:, None, None, :],
                              jnp.einsum("qkgd,tkd->qkgt", qb, k_ctx),
                              -jnp.inf)
            s_own = jnp.sum(qb * cut(k_own)[:, :, None, :], -1,
                            keepdims=True)
            p = jax.nn.softmax(jnp.concatenate([s_ctx, s_own], -1), axis=-1)
            return jnp.einsum("qkgt,tkd->qkgd", p[..., :-1], v_ctx) \
                + p[..., -1:] * cut(v_own)[:, :, None, :]

    out = jax.lax.map(block, jnp.arange(0, h.shape[0], Q_BLOCK))
    out = out.reshape((h.shape[0], -1, out.shape[-1]))  # (n, H, D)
    with jax.default_matmul_precision("highest"):
        return h + jnp.einsum("nhd,hde->ne", out, attn["wo"].astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def convolve_at(lp, h, pos, g_ctx, *, eps):
    """A conv layer's mixer half for single tokens against a given context:
    row i's own g behind the context's g (``conv_gates`` of the layer's
    normalised plain input, (T, E)) at the K - 1 positions before
    ``pos[i]`` (zeros before the first token). Returns h + Mixer(N_op h)."""
    attn = lp["attn"]
    g_own, gate = conv_gates(attn, _rms_norm(h, lp["norm1"]["scale"], eps))
    taps = attn["conv"].astype(F32)
    k = taps.shape[0]
    c = taps[k - 1][None, :] * g_own
    for j in range(k - 1):
        at = pos - (k - 1) + j
        c = c + taps[j][None, :] * jnp.where(
            (at >= 0)[:, None], g_ctx[jnp.maximum(at, 0)], 0.0)
    with jax.default_matmul_precision("highest"):
        return h + jnp.einsum("nf,fe->ne", gate * c,
                              attn["w_out"].astype(F32))


def candidate_hidden(params, inputs, rows, config, tie_margin):
    """The tokens at ``rows`` walked through the stack once more, each
    against the plain forward's context (``inputs``: ``hidden``'s, every
    layer's input (T, E)); in a routed layer every set of
    ``candidate_sets`` goes on as a candidate of its own. Candidates travel
    in blocks of at most ``TOKEN_BLOCK`` (padded to it on the device: one
    program a step whatever their number), in the order of their rows, and
    lie on the HOST between layers, so that the walk holds one block on the
    device beside the server whatever the candidates' number; a block's
    experts are the plain forward's ``experts_out`` under the candidates'
    own weights (the programs the plain forward has loaded, at its shapes).
    Returns the blocks: [(the rows of its candidates (k,), their hidden states before
    the last norm (k, E), numpy)]."""
    top_k, sizes = int(config["num_experts_per_tok"]), _sizes(config)
    eps, routing = sizes["eps"], _routing_sizes(config)
    rows = np.asarray(rows)
    blocks = [(src, np.asarray(inputs[0][rows[src]]))
              for src in np.array_split(np.arange(len(rows)),
                                        -(-len(rows) // TOKEN_BLOCK))]
    for lp, x in zip(_layers(params), inputs):
        a = _rms_norm(x, lp["norm1"]["scale"], eps)
        if "w_in" in lp["attn"]:
            g_ctx = conv_gates(lp["attn"], a)[0]
            mix = functools.partial(convolve_at, g_ctx=g_ctx, eps=eps)
        else:
            _, k_ctx, v_ctx = queries_keys_values(
                lp["attn"], a, jnp.arange(x.shape[0]), **sizes)
            mix = functools.partial(attend, k_ctx=k_ctx, v_ctx=v_ctx, **sizes)
        routed = "router" in lp["mlp"]
        mixer = {name: lp[name] for name in ("attn", "norm1")}
        halves = []
        for src, h in blocks:
            h = mix(mixer, jnp.asarray(_padded_block(h)), jnp.asarray(
                _padded_block(rows[src].astype(np.int32))))
            m = _rms_norm(h, lp["norm2"]["scale"], eps)
            if routed:
                halves.append(tuple(np.asarray(v)[:len(src)] for v in (
                    h, m, scores(m, lp["mlp"]["router"]))))
            else:
                halves.append((np.asarray(
                    h + dense_ffn(lp["mlp"], m))[:len(src)],))
        if not routed:
            blocks = [(src, h) for (src, _), (h,) in zip(blocks, halves)]
            continue
        # every candidate that goes on: its parent among the blocks laid
        # end to end, and its own set; then cut into blocks again
        h, m, s = (np.concatenate(part) for part in zip(*halves))
        bias = np.asarray(lp["mlp"]["router_bias"], np.float32)
        parent, chosen = candidate_sets(s + bias, top_k, tie_margin)
        src = np.concatenate([src for src, _ in blocks])[parent]
        most = np.bincount(src).max()
        if most > MAX_CANDIDATES:
            raise ValueError(f"{most} candidate routings of one row within "
                             f"{tie_margin}: a margin that compares nothing")
        picked = np.take_along_axis(s[parent], chosen, axis=1)
        if routing["normalise"]:
            picked = picked / (picked.sum(1, keepdims=True) + NORM_EPS)
        weights = np.zeros((len(src), s.shape[1]), np.float32)
        np.put_along_axis(weights, chosen, picked * routing["factor"], axis=1)
        blocks = []
        for c0 in range(0, len(src), TOKEN_BLOCK):
            cut = slice(c0, c0 + TOKEN_BLOCK)
            at = parent[cut]
            y = experts_out(jnp.asarray(_padded_block(m[at])),
                            jnp.asarray(_padded_block(weights[cut])),
                            lp["mlp"])
            blocks.append((src[cut], h[at] + np.asarray(y)[:len(at)]))
    return blocks


@functools.partial(jax.jit, static_argnames=("eps", "size"))
def _envelope_at(h, top, row, scale, tok, v0, *, eps, size):
    """max over the candidates of each row of (their logits - their own
    maximum ``top``), at the vocabulary's [v0, v0 + size): (n + 1, size),
    row ``row[i]`` of candidate i; the padding's row is n."""
    logits = _head_at(h, scale, tok, v0, eps=eps, size=size)
    return jax.ops.segment_max(logits - top[:, None], row,
                               num_segments=h.shape[0] + 1,
                               indices_are_sorted=True)


def envelope(blocks, n_rows, scale, tok, eps):
    """(n_rows, V) float32 on the host: each row the upper envelope of its
    candidates' logits, each relative to its own maximum. Made on the device
    a block of candidates and of the vocabulary at a time; only a block's
    rows come to the host."""
    vocab = tok.shape[0]
    size, starts = _vocab_blocks(vocab)
    out = np.full((n_rows, vocab), -np.inf, np.float32)
    for src, h in blocks:
        h = jnp.asarray(_padded_block(h))
        top = functools.reduce(jnp.maximum, (
            _head_at(h, scale, tok, v0, eps=eps, size=size).max(-1)
            for v0 in starts))
        first, held = src[0], src[-1] - src[0] + 1
        row = jnp.asarray(_padded_block((src - first).astype(np.int32),
                                        fill=TOKEN_BLOCK))
        for v0 in starts:
            part = np.asarray(_envelope_at(
                h, top, row, scale, tok, v0, eps=eps,
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
    scale, eps = params["final_norm"]["scale"], float(config["norm_eps"])
    tok = params["embed"]["tok"]
    if not tie_margin:
        return _head(h[np.asarray(rows)], scale, tok, eps)
    blocks = candidate_hidden(params, inputs, rows, config, tie_margin)
    counts = np.bincount(np.concatenate([src for src, _ in blocks]))
    print(f"lfm2_moe_reference: {len(rows)} rows compared under "
          f"{counts.sum()} candidate routings (at most {counts.max()} a row, "
          f"{len(blocks)} blocks) within {tie_margin}", file=sys.stderr,
          flush=True)
    return envelope(blocks, len(rows), scale, tok, eps)
