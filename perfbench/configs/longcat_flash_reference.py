"""Plain reference of LongCat-Flash-Omni's language model, one chip's share
of an expert-parallel layer: straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernels, no cache, no pages, no absorbed
products, no grouped product, no sort. Independent of the program's model
code: it takes only the program's WEIGHTS (the pytree ``models.CausalLM.init``
makes for a shortcut-connected stack: ``embed.tok``, ``embed.lm_head``,
``final_norm``, and ``layers`` stacked over layers with
``attn.{wq_a,q_norm,wq_b,wkv_a,kv_norm,wkv_b,wo}``, ``mlp.{wi_gate,wi_up,wo}``,
``norm1``, ``norm2`` each stacked over the layer's PAIR behind the layer
axis, and ``moe.{router,router_bias,wi_gate,wi_up,wo}`` with the three
expert matrices stacked over the experts HELD) and the sizes from the
configuration file.

Written from the published ``config.json``
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni) and ISSUE 34's
equations. E hidden, H heads, ranks r_q / r_kv, head widths d_n (nope), d_r
(rope), d_v (value).

MLA(a), the EXPANDED form, for normalised input ``a`` at position t:

    c_q = rms(a W_qa);  [q_nope_h | q_rope_h] = s_q (c_q W_qb)_h
    [u | r] = a W_kva;  c = s_kv rms(u);  k_rope = RoPE(r, t), one head
    shared by every query head, not scaled;  q_rope_h = RoPE(q_rope_h, t)
    [k_nope_h | v_h] = (c W_kvb)_h
    score (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(d_n + d_r),
    causal, softmax in float32;  y = concat_h(P_h v_h) W_o

    s_q = sqrt(E / r_q), s_kv = sqrt(E / r_kv) where ``mla_scale_q_lora`` /
    ``mla_scale_kv_lora`` are set (assumed: the config has the flags, not
    the formula). RoPE on interleaved pairs (x_2i, x_2i+1), angle
    t theta^(-2i / d_r) (assumed: the DeepSeek-V3 family's layout).

Routed block R(m), router width X = published experts + zero experts:

    p = softmax_float32(m W_r) over all X;  S = the ``moe_topk`` largest of
    p + b (``router_bias``, for the choice only);  w_i = f p_i
    (``routed_scaling_factor``), not renormalised
    R_here(m) = sum_{i in S, first <= i < first + held} w_i FFN_i(m)
                + (sum_{i in S, i >= X - zero_expert_num} w_i) m
    FFN_i(m) = (silu(m Wg_i) * (m Wu_i)) Wd_i

    What the experts of other chips would have added is left out, as in
    the program; ``first`` is 0 (this chip holds experts 0 .. held - 1).

Layer, N = RMSNorm, F_0 / F_1 the dense gated FFNs:

    h1 = x + MLA_0(N_a0(x));  m = N_b0(h1);  s = R(m);  h2 = h1 + F_0(m)
    h3 = h2 + MLA_1(N_a1(h2));  h4 = h3 + F_1(N_b1(h3));  y = h4 + s

then a last RMSNorm and an untied head. Departures, same mathematics:
attention runs a group of heads and a block of queries at a time against
every key (a whole S x S score matrix of 15k tokens over 64 heads would not
fit beside the system under test; the mask is the same), each group's
part of the output projection summed into the stream's own buffer a block
of tokens at a time; the dense FFNs run a block of tokens and of their
width at a time, summed into that buffer too (the stream is donated: a
caller's array handed to ``layer`` or as ``into`` is consumed); every held
expert is
computed for every token, its gated product weighted by w where the expert
is among the token's choices and by 0 where it is not, before the down
projection; weights are upcast a matrix (a block of one) or an expert at a
time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128
HEAD_BLOCK = 8
TOKEN_BLOCK = 1024       # contexts are padded to a multiple of it
WIDTH_BLOCK = 4096       # of a dense FFN's width at a time
F32 = jnp.float32


@functools.partial(jax.jit, donate_argnums=0)
def _add(a, b):
    """a + b in a's buffer: at 15k tokens a (T, E) float32 is 0.38 GB beside
    the system under test, so sums are made in place."""
    return a + b


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("t0",))
def _add_at(a, block, *, t0):
    """a with ``block`` added at token ``t0`` on, in a's buffer."""
    return a.at[:, t0:t0 + block.shape[1]].add(block)


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


# the stream's norms as one program each: op by op, a norm of 15k tokens
# leaves two more (T, E) float32 arrays beside its result
_norm = jax.jit(_rms_norm, static_argnums=2)


def scales(config):
    """(s_q, s_kv)."""
    e = float(config["hidden_size"])
    return (np.sqrt(e / config["q_lora_rank"])
            if config.get("mla_scale_q_lora") else 1.0,
            np.sqrt(e / config["kv_lora_rank"])
            if config.get("mla_scale_kv_lora") else 1.0)


def rope(x, theta):
    """x: (B, T, heads, d_r) at positions 0..T-1, interleaved pairs."""
    d = x.shape[-1]
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v):
    """q, k: (B, T, G, D); v: (B, T, G, Dv), T a multiple of ``Q_BLOCK``.
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


@functools.partial(jax.jit, static_argnames=("eps", "s_kv", "theta", "d_r"))
def latents(attn, x, *, eps, s_kv, theta, d_r):
    """(c_q (B, T, r_q), c (B, T, r_kv), k_rope (B, T, 1, d_r)) of normalised
    input x."""
    with jax.default_matmul_precision("highest"):
        c_q = jnp.einsum("bte,er->btr", x, attn["wq_a"].astype(F32))
        kv = jnp.einsum("bte,er->btr", x, attn["wkv_a"].astype(F32))
    c = s_kv * _rms_norm(kv[..., :-d_r], attn["kv_norm"]["scale"], eps)
    return (_rms_norm(c_q, attn["q_norm"]["scale"], eps), c,
            rope(kv[..., None, -d_r:], theta))


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("s_q", "theta", "d_n", "block"))
def _add_heads(y, attn, c_q, c, k_rope, first, *, s_q, theta, d_n, block):
    """y + the attention of heads [first, first + block) through their part
    of the output projection, in y's buffer, a block of tokens at a time."""
    with jax.default_matmul_precision("highest"):
        def of(w, axis):
            return jax.lax.dynamic_slice_in_dim(
                w, first, block, axis).astype(F32)
        q = s_q * jnp.einsum("btr,rhd->bthd", c_q, of(attn["wq_b"], 1))
        kv = jnp.einsum("btr,rhd->bthd", c, of(attn["wkv_b"], 1))
        q = jnp.concatenate([q[..., :d_n], rope(q[..., d_n:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :d_n],
             jnp.broadcast_to(k_rope, kv.shape[:3] + k_rope.shape[-1:])], -1)
        a = _attention(q / np.sqrt(q.shape[-1]), k, kv[..., d_n:])
        wo = of(attn["wo"], 0)

        def project(i, y):
            t0 = i * Q_BLOCK
            ab = jax.lax.dynamic_slice_in_dim(a, t0, Q_BLOCK, axis=1)
            yb = jax.lax.dynamic_slice_in_dim(y, t0, Q_BLOCK, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                y, yb + jnp.einsum("bthd,hde->bte", ab, wo), t0, axis=1)

        return jax.lax.fori_loop(0, y.shape[1] // Q_BLOCK, project, y)


def mla(attn, x, config, into=None):
    """MLA(x) of normalised input x (B, T, E), expanded form; added to
    ``into`` in its buffer where that is given."""
    s_q, s_kv = scales(config)
    theta = float(config["rope_theta"])
    y = jnp.zeros_like(x) if into is None else into
    c_q, c, k_rope = latents(attn, x, eps=float(config["rms_norm_eps"]),
                             s_kv=float(s_kv), theta=theta,
                             d_r=int(config["qk_rope_head_dim"]))
    heads = int(config["num_attention_heads"])
    for first in range(0, heads, HEAD_BLOCK):
        y = _add_heads(y, attn, c_q, c, k_rope, first, s_q=float(s_q),
                       theta=theta, d_n=int(config["qk_nope_head_dim"]),
                       block=min(HEAD_BLOCK, heads))
    return y


@jax.jit
def _dense_block(x, wi_gate, wi_up, wo):
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,ef->btf", x, wi_gate.astype(F32)))
        up = jnp.einsum("bte,ef->btf", x, wi_up.astype(F32))
        return jnp.einsum("btf,fe->bte", gate * up, wo.astype(F32))


def add_dense(h, mlp, at, x):
    """h + F(x) in h's buffer, a block of the width and of tokens at a time;
    ``mlp`` is the stack of every layer's pair and ``at`` (layer, which of
    the pair). Each block of the width is sliced once and waited for, so
    no more than one is on the device."""
    i, j = at
    for f0 in range(0, mlp["wo"].shape[-2], WIDTH_BLOCK):
        f1 = f0 + WIDTH_BLOCK
        w = (mlp["wi_gate"][i, j, :, f0:f1], mlp["wi_up"][i, j, :, f0:f1],
             mlp["wo"][i, j, f0:f1])
        for t0 in range(0, x.shape[1], TOKEN_BLOCK):
            h = _add_at(h, _dense_block(x[:, t0:t0 + TOKEN_BLOCK], *w), t0=t0)
        h = jax.block_until_ready(h)
    return h


@functools.partial(jax.jit, static_argnames=("top_k", "factor"))
def route(m, router, bias, *, top_k, factor):
    """(weights (B, T, X) float32 with w_i at the token's ``top_k`` choices
    and 0 elsewhere, the choices (B, T, top_k), the scores p)."""
    with jax.default_matmul_precision("highest"):
        logits = jnp.einsum("bte,ex->btx", m, router.astype(F32))
    p = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(p + bias.astype(F32), top_k)
    weights = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=F32),
                      axis=-2) * p * factor
    return weights, chosen, p


@jax.jit
def _expert(x, w, wi_gate, wi_up, wo):
    """Wd(w * silu(Wg x) * Wu x) of one expert over every token."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,ef->btf", x, wi_gate.astype(F32)))
        up = jnp.einsum("bte,ef->btf", x, wi_up.astype(F32))
        return jnp.einsum("btf,fe->bte", gate * up * w[..., None],
                          wo.astype(F32))


def identity_part(m, weights, config):
    """(sum of the chosen zero experts' weights) m."""
    zero = int(config["zero_expert_num"])
    return jnp.sum(weights[..., weights.shape[-1] - zero:], -1,
                   keepdims=True) * m


def routed_block(m, moe, layer, config, first=0, routing=None):
    """R_here(m) of layer ``layer``: the held experts' part (``moe``'s
    matrices, stacked over layers and experts, are experts ``first`` .. of
    the router's outputs) and the identity part. ``routing``, a list,
    receives (choices, scores)."""
    weights, chosen, p = route(
        m, moe["router"][layer], moe["router_bias"][layer],
        top_k=int(config["moe_topk"]),
        factor=float(config["routed_scaling_factor"]))
    if routing is not None:
        routing.append((chosen, p))
    out = identity_part(m, weights, config)
    for i in range(moe["wo"].shape[1]):     # an expert sliced once, waited for
        w = [moe[name][layer, i] for name in ("wi_gate", "wi_up", "wo")]
        for t0 in range(0, m.shape[1], TOKEN_BLOCK):
            t1 = t0 + TOKEN_BLOCK
            out = _add_at(out, _expert(
                m[:, t0:t1], weights[:, t0:t1, first + i], *w), t0=t0)
        out = jax.block_until_ready(out)
    return out


def layer(x, layers, i, config, routing=None):
    """Shortcut-connected layer ``i`` of ``layers`` (``params["layers"]``:
    an expert or a matrix is sliced where it is used)."""
    eps = float(config["rms_norm_eps"])

    def attn(j):
        return jax.tree.map(lambda w: w[i, j], layers["attn"])

    def norm(name, j, h):
        return _norm(h, layers[name]["scale"][i, j], eps)

    h = mla(attn(0), norm("norm1", 0, x), config, into=x)             # h1
    del x
    m = norm("norm2", 0, h)
    s = routed_block(m, layers["moe"], i, config, routing=routing)
    h = add_dense(h, layers["mlp"], (i, 0), m)                        # h2
    del m
    h = mla(attn(1), norm("norm1", 1, h), config, into=h)             # h3
    h = add_dense(h, layers["mlp"], (i, 1), norm("norm2", 1, h))      # h4
    return _add(h, s)                                                 # y


@jax.jit
def _embed(tok, ids):
    return tok[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(h, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bte,ev->btv", _rms_norm(h, scale, eps),
                          lm_head.astype(F32))


def hidden(params, ids, config, routing=None):
    """(B, T) token ids -> (B, T, E) float32, before the last norm."""
    h = _embed(params["embed"]["tok"], ids)
    layers = params["layers"]
    for i in range(jax.tree.leaves(layers)[0].shape[0]):
        h = layer(h, layers, i, config, routing)
    return h


def logits_rows(params, ids, rows, config, routing=None):
    """Reference logits (float32, (len(rows), V)) of one sequence at the
    given positions only; the whole context is read."""
    ids = np.asarray(ids, np.int32)
    # causal: a zero tail changes nothing before it; few distinct shapes
    padded = np.zeros((1, -(-len(ids) // TOKEN_BLOCK) * TOKEN_BLOCK), np.int32)
    padded[0, :len(ids)] = ids
    h = hidden(params, jnp.asarray(padded), config, routing)
    picked = h[:, np.asarray(rows)]
    return np.asarray(_logits(picked, params["final_norm"]["scale"],
                              params["embed"]["lm_head"],
                              eps=float(config["rms_norm_eps"]))[0])
