"""Plain reference of Qwen3-Next-80B-A3B, one chip's share of an
expert-parallel layer: straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernels, no cache, no pages, no chunked
delta rule, no grouped product, no sort. Independent of the program's model
code: it takes only the program's WEIGHTS (the pytree ``models.CausalLM.init``
makes for a stack of mixed mixers: ``embed.tok``, ``embed.lm_head``,
``final_norm``, and ``layers.g{j}`` for the layers at place j of a period of
``full_attention_interval``, each stacked over the periods, with ``norm1``,
``norm2``, ``mlp.{router,wi_gate,wi_up,wo,shared_wi_gate,shared_wi_up,
shared_wo,shared_gate}`` (the three expert matrices stacked over the experts
HELD) and under ``attn`` either a Gated DeltaNet's ``{w_qkvz,w_ba,conv,
A_log,dt_bias,norm,w_out}`` or a gated attention's ``{wq,wk,wv,wo,q_norm,
k_norm}``) and the sizes from the configuration file.

Written from the published ``config.json``
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), transformers'
``modeling_qwen3_next.py`` as ISSUE 43 writes its equations out, and the
Gated DeltaNet paper (arXiv:2412.06464). Layer i (0-based) is FULL attention
iff (i + 1) % ``full_attention_interval`` == 0, else LINEAR. Every layer:
h += mixer(N(h)); h += moe(N(h)). N(x) = x rsqrt(mean(x^2) + eps) (1 + w),
also the last norm and the q / k norms; untied head, no bias anywhere.

Gated DeltaNet (Hk key heads, Hv value heads, dk = dv), x the normed input:

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    u = [q | k | v]; u'_t = silu(sum_{j=0..K-1} c_j u_{t-K+1+j}), zeros
    before the sequence's first token (causal depthwise convolution, K taps)
    q, k, v = split(u'); q = l2norm(q) / sqrt(dk); k = l2norm(k)  (eps 1e-6
    inside the root); each key head serves Hv / Hk value heads
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
    S_0 = 0;  S' = exp(g_t) S_{t-1};  delta_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t (x) delta_t;  o_t = S_t^T q_t        (a head at a time)
    y = (rmsnorm(o_t) w_n silu(z_t)) W_out    (plain weight w_n, no 1 + w)

The rule is the RECURRENCE, one position after the other (``lax.scan``):
the program's chunked form is an algebraic rewriting this does not share.

Gated attention (H query heads, KVH key-value heads of D):

    [query_h | gate_h] = (x W_q)_h;  k, v = x W_k, x W_v
    query, k = N_D(.) (one weight for all heads), RoPE on the first
    ``partial_rotary_factor`` D lanes (split halves, theta ``rope_theta``)
    causal softmax at 1 / sqrt(D);  y = (attn * sigmoid(gate)) W_o

Routed block, router width X (``n_routed_experts_published``, else
``num_experts``), experts ``first`` .. ``first + num_experts - 1`` held:

    p = softmax_float32(m W_r) over all X;  S = the ``num_experts_per_tok``
    largest;  w_i = p_i / sum_{j in S} p_j  (``norm_topk_prob``: the sum is
    over all the picks, held here or not)
    R_here(m) = sum_{i in S held} w_i FFN_i(m) + sigmoid(m w_sg) FFN_sh(m)
    FFN(m) = (silu(m Wg) * (m Wu)) Wd

What the experts of other chips would have added is left out, as in the
program. Departures, same mathematics: attention runs a block of queries at
a time against every key; every held expert is computed for every token,
weighted by w where it is among the token's choices and by 0 where not; the
head runs a block of the vocabulary at a time. The checkpoint's prediction
module is not among the config's keys and is not here.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
TOKEN_BLOCK = 1024       # contexts are padded to a multiple of it
VOCAB_BLOCK = 32768
F32 = jnp.float32


def _rms_norm(x, w, eps, offset=1.0):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (offset + w.astype(F32))


def period(config):
    return int(config.get("full_attention_interval", 4))


def layer_weights(layers, i, config):
    """Layer ``i``'s tree: place ``i % period`` of period ``i // period``;
    the experts stay stacked (sliced where they are used)."""
    group = layers[f"g{i % period(config)}"]
    t = i // period(config)
    return {"norm1": group["norm1"]["scale"][t],
            "norm2": group["norm2"]["scale"][t],
            "attn": jax.tree.map(lambda w: w[t], group["attn"]),
            "mlp": group["mlp"], "at": t}


def is_full(i, config):
    return (i + 1) % period(config) == 0


# ---- Gated DeltaNet --------------------------------------------------------

def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps",
                                             "with_state"))
def gated_delta_net(mix, x, *, hk, hv, dk, dv, eps, with_state=False):
    """The mixer over normalised input x (B, T, E) from an empty state; with
    ``with_state`` also the final state (B, Hv, dk, dv) and the last K - 1
    convolution inputs (B, K - 1, channels)."""
    with jax.default_matmul_precision("highest"):
        b_, t = x.shape[:2]
        qkvz = jnp.einsum("bte,ef->btf", x, mix["w_qkvz"].astype(F32))
        ba = jnp.einsum("bte,ef->btf", x, mix["w_ba"].astype(F32))
        ch = 2 * hk * dk + hv * dv
        u, z = qkvz[..., :ch], qkvz[..., ch:].reshape(b_, t, hv, dv)
        taps = mix["conv"].astype(F32)                        # (K, ch)
        k_taps = taps.shape[0]
        padded = jnp.pad(u, ((0, 0), (k_taps - 1, 0), (0, 0)))
        conv = jax.nn.silu(sum(padded[:, j:j + t] * taps[j]
                               for j in range(k_taps)))
        q = _l2norm(conv[..., :hk * dk].reshape(b_, t, hk, dk)) / np.sqrt(dk)
        k = _l2norm(conv[..., hk * dk:2 * hk * dk].reshape(b_, t, hk, dk))
        v = conv[..., 2 * hk * dk:].reshape(b_, t, hv, dv)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(mix["A_log"].astype(F32)) * jax.nn.softplus(
            ba[..., hv:] + mix["dt_bias"].astype(F32))

        def step(s, xs):
            q_t, k_t, v_t, beta_t, g_t = xs                   # (B, Hv, ...)
            s = s * jnp.exp(g_t)[..., None, None]
            delta = beta_t[..., None] * (
                v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
            s = s + k_t[..., :, None] * delta[..., None, :]
            return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

        s, o = jax.lax.scan(
            step, jnp.zeros((b_, hv, dk, dv), F32),
            tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
        o = jnp.moveaxis(o, 0, 1)                             # (B, T, Hv, dv)
        o = _rms_norm(o, mix["norm"]["scale"], eps, offset=0.0) \
            * jax.nn.silu(z)
        y = jnp.einsum("btf,fe->bte", o.reshape(b_, t, hv * dv),
                       mix["w_out"].astype(F32))
    if with_state:
        return y, s, padded[:, t:]
    return y


# ---- gated attention -------------------------------------------------------

def rope(x, theta, rotary):
    """x: (B, T, heads, D) at positions 0..T-1; the first ``rotary`` lanes
    rotated, split halves."""
    inv_freq = theta ** (-np.arange(0, rotary, 2, dtype=np.float64) / rotary)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(inv_freq, F32)[None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def _attention(q, k, v):
    """q: (B, T, H, D) scaled; k, v: (B, T, KVH, D); T a multiple of
    ``Q_BLOCK``. Causal softmax attention, a block of queries at a time."""
    t, h = q.shape[1:3]
    k, v = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (k, v))

    def block(a):
        qb = jax.lax.dynamic_slice_in_dim(q, a, Q_BLOCK, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k)
        mask = jnp.arange(t)[None, :] <= a + jnp.arange(Q_BLOCK)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "rotary"))
def gated_attention(attn, x, *, eps, theta, rotary):
    """The gated softmax attention over normalised input x (B, T, E)."""
    with jax.default_matmul_precision("highest"):
        d = attn["wk"].shape[-1]
        qg = jnp.einsum("bte,ehd->bthd", x, attn["wq"].astype(F32))
        q, gate = qg[..., :d], qg[..., d:]
        k = jnp.einsum("bte,ehd->bthd", x, attn["wk"].astype(F32))
        v = jnp.einsum("bte,ehd->bthd", x, attn["wv"].astype(F32))
        q = rope(_rms_norm(q, attn["q_norm"]["scale"], eps), theta, rotary)
        k = rope(_rms_norm(k, attn["k_norm"]["scale"], eps), theta, rotary)
        a = _attention(q / np.sqrt(d), k, v) * jax.nn.sigmoid(gate)
        return jnp.einsum("bthd,hde->bte", a, attn["wo"].astype(F32))


# ---- the routed block ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk"))
def route(m, router, *, top_k, norm_topk=True):
    """(weights (B, T, X) float32 with w_i at the token's ``top_k`` choices
    and 0 elsewhere, the choices (B, T, top_k), the scores p)."""
    with jax.default_matmul_precision("highest"):
        logits = jnp.einsum("bte,ex->btx", m, router.astype(F32))
    p = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(p, top_k)
    weights = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=F32),
                      axis=-2) * p
    if norm_topk:
        weights = weights / jnp.sum(top, axis=-1, keepdims=True)
    return weights, chosen, p


@jax.jit
def _held_experts(m, weights, mlp, at):
    """sum over the held experts i of Wd_i(w_i silu(Wg_i m) * Wu_i m), every
    expert over every token; ``weights`` (B, T, held)."""
    with jax.default_matmul_precision("highest"):
        def add(i, out):
            wg, wu, wd = (mlp[n][at, i].astype(F32)
                          for n in ("wi_gate", "wi_up", "wo"))
            gate = jax.nn.silu(jnp.einsum("bte,ef->btf", m, wg))
            up = jnp.einsum("bte,ef->btf", m, wu)
            w = jax.lax.dynamic_index_in_dim(weights, i, 2)   # (B, T, 1)
            return out + jnp.einsum("btf,fe->bte", gate * up * w, wd)

        return jax.lax.fori_loop(0, mlp["wo"].shape[1], add,
                                 jnp.zeros_like(m))


@jax.jit
def shared_expert(m, mlp, at):
    """sigmoid(m w_sg) FFN_sh(m)."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum(
            "bte,ef->btf", m, mlp["shared_wi_gate"][at].astype(F32)))
        up = jnp.einsum("bte,ef->btf", m, mlp["shared_wi_up"][at].astype(F32))
        y = jnp.einsum("btf,fe->bte", gate * up,
                       mlp["shared_wo"][at].astype(F32))
        return jax.nn.sigmoid(jnp.einsum(
            "bte,eo->bto", m, mlp["shared_gate"][at].astype(F32))) * y


def routed_part(m, mlp, at, config, first=0, routing=None):
    """The held experts' part of R(m) in period ``at`` of the group ``mlp``
    (its expert matrices are experts ``first`` .. of the router's outputs),
    without the shared expert. ``routing``, a list, receives (choices,
    scores)."""
    weights, chosen, p = route(
        m, mlp["router"][at], top_k=int(config["num_experts_per_tok"]),
        norm_topk=bool(config.get("norm_topk_prob", True)))
    if routing is not None:
        routing.append((chosen, p))
    held = mlp["wo"].shape[1]
    return _held_experts(m, weights[..., first:first + held], mlp, at)


# ---- the stack -------------------------------------------------------------

_norm = jax.jit(_rms_norm, static_argnums=2)


def mixer(w, i, x, config):
    eps = float(config["rms_norm_eps"])
    if is_full(i, config):
        d = int(config["head_dim"])
        return gated_attention(
            w["attn"], x, eps=eps, theta=float(config["rope_theta"]),
            rotary=int(d * float(config["partial_rotary_factor"])) // 2 * 2)
    return gated_delta_net(
        w["attn"], x, hk=int(config["linear_num_key_heads"]),
        hv=int(config["linear_num_value_heads"]),
        dk=int(config["linear_key_head_dim"]),
        dv=int(config["linear_value_head_dim"]), eps=eps)


def layer(h, layers, i, config, routing=None):
    """Layer ``i`` of ``layers`` (``params["layers"]``)."""
    eps = float(config["rms_norm_eps"])
    w = layer_weights(layers, i, config)
    h = h + mixer(w, i, _norm(h, w["norm1"], eps), config)
    m = _norm(h, w["norm2"], eps)
    return h + routed_part(m, w["mlp"], w["at"], config,
                           int(config.get("experts_first", 0)), routing) \
        + shared_expert(m, w["mlp"], w["at"])


@jax.jit
def _embed(tok, ids):
    return tok[ids].astype(F32)


@jax.jit
def _head(h, lm_head):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bte,ev->btv", h, lm_head.astype(F32))


def num_layers(layers):
    return sum(jax.tree.leaves(g)[0].shape[0] for g in layers.values())


def hidden(params, ids, config, routing=None):
    """(B, T) token ids -> (B, T, E) float32, before the last norm."""
    h = _embed(params["embed"]["tok"], ids)
    layers = params["layers"]
    for i in range(num_layers(layers)):
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
    picked = _norm(h[:, np.asarray(rows)], params["final_norm"]["scale"],
                   float(config["rms_norm_eps"]))
    lm_head = params["embed"]["lm_head"]
    return np.concatenate(
        [np.asarray(_head(picked, lm_head[:, v0:v0 + VOCAB_BLOCK])[0])
         for v0 in range(0, lm_head.shape[1], VOCAB_BLOCK)], axis=-1)
