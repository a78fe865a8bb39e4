"""Plain reference of the OLMoE-1B-7B decoder: straightforward ``jax.numpy``
in float32 at ``highest`` matmul precision, no kernels, no cache, no
grouped product, no sort. Independent of the program's model code: it takes
only the program's WEIGHTS (the pytree ``models.CausalLM.init`` makes:
``embed.tok``, ``embed.lm_head``, ``layers.{attn,mlp,norm1,norm2}`` stacked
over layers, ``final_norm``; ``attn.{q_norm,k_norm}.scale`` of the whole
projection's width, ``mlp.{router,wi_gate,wi_up,wo}`` with the three expert
matrices stacked over experts) and the sizes from the configuration file.

Follows the published architecture (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models"; ``modeling_olmoe.py`` of
transformers). One layer:

    x = rms(h);  q = rms_HD(x Wq), k = rms_HD(x Wk)   (one RMSNorm over the
    whole projection, before the head split and RoPE),  v = x Wv
    h += causal_full_attention(rope(q), rope(k), v) Wo
    x = rms(h);  p = softmax_float32(x Wr) over all experts
    the ``num_experts_per_tok`` largest p and their experts; the weights are
    NOT renormalized (``norm_topk_prob`` false)
    h += sum_k p_k * Wdown_k(silu(Wgate_k x) * Wup_k x)

Rotary embeddings in the split-halves layout on all of head_dim; untied LM
head. Departures, same mathematics: attention is computed a block of
queries at a time (a full S x S score matrix would not fit beside the
system under test), and every expert is computed for every token, a block
of experts at a time (one layer's experts in float32 are 1.6 GB), its
output weighted by p where the expert is among the token's top k and by 0
where it is not.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
EXPERT_BLOCK = 8
F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (B, T, heads, D), positions 0..T-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """q: (B, T, H, D); k, v: (B, T, KVH, D). Causal softmax attention over
    the whole context, one block of queries at a time."""
    t, d = q.shape[1], q.shape[3]
    groups = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    out = []
    for a in range(0, t, Q_BLOCK):
        z = min(a + Q_BLOCK, t)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, a:z], k[:, :z]) / np.sqrt(d)
        mask = jnp.arange(z)[None, :] <= jnp.arange(a, z)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :z]))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _attention_block(attn, norm1, h, *, theta, eps):
    """h + attention(rms(h)): the first half of a layer."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), attn)
        x = _rms_norm(h, norm1["scale"], eps)
        q = jnp.einsum("bte,ehd->bthd", x, w["wq"])
        k = jnp.einsum("bte,ehd->bthd", x, w["wk"])
        v = jnp.einsum("bte,ehd->bthd", x, w["wv"])
        # one norm over the whole projection, before the head split
        q = _rms_norm(q.reshape(q.shape[:2] + (-1,)), w["q_norm"]["scale"],
                      eps).reshape(q.shape)
        k = _rms_norm(k.reshape(k.shape[:2] + (-1,)), w["k_norm"]["scale"],
                      eps).reshape(k.shape)
        a = _attention(_rope(q, theta), _rope(k, theta), v)
        return h + jnp.einsum("bthd,hde->bte", a, w["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "eps"))
def route(h, norm2, router, *, top_k, renormalize, eps):
    """The MoE layer's input and routing: (x (B, T, E), weights (B, T, X)
    float32 with p at the token's ``top_k`` experts and 0 elsewhere, the
    chosen experts (B, T, top_k), the router's logits)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm2["scale"], eps)
        logits = jnp.einsum("bte,ex->btx", x, router.astype(F32))
    p = jax.nn.softmax(logits, axis=-1)
    top_p, chosen = jax.lax.top_k(p, top_k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=F32)
                      * top_p[..., None], axis=-2)
    return x, weights, chosen, logits


@jax.jit
def _expert_block(x, weights, wi_gate, wi_up, wo):
    """sum over this block's experts of weight * Wdown(silu(Wgate x) * Wup
    x), every token through every expert of the block."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,xef->btxf", x,
                                      wi_gate.astype(F32)))
        up = jnp.einsum("bte,xef->btxf", x, wi_up.astype(F32))
        out = jnp.einsum("btxf,xfe->btxe", gate * up, wo.astype(F32))
        return jnp.einsum("btxe,btx->bte", out, weights)


def moe(h, norm2, mlp, layer, config):
    """h + routed experts(rms(h)) of one layer; ``mlp`` holds every layer's
    (a block of experts is sliced from the stack: a whole layer's would be
    0.8 GB beside the system under test). Also the chosen experts and the
    router's logits, for a test of the routing itself."""
    x, weights, chosen, logits = route(
        h, norm2, mlp["router"][layer],
        top_k=int(config["num_experts_per_tok"]),
        renormalize=bool(config["norm_topk_prob"]),
        eps=float(config["rms_norm_eps"]))
    for a in range(0, int(config["num_experts"]), EXPERT_BLOCK):
        z = a + EXPERT_BLOCK
        h = h + _expert_block(x, weights[..., a:z], mlp["wi_gate"][layer, a:z],
                              mlp["wi_up"][layer, a:z], mlp["wo"][layer, a:z])
    return h, chosen, logits


@jax.jit
def _embed(tok, ids):
    return tok[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(h, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bte,ev->btv", _rms_norm(h, scale, eps),
                          lm_head.astype(F32))


def hidden(params, ids, config, routing=None):
    """(B, T) token ids -> (B, T, E) float32, before the last norm. Layer
    by layer, a block of experts at a time, so little float32 exists at
    once. ``routing``, a list, receives each layer's (chosen experts,
    router logits)."""
    h = _embed(params["embed"]["tok"], ids)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    layers = params["layers"]
    for i in range(n_layers):
        attn, norm1, norm2 = jax.tree.map(
            lambda w, i=i: w[i],
            (layers["attn"], layers["norm1"], layers["norm2"]))
        h = _attention_block(attn, norm1, h,
                             theta=float(config["rope_theta"]),
                             eps=float(config["rms_norm_eps"]))
        h, chosen, logits = moe(h, norm2, layers["mlp"], i, config)
        if routing is not None:
            routing.append((chosen, logits))
    return h


def logits_rows(params, ids, rows, config, routing=None):
    """Reference logits (float32, (len(rows), V)) of one sequence at the
    given positions only; the whole context is read."""
    ids = np.asarray(ids, np.int32)
    # causal: a zero tail changes nothing before it; few distinct shapes
    padded = np.zeros((1, -(-len(ids) // Q_BLOCK) * Q_BLOCK), np.int32)
    padded[0, :len(ids)] = ids
    h = hidden(params, jnp.asarray(padded), config, routing)
    picked = h[:, np.asarray(rows)]
    return np.asarray(_logits(picked, params["final_norm"]["scale"],
                              params["embed"]["lm_head"],
                              eps=float(config["rms_norm_eps"]))[0])
