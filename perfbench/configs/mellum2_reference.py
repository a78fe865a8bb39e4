"""Plain reference of the Mellum2-12B-A2.5B decoder: straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
cache, no pages, no grouped product, no sort. Independent of the program's
model code: it takes only the program's WEIGHTS (the pytree
``models.CausalLM.init`` makes: ``embed.tok``, ``embed.lm_head``,
``layers.{attn,mlp,norm1,norm2}`` stacked over layers, ``final_norm``;
``mlp.{router,wi_gate,wi_up,wo}`` with the three expert matrices stacked
over experts) and the sizes and kinds from the configuration file.

Written from the published ``config.json``
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct) and, for the
scaled rotary frequencies, from transformers' ``_compute_yarn_parameters``
(``modeling_rope_utils.py``; Peng et al. 2023, "YaRN"). One layer ``l`` of
kind ``layer_types[l]``:

    x = rms(h);  q = x Wq, k = x Wk, v = x Wv   (32 / 4 / 4 heads of 128)
    RoPE, split-halves layout, theta from ``rope_parameters[kind]``:
      sliding_attention  inv_freq_j = theta^(-2j/D), cos and sin as they are
      full_attention     YaRN: low = floor(D ln(L0 / (beta_fast 2 pi)) /
                         (2 ln theta)), high = ceil(D ln(L0 / (beta_slow
                         2 pi)) / (2 ln theta)), clipped to [0, D - 1];
                         ramp_j = clip((j - low) / (high - low), 0, 1);
                         inv_freq_j = (1 - ramp_j) theta^(-2j/D)
                                      + ramp_j theta^(-2j/D) / factor;
                         cos and sin times ``attention_factor``
    scores q k^T / sqrt(D), causal; a sliding layer also masks keys with
    i - j >= sliding_window; softmax in float32;  h += (P v) Wo
    x = rms(h);  p = softmax_float32(x Wr) over all experts; the
    ``num_experts_per_tok`` largest, their weights divided by their sum
    (``norm_topk_prob``);  h += sum_k w_k Wdown_k(silu(Wgate_k x) * Wup_k x)

then a last RMSNorm and an untied head. No q/k norm and no
multi-token-prediction head (the config has a key for neither). Departures,
same mathematics: attention is computed a block of queries at a time against
one width of keys, every key or those a sliding layer's block can see (a
whole S x S score matrix of 31k tokens would not fit beside the system under
test; the mask is the same); every expert is computed for every
token, a block of experts and of tokens at a time, its gated product weighted
by w where the expert is among the token's top k and by 0 where it is not,
before the down projection sums over the block's experts.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128
EXPERT_BLOCK = 8
TOKEN_BLOCK = 1024       # contexts are padded to a multiple of it
F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope_table(rope, d):
    """(inv_freq (d/2,) as numpy float64, cos/sin factor) of one
    ``rope_parameters`` entry."""
    theta = float(rope["rope_theta"])
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope.get("rope_type", "default") == "default":
        return inv_freq, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    scaled = (1 - ramp) * inv_freq + ramp * inv_freq / factor
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return scaled, float(attention_factor)


def _rope(x, inv_freq, factor):
    """x: (B, T, heads, D), positions 0..T-1."""
    d = x.shape[-1]
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    sin = jnp.sin(angles)[None, :, None, :] * factor
    cos = jnp.cos(angles)[None, :, None, :] * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q: (B, T, H, D); k, v: (B, T, KVH, D), T a multiple of ``Q_BLOCK``.
    Causal softmax attention, a block of queries at a time against one
    width of keys (every key, or the ``window - 1 + Q_BLOCK`` a block of a
    sliding layer can see: ``window`` > 0 lets query i see keys j with
    i - j < window), so a context has one shape whatever its length."""
    b, t, heads, d = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, t, kvh, heads // kvh, d)
    width = min(t, window - 1 + Q_BLOCK) if window else t

    def block(a):
        first = jnp.clip(a - window + 1, 0, t - width) if window else 0
        qb = jax.lax.dynamic_slice_in_dim(q, a, Q_BLOCK, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, first, width, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, width, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb) / np.sqrt(d)
        i = a + jnp.arange(Q_BLOCK)[:, None]
        j = first + jnp.arange(width)[None, :]
        mask = j <= i
        if window:
            mask = mask & (i - j < window)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, vb)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads, d)


@functools.partial(jax.jit, static_argnames=("window", "factor", "eps"))
def _attention_block(attn, norm1, h, inv_freq, *, window, factor, eps):
    """h + attention(rms(h)): the first half of a layer."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), attn)
        x = _rms_norm(h, norm1["scale"], eps)
        q = jnp.einsum("bte,ehd->bthd", x, w["wq"])
        k = jnp.einsum("bte,ehd->bthd", x, w["wk"])
        v = jnp.einsum("bte,ehd->bthd", x, w["wv"])
        a = _attention(_rope(q, inv_freq, factor), _rope(k, inv_freq, factor),
                       v, window)
        return h + jnp.einsum("bthd,hde->bte", a, w["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "eps"))
def route(h, norm2, router, *, top_k, renormalize, eps):
    """The MoE layer's input and routing: (x (B, T, E), weights (B, T, X)
    float32 with w at the token's ``top_k`` experts and 0 elsewhere, the
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
    """sum over this block's experts of Wdown(w * silu(Wgate x) * Wup x),
    every token of the block through every expert of the block."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("bte,xef->btxf", x,
                                      wi_gate.astype(F32)))
        up = jnp.einsum("bte,xef->btxf", x, wi_up.astype(F32))
        return jnp.einsum("btxf,xfe->bte", gate * up * weights[..., None],
                          wo.astype(F32))


def moe(h, norm2, mlp, layer, config):
    """h + routed experts(rms(h)) of one layer; ``mlp`` holds every layer's
    (a block of experts is sliced from the stack). Also the chosen experts
    and the router's logits, for a test of the routing itself."""
    x, weights, chosen, logits = route(
        h, norm2, mlp["router"][layer],
        top_k=int(config["num_experts_per_tok"]),
        renormalize=bool(config["norm_topk_prob"]),
        eps=float(config["rms_norm_eps"]))
    out = []
    for t0 in range(0, h.shape[1], TOKEN_BLOCK):
        t1 = t0 + TOKEN_BLOCK
        y = h[:, t0:t1]
        for a in range(0, int(config["num_experts"]), EXPERT_BLOCK):
            z = a + EXPERT_BLOCK
            y = y + _expert_block(
                x[:, t0:t1], weights[:, t0:t1, a:z], mlp["wi_gate"][layer, a:z],
                mlp["wi_up"][layer, a:z], mlp["wo"][layer, a:z])
        out.append(y)
    return jnp.concatenate(out, axis=1), chosen, logits


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
    by layer, so little float32 exists at once. ``routing``, a list,
    receives each layer's (chosen experts, router logits)."""
    h = _embed(params["embed"]["tok"], ids)
    layers = params["layers"]
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    # the published list, whole; a cut in depth runs its first layers
    kinds = list(config["layer_types"])[:n_layers]
    assert len(kinds) == n_layers, (len(kinds), n_layers)
    d = layers["attn"]["wq"].shape[-1]
    for i in range(n_layers):
        attn, norm1, norm2 = jax.tree.map(
            lambda w, i=i: w[i],
            (layers["attn"], layers["norm1"], layers["norm2"]))
        inv_freq, factor = rope_table(config["rope_parameters"][kinds[i]], d)
        h = _attention_block(
            attn, norm1, h, jnp.asarray(inv_freq, F32),
            window=int(config["sliding_window"]) if kinds[i] == SLIDING
            else 0, factor=factor, eps=float(config["rms_norm_eps"]))
        h, chosen, logits = moe(h, norm2, layers["mlp"], i, config)
        if routing is not None:
            routing.append((chosen, logits))
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
