"""Plain reference of the Mistral-7B-v0.1 decoder: straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
cache, no batching tricks. Independent of the program's model code: it
takes only the program's WEIGHTS (the pytree ``models.CausalLM.init``
makes: ``embed.tok``, ``embed.lm_head``, ``layers.{attn,mlp,norm1,norm2}``
stacked over layers, ``final_norm``) and the sizes from the configuration
file.

Follows the published architecture (Jiang et al. 2023, "Mistral 7B";
``modeling_mistral.py`` of transformers): pre-norm RMSNorm, rotary
embeddings in the split-halves layout on all of head_dim, grouped-query
attention (query head h reads KV head h // (H // KVH)), causal
sliding-window mask (query i sees keys j with i - W < j <= i), SwiGLU MLP,
untied LM head. Departure: attention is computed a block of queries at a
time (same mathematics; a full S x S score matrix at S = 8192 would not
fit beside the system under test).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
ROW_BLOCK = 2048
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


def _attention(q, k, v, window):
    """q: (B, T, H, D); k, v: (B, T, KVH, D). Softmax attention under the
    causal sliding-window mask, one block of queries at a time."""
    b, t, h, d = q.shape
    groups = h // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    out = []
    for a in range(0, t, Q_BLOCK):
        z = min(a + Q_BLOCK, t)
        lo = max(0, a - window + 1) if window else 0
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, a:z], k[:, lo:z]) / np.sqrt(d)
        qi = jnp.arange(a, z)[:, None]
        kj = jnp.arange(lo, z)[None, :]
        mask = kj <= qi
        if window:
            mask &= kj > qi - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, lo:z]))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("window", "theta", "eps"))
def _layer(lp, h, *, window, theta, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(F32), lp)
        x = _rms_norm(h, lp["norm1"]["scale"], eps)
        q = jnp.einsum("bte,ehd->bthd", x, lp["attn"]["wq"])
        k = jnp.einsum("bte,ehd->bthd", x, lp["attn"]["wk"])
        v = jnp.einsum("bte,ehd->bthd", x, lp["attn"]["wv"])
        a = _attention(_rope(q, theta), _rope(k, theta), v, window)
        h = h + jnp.einsum("bthd,hde->bte", a, lp["attn"]["wo"])
        out = []
        for a in range(0, h.shape[1], ROW_BLOCK):     # bounds the MLP's memory
            x = _rms_norm(h[:, a:a + ROW_BLOCK], lp["norm2"]["scale"], eps)
            gate = jax.nn.silu(jnp.einsum("bte,ef->btf", x,
                                          lp["mlp"]["wi_gate"]))
            up = jnp.einsum("bte,ef->btf", x, lp["mlp"]["wi_up"])
            out.append(h[:, a:a + ROW_BLOCK] + jnp.einsum(
                "btf,fe->bte", gate * up, lp["mlp"]["wo"]))
        return jnp.concatenate(out, axis=1)


@jax.jit
def _embed(tok, ids):
    return tok[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(h, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bte,ev->btv", _rms_norm(h, scale, eps),
                          lm_head.astype(F32))


def hidden(params, ids, config):
    """(B, T) token ids -> (B, T, E) float32, before the last norm. Layer
    by layer, so only one layer's float32 copy exists at a time."""
    h = _embed(params["embed"]["tok"], ids)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda w, i=i: w[i], params["layers"])
        h = _layer(lp, h, window=int(config.get("sliding_window") or 0),
                   theta=float(config["rope_theta"]),
                   eps=float(config["rms_norm_eps"]))
    return h


def logits_rows(params, ids, rows, config):
    """Reference logits (float32, (len(rows), V)) of one sequence at the
    given positions only; the whole context is read."""
    ids = np.asarray(ids, np.int32)
    # causal: a zero tail changes nothing before it; few distinct shapes
    padded = np.zeros((1, -(-len(ids) // Q_BLOCK) * Q_BLOCK), np.int32)
    padded[0, :len(ids)] = ids
    h = hidden(params, jnp.asarray(padded), config)
    picked = h[:, np.asarray(rows)]
    return np.asarray(_logits(picked, params["final_norm"]["scale"],
                              params["embed"]["lm_head"],
                              eps=float(config["rms_norm_eps"]))[0])


def loss(params, batch, config, row_block=2048):
    """Mean next-token cross-entropy of a batch {input_ids, labels}, each
    (B, S); float32 throughout."""
    h = hidden(params, batch["input_ids"], config)
    total = 0.0
    s = h.shape[1]
    for a in range(0, s, row_block):
        lg = _logits(h[:, a:a + row_block], params["final_norm"]["scale"],
                     params["embed"]["lm_head"],
                     eps=float(config["rms_norm_eps"]))
        total += float(_nll_sum(lg, batch["labels"][:, a:a + row_block]))
    return total / (h.shape[0] * s)


@jax.jit
def _nll_sum(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)
