"""Transformer layer primitives: pure functions over explicit param pytrees.

Every parameter leaf has a parallel *logical-axes* annotation (see
``parallel/sharding.py``) so ZeRO/TP/EP sharding is declarative. Initializers
follow the conventions the reference's target models use (normal(0.02) for
embeddings, scaled-variance for projections).
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.attention import multihead_attention, decode_attention
from .config import TransformerConfig

# ---- init helpers -------------------------------------------------------

def _normal(rng, shape, dtype, stddev):
    return (jax.random.normal(rng, shape, jnp.float32) * stddev).astype(dtype)


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def _ones(shape, dtype):
    return jnp.ones(shape, dtype)


def bcast(w, ndim: int):
    """Left-pad ``w`` with size-1 axes to rank ``ndim`` — the explicit form
    of trailing-dim weight broadcasting ((B, S, E) op (E,) etc.), so the
    serving forward stays legal under ``jax_numpy_rank_promotion="raise"``
    (the GRAFT_SANITIZE suite mode and graft-lint's dtype/rank hygiene)."""
    return w.reshape((1,) * (ndim - w.ndim) + w.shape)


def dq(w, dt):
    """Dequantize-or-cast a weight leaf to compute dtype ``dt``.

    Serving-side weight quantization (``inference/v2/model_implementations/
    quantize.py``) replaces a matmul weight leaf with ``{"q": int8,
    "s": f32 keepdims-scale}``; everything else stays a plain array. The
    structure check is a static (trace-time) decision, so unquantized
    models trace the exact pre-quantization program, and the dequantized
    product broadcasts the per-output-channel scale back over the reduced
    axes (keepdims size-1 dims)."""
    if isinstance(w, dict) and "q" in w:
        return w["q"].astype(dt) * w["s"].astype(dt)
    return w.astype(dt)


# ---- norms --------------------------------------------------------------

def _norm_scale(cfg: TransformerConfig, shape, rng=None):
    """A norm's stored weight: ones, or under ``norm_unit_offset`` (the
    norm multiplies by 1 + w) zeros, drawn around 0 where a key is given
    so that seeded weights exercise the offset."""
    if not cfg.norm_unit_offset:
        return _ones(shape, cfg.p_dtype)
    if rng is None:
        return _zeros(shape, cfg.p_dtype)
    return _normal(rng, shape, cfg.p_dtype, 0.02)


def norm_scale(params, cfg: TransformerConfig):
    """The float32 factor a norm multiplies by: its weight, or 1 + it."""
    w = params["scale"].astype(jnp.float32)
    return 1.0 + w if cfg.norm_unit_offset else w


def init_norm(cfg: TransformerConfig, rng=None):
    params = {"scale": _norm_scale(cfg, (cfg.hidden_size,), rng)}
    axes = {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        params["bias"] = _zeros((cfg.hidden_size,), cfg.p_dtype)
        axes["bias"] = ("embed",)
    return params, axes


def apply_norm(params, x, cfg: TransformerConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
        return (y * bcast(norm_scale(params, cfg), y.ndim)).astype(x.dtype)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    y = (y * bcast(params["scale"].astype(jnp.float32), y.ndim)
         + bcast(params["bias"].astype(jnp.float32), y.ndim))
    return y.astype(x.dtype)


# ---- rotary embeddings --------------------------------------------------

def _rotary_dims(cfg: TransformerConfig) -> int:
    if cfg.kv_lora_rank:        # latent attention rotates its RoPE part whole
        return cfg.qk_rope_head_dim
    d = int(cfg.dims_per_head * cfg.rotary_pct)  # partial rotary (GPT-NeoX)
    return d - d % 2


def rope_frequencies(cfg: TransformerConfig):
    d = _rotary_dims(cfg)
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return inv_freq  # (d/2,)


def yarn_frequencies(cfg: TransformerConfig):
    """``cfg.rope_yarn``'s scaled frequencies (d/2,) and the factor its cos
    and sin are multiplied by, after transformers'
    ``_compute_yarn_parameters``: a band that turns more than ``beta_fast``
    times over the original context keeps its frequency, one that turns less
    than ``beta_slow`` times is divided by ``factor``, a linear ramp
    between."""
    factor, original, beta_fast, beta_slow, attention_factor = cfg.rope_yarn
    d = _rotary_dims(cfg)

    def correction_dim(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = rope_frequencies(cfg)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return (inv_freq * (1.0 - ramp) + inv_freq / factor * ramp,
            float(attention_factor))


def rope_by_layer(cfg: TransformerConfig):
    """A model whose RoPE differs from the plain one (``rope_yarn``): every
    layer's (L, d/2) frequencies and (L,) cos/sin factor, which a layer
    reads by its index: YaRN's on the layers of full attention, the plain
    ones on the windowed layers of a mixed stack. None for plain RoPE on
    every layer."""
    if cfg.position != "rope" or cfg.rope_yarn is None:
        return None
    plain = rope_frequencies(cfg)
    scaled, factor = yarn_frequencies(cfg)
    windows = cfg.layer_windows() or (0,) * cfg.num_layers
    return (jnp.stack([plain if w else scaled for w in windows]),
            jnp.asarray([1.0 if w else factor for w in windows], jnp.float32))


def apply_rope(x, positions, inv_freq, *, interleaved=False, factor=None):
    """x: (B, S, H, D); positions: (B, S) int32.

    ``inv_freq`` has rd/2 entries where rd <= D is the rotary span (partial
    rotary, GPT-NeoX ``rotary_pct``); dims past rd pass through untouched.
    ``interleaved`` uses the (x0,x1),(x2,x3)... pair layout (GPT-J/NeoX
    checkpoints) instead of split halves (Llama). ``factor`` multiplies cos
    and sin (YaRN's attention factor: a score carries its square).
    """
    rd = 2 * inv_freq.shape[0]
    rot = x[..., :rd].astype(jnp.float32)
    angles = (positions[..., None].astype(jnp.float32)
              * inv_freq[None, None, :])                     # (B, S, rd/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    if factor is not None:
        sin, cos = sin * factor, cos * factor
    if interleaved:
        x1 = rot[..., 0::2]
        x2 = rot[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rd < x.shape[-1]:
        out = jnp.concatenate([out, x[..., rd:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ---- ALiBi --------------------------------------------------------------

def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Press et al.; the layout HF BLOOM uses).

    For a power-of-two head count: geometric sequence starting at
    2^(-8/n). Otherwise the closest power of two's sequence is extended
    with the odd-indexed slopes of the doubled sequence.
    """
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        s = pow2_slopes(num_heads)
    else:
        base = 2 ** math.floor(math.log2(num_heads))
        s = pow2_slopes(base)
        extra = pow2_slopes(2 * base)[0::2][: num_heads - base]
        s = s + extra
    return jnp.asarray(s, jnp.float32)


def alibi_bias(num_heads: int, q_pos, k_pos) -> jnp.ndarray:
    """Additive attention bias slope_h * (k - q): (..., H, Sq, Sk).

    q_pos: (Sq,) or (B, Sq); k_pos: (Sk,). The relative form differs from
    HF's per-key-position form by a per-row constant, which softmax
    cancels.
    """
    slopes = alibi_slopes(num_heads)                                   # (H,)
    rel = (k_pos[None, :] - q_pos[..., :, None]).astype(jnp.float32)   # (..., Sq, Sk)
    return slopes[:, None, None] * rel[..., None, :, :]


# ---- attention ----------------------------------------------------------

def init_attention(rng, cfg: TransformerConfig):
    e, h, kvh, d = cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dims_per_head
    r = jax.random.split(rng, 4)
    std = 0.02
    # ``attn_output_gate``: a head's query and, behind it, its output gate
    params = {
        "wq": _normal(r[0], (e, h, d * (2 if cfg.attn_output_gate else 1)),
                      cfg.p_dtype, std),
        "wk": _normal(r[1], (e, kvh, d), cfg.p_dtype, std),
        "wv": _normal(r[2], (e, kvh, d), cfg.p_dtype, std),
        "wo": _normal(r[3], (h, d, e), cfg.p_dtype, std / math.sqrt(2 * cfg.num_layers)),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.use_bias or cfg.qkv_bias:
        params.update(bq=_zeros((h, d), cfg.p_dtype), bk=_zeros((kvh, d), cfg.p_dtype),
                      bv=_zeros((kvh, d), cfg.p_dtype))
        axes.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                    bv=("kv_heads", "head_dim"))
    out_bias = cfg.use_bias if cfg.out_bias is None else cfg.out_bias
    if out_bias:
        params.update(bo=_zeros((e,), cfg.p_dtype))
        axes.update(bo=("embed",))
    if cfg.qk_norm:
        q_shape, k_shape = {
            "full": ((h * d,), (kvh * d,)),
            "head_dim": ((d,), (d,)),
            "per_head": ((h, d), (kvh, d)),
        }[cfg.qk_norm]
        for i, (nm, shape) in enumerate((("q_norm", q_shape),
                                         ("k_norm", k_shape))):
            grp = {"scale": _norm_scale(
                cfg, shape, jax.random.fold_in(rng, 4 + i)
                if cfg.norm_unit_offset else None)}
            grp_axes = {"scale": tuple("unmodeled" for _ in shape)}
            if cfg.norm == "layernorm" and cfg.qk_norm_bias:
                grp["bias"] = _zeros(shape, cfg.p_dtype)
                grp_axes["bias"] = grp_axes["scale"]
            params[nm] = grp
            axes[nm] = grp_axes
    return params, axes


# ---- latent attention (MLA) ----------------------------------------------

def init_mla(rng, cfg: TransformerConfig):
    """Latent attention's weights (DeepSeek-V2's names, heads kept as an
    axis): ``wq_a`` / ``q_norm`` / ``wq_b`` the low-rank query, ``wkv_a`` the
    cached row's projection (latent | RoPE key), ``kv_norm`` the latent's
    norm, ``wkv_b`` (latent, heads, nope | value) what expands a latent to a
    head's key and value, or is absorbed into the query and the output."""
    e, h = cfg.hidden_size, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = jax.random.split(rng, 5)
    std = 0.02
    params = {
        "wq_a": _normal(r[0], (e, rq), cfg.p_dtype, std),
        "q_norm": {"scale": _ones((rq,), cfg.p_dtype)},
        "wq_b": _normal(r[1], (rq, h, dn + dr), cfg.p_dtype, std),
        "wkv_a": _normal(r[2], (e, rkv + dr), cfg.p_dtype, std),
        "kv_norm": {"scale": _ones((rkv,), cfg.p_dtype)},
        "wkv_b": _normal(r[3], (rkv, h, dn + dv), cfg.p_dtype, std),
        "wo": _normal(r[4], (h, dv, e), cfg.p_dtype,
                      std / math.sqrt(2 * cfg.attn_layers)),
    }
    axes = {
        "wq_a": ("embed", "unmodeled"), "q_norm": {"scale": ("unmodeled",)},
        "wq_b": ("unmodeled", "heads", "head_dim"),
        "wkv_a": ("embed", "unmodeled"), "kv_norm": {"scale": ("unmodeled",)},
        "wkv_b": ("unmodeled", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def mla_scales(cfg: TransformerConfig):
    """(s_q, s_kv): what ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` put on
    the query and on the latent, sqrt(hidden / rank)."""
    return (math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
            if cfg.mla_scale_q_lora else 1.0,
            math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
            if cfg.mla_scale_kv_lora else 1.0)


def _mla_lanes(cfg: TransformerConfig, *parts):
    """``parts`` side by side, zeros behind them up to a cache row's lanes."""
    pad = cfg.latent_lanes - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    zeros = [jnp.zeros(parts[0].shape[:-1] + (pad,), cfg.act_dtype)] \
        if pad else []
    return jnp.concatenate(list(parts) + zeros, axis=-1)


@jax.named_scope("mla_kv")
def mla_row(params, a, positions, cfg: TransformerConfig, inv_freq):
    """The cached row alone (``mla_query_and_row``'s second value): what a
    layer's cache needs of a position whose output nobody asks for."""
    dt = cfg.act_dtype
    s_kv, rkv = mla_scales(cfg)[1], cfg.kv_lora_rank
    kv = jnp.einsum("bse,er->bsr", a, dq(params["wkv_a"], dt))
    c = apply_norm(params["kv_norm"], kv[..., :rkv], cfg)
    if s_kv != 1.0:
        c = c * jnp.asarray(s_kv, dt)
    k_rope = apply_rope(kv[..., None, rkv:], positions, inv_freq,
                        interleaved=cfg.rope_interleaved)
    return _mla_lanes(cfg, c[:, :, None], k_rope)


def mla_query_and_row(params, a, positions, cfg: TransformerConfig, inv_freq):
    """The ABSORBED query and the cached row of normalised input ``a``
    (B, S, E) at ``positions`` (B, S), both ``cfg.latent_lanes`` wide:
    q (B, S, H, lanes) = [q_nope W_UK^T | RoPE(q_rope) | 0], row (B, S, 1,
    lanes) = [s_kv RMSNorm(u) | RoPE(r) | 0], so that q . row is the
    expanded form's q_nope . k_nope + q_rope . k_rope, and the row's first
    ``kv_lora_rank`` lanes are the value before W_UV."""
    dt = cfg.act_dtype
    s_q, dn = mla_scales(cfg)[0], cfg.qk_nope_head_dim
    with jax.named_scope("mla_q"):
        cq = apply_norm(params["q_norm"], jnp.einsum(
            "bse,er->bsr", a, dq(params["wq_a"], dt)), cfg)
        q = jnp.einsum("bsr,rhd->bshd", cq, dq(params["wq_b"], dt))
        if s_q != 1.0:
            q = q * jnp.asarray(s_q, dt)
        q_rope = apply_rope(q[..., dn:], positions, inv_freq,
                            interleaved=cfg.rope_interleaved)
    row = mla_row(params, a, positions, cfg, inv_freq)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn],
                           dq(params["wkv_b"], dt)[..., :dn])
    return _mla_lanes(cfg, q_lat, q_rope), row


def mla_output(params, o_lat, cfg: TransformerConfig):
    """(B, S, H, >= kv_lora_rank) attention output over latents -> (B, S,
    E): W_UV a head, then the output projection."""
    dt = cfg.act_dtype
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bshr,rhv->bshv", o_lat[..., :cfg.kv_lora_rank],
                       dq(params["wkv_b"], dt)[..., cfg.qk_nope_head_dim:])
    return jnp.einsum("bshv,hve->bse", o, dq(params["wo"], dt))


# ---- Gated DeltaNet (linear attention; arXiv:2412.06464) ------------------

#: the delta rule's products are float32 to the bit the chip can give: its
#: default rounds a float32 product's operands to bfloat16
_RULE_PRECISION = jax.lax.Precision.HIGHEST
#: positions the chunked rule solves at once (the WY representation's block)
GDN_CHUNK = 64


def init_gdn(rng, cfg: TransformerConfig):
    """A Gated DeltaNet mixer's weights (Qwen3-Next's names): ``w_qkvz`` the
    input projection, its outputs q | k | v | z side by side (the
    checkpoint groups them by key head: with seeded weights a permutation),
    ``w_ba`` the per-head write strength b and decay input a, ``conv`` the
    taps of the causal depthwise convolution over q | k | v (tap j meets the
    input 3 - j positions back), ``A_log`` / ``dt_bias`` the decay's
    parameters a value head (the DeltaNet paper's draw: A ~ U(0, 16),
    softplus(dt_bias) log-uniform in [0.001, 0.1]), ``norm`` the gated
    RMSNorm's plain weight, ``w_out`` the output projection."""
    e, hv = cfg.hidden_size, cfg.linear_num_value_heads
    dv, ch = cfg.linear_value_head_dim, cfg.linear_channels
    r = jax.random.split(rng, 6)
    std = 0.02
    a = jax.random.uniform(r[3], (hv,), jnp.float32, 1e-3, 16.0)
    dt = jnp.exp(jax.random.uniform(r[4], (hv,), jnp.float32,
                                    math.log(1e-3), math.log(0.1)))
    params = {
        "w_qkvz": _normal(r[0], (e, ch + hv * dv), cfg.p_dtype, std),
        "w_ba": _normal(r[1], (e, 2 * hv), cfg.p_dtype, std),
        "conv": _normal(r[2], (cfg.linear_conv_kernel, ch), cfg.p_dtype,
                        cfg.linear_conv_kernel ** -0.5),
        "A_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus's inverse
        "norm": {"scale": _ones((dv,), cfg.p_dtype)},
        "w_out": _normal(r[5], (hv * dv, e), cfg.p_dtype,
                         std / math.sqrt(2 * cfg.num_layers)),
    }
    axes = {"w_qkvz": ("embed", "unmodeled"), "w_ba": ("embed", "unmodeled"),
            "conv": ("unmodeled", "unmodeled"), "A_log": ("unmodeled",),
            "dt_bias": ("unmodeled",), "norm": {"scale": ("unmodeled",)},
            "w_out": ("unmodeled", "embed")}
    return params, axes


@jax.named_scope("gdn_proj")
def gdn_project(params, x, cfg: TransformerConfig):
    """Normalised input (B, S, E) -> the convolution's input u (B, S,
    channels) = q | k | v, the output gate z (B, S, Hv, dv), and b, a
    (B, S, Hv) in float32. Treats every position alike."""
    dt = cfg.act_dtype
    hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    qkvz = jnp.einsum("bse,ef->bsf", x, dq(params["w_qkvz"], dt))
    ba = jnp.einsum("bse,ef->bsf", x, dq(params["w_ba"], dt)).astype(
        jnp.float32)
    ch = cfg.linear_channels
    return (qkvz[..., :ch], qkvz[..., ch:].reshape(x.shape[:2] + (hv, dv)),
            ba[..., :hv], ba[..., hv:])


def causal_conv(taps, u, tail, n):
    """The causal depthwise convolution of ``taps`` (K, channels) over a
    chunk ``u`` (B, C, channels) whose row b holds ``n[b]`` live positions,
    behind the ``tail`` (B, K - 1, channels) of inputs the sequence had
    before the chunk (zeros before its first token). Returns the chunk's
    outputs in float32 (a dead position's are garbage) and the new tail:
    the last K - 1 inputs of the LIVE positions, so a row with n = 0 keeps
    its tail to the bit. What a Gated DeltaNet layer (``gdn_conv``) and a
    gated short convolution (``conv_mix``) share."""
    k = taps.shape[0]
    c = u.shape[1]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)   # (B, K-1+C, ch)
    taps = taps.astype(jnp.float32)
    y = sum(ext[:, j:j + c].astype(jnp.float32) * taps[j][None, None]
            for j in range(k))
    # input t sits at ext[t + K - 1]: the live ones' last K - 1 start at n.
    # They are read where they lie, the chunk's as rows of (B C, channels)
    # and the old tail's, not out of ``ext``: a gather along the positions
    # of a (B, K - 1 + C, channels) operand made the chip's compiler lay the
    # whole chunk out positions-first for it (PERF.md, PR 49)
    idx = n[:, None] + jnp.arange(k - 1)[None, :]              # (B, K-1)
    rows = jnp.arange(u.shape[0])[:, None] * c + jnp.clip(idx - (k - 1), 0,
                                                         c - 1)
    new_tail = jnp.where(
        (idx >= k - 1)[:, :, None],
        u.reshape(-1, u.shape[2])[rows],
        jnp.take_along_axis(tail.astype(u.dtype),
                            jnp.minimum(idx, k - 2)[:, :, None], axis=1))
    return y, new_tail.astype(tail.dtype)


@jax.named_scope("gdn_conv")
def gdn_conv(params, u, tail, n, cfg: TransformerConfig):
    """``causal_conv`` of a Gated DeltaNet layer's q | k | v channels and
    its ``silu``: the chunk's outputs in ``u``'s dtype and the new tail."""
    y, new_tail = causal_conv(params["conv"], u, tail, n)
    return jax.nn.silu(y).astype(u.dtype), new_tail


# ---- gated short convolution (LFM2) ---------------------------------------


def init_conv_mixer(rng, cfg: TransformerConfig):
    """A gated short convolution: ``w_in`` the input projection to B | C | X
    (in that order, no bias), ``conv`` the taps of the causal depthwise
    convolution over B * X (tap j meets the input K - 1 - j positions back;
    no bias, no activation; drawn like the matrices, as the family's own
    initialisation draws its Conv1d: normal, std 0.02), ``w_out`` the output
    projection of C * conv(B * X)."""
    e = cfg.hidden_size
    r = jax.random.split(rng, 3)
    std = 0.02
    params = {
        "w_in": _normal(r[0], (e, 3 * e), cfg.p_dtype, std),
        "conv": _normal(r[1], (cfg.conv_kernel, e), cfg.p_dtype, std),
        "w_out": _normal(r[2], (e, e), cfg.p_dtype,
                         std / math.sqrt(2 * cfg.num_layers)),
    }
    axes = {"w_in": ("embed", "unmodeled"),
            "conv": ("unmodeled", "unmodeled"),
            "w_out": ("unmodeled", "embed")}
    return params, axes


def conv_project(params, x, cfg: TransformerConfig):
    """Normalised input (B, S, E) -> the convolution's input g = B * X and
    the output gate C, both (B, S, E). Treats every position alike."""
    e = cfg.hidden_size
    with jax.named_scope("conv_proj"):
        bcx = jnp.einsum("bse,ef->bsf", x, dq(params["w_in"], cfg.act_dtype))
    with jax.named_scope("conv_mix"):
        return bcx[..., :e] * bcx[..., 2 * e:], bcx[..., e:2 * e]


@jax.named_scope("conv_mix")
def conv_mix(taps, g, tail, n):
    """``causal_conv`` of a gated short convolution's input ``g`` (no
    activation): the chunk's outputs in ``g``'s dtype and the new tail."""
    y, new_tail = causal_conv(taps, g, tail, n)
    return y.astype(g.dtype), new_tail


def conv_output(params, c, gate, cfg: TransformerConfig):
    """The convolution's outputs under the gate C, through ``w_out``.
    Treats every position alike."""
    with jax.named_scope("conv_mix"):
        y = gate * c
    with jax.named_scope("conv_out"):
        return jnp.einsum("bsf,fe->bse", y, dq(params["w_out"],
                                               cfg.act_dtype))


def gdn_split(u, cfg: TransformerConfig):
    """The convolved channels back into q, k (B, S, Hk, dk) and v (B, S,
    Hv, dv), float32; q and k L2-normalised over dk (eps 1e-6), q scaled by
    dk ** -0.5, each key head repeated for the value heads it serves."""
    hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    u = u.astype(jnp.float32)
    q = u[..., :hk * dk].reshape(u.shape[:2] + (hk, dk))
    k = u[..., hk * dk:2 * hk * dk].reshape(u.shape[:2] + (hk, dk))
    v = u[..., 2 * hk * dk:].reshape(u.shape[:2] + (hv, dv))

    def l2norm(x):
        return x * jax.lax.rsqrt(
            jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2norm(q) * dk ** -0.5, hv // hk, axis=2)
    return q, jnp.repeat(l2norm(k), hv // hk, axis=2), v


def gdn_gates(params, b, a, live):
    """The write strength beta = sigmoid(b) and the log decay g =
    -exp(A_log) softplus(a + dt_bias), (B, S, Hv) float32; both 0 at a dead
    position (``live`` (B, S) False), which then leaves the state as it
    was."""
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(params["A_log"].astype(jnp.float32))[None, None] \
        * jax.nn.softplus(a + params["dt_bias"].astype(jnp.float32)[None, None])
    live = live[:, :, None]
    return jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)


@jax.named_scope("gdn_scan")
def gdn_rule(q, k, v, beta, g, state, chunk=GDN_CHUNK):
    """The gated delta rule over a chunk: per head, with S (dk, dv) the
    state, for each position S' = exp(g) S; delta = beta (v - S'^T k);
    S = S' + k (x) delta; o = S^T q. q, k (B, C, H, dk), v (B, C, H, dv),
    beta, g (B, C, H), state (B, H, dk, dv), all float32. Returns o (B, C,
    H, dv) and the new state.

    C = 1 is the recurrence itself. A wider chunk runs the paper's chunked
    form, an algebraic rewriting: within a block of ``chunk`` positions the
    deltas solve a unit lower-triangular system ((I - A)^-1, A strictly
    lower and so nilpotent: the product of (I + A^(2^m)), six products for
    64), the blocks follow each other through the state. A position with
    beta = g = 0 changes nothing, so a row's dead positions, behind its live
    ones, leave the state the live ones gave."""
    p = _RULE_PRECISION
    b, c, h, dk = q.shape
    if c == 1:
        s = state * jnp.exp(g[:, 0])[:, :, None, None]
        sk = jnp.einsum("bhkv,bhk->bhv", s, k[:, 0], precision=p)
        delta = beta[:, 0, :, None] * (v[:, 0] - sk)
        s = s + k[:, 0, :, :, None] * delta[:, :, None, :]
        return jnp.einsum("bhkv,bhk->bhv", s, q[:, 0], precision=p)[:, None], s
    cs = min(chunk, c)
    pad = -c % cs
    if pad:   # dead positions behind the chunk
        q, k, v, beta, g = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, beta, g))
    n = (c + pad) // cs

    def blocks(x):      # (B, C, H, ...) -> (B, H, N, cs, ...)
        x = x.reshape((b, n, cs) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, beta, g = map(blocks, (q, k, v, beta, g))
    gc = jnp.cumsum(g, axis=-1)                                 # (B, H, N, cs)
    lower = jnp.tril(jnp.ones((cs, cs), bool))
    strict = jnp.tril(jnp.ones((cs, cs), bool), -1)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(strict, -jnp.einsum("bhnik,bhnjk->bhnij", kb, k,
                                      precision=p) * decay, 0.0)
    eye = jnp.eye(cs, dtype=jnp.float32)
    t, power = eye + a, a
    for _ in range(max(0, (cs - 1).bit_length() - 1)):
        power = jnp.einsum("bhnij,bhnjk->bhnik", power, power, precision=p)
        t = t + jnp.einsum("bhnij,bhnjk->bhnik", t, power, precision=p)
    u = jnp.einsum("bhnij,bhnjv->bhniv", t, v * beta[..., None], precision=p)
    w = jnp.einsum("bhnij,bhnjk->bhnik", t, kb * jnp.exp(gc)[..., None],
                   precision=p)
    qk = jnp.where(lower, jnp.einsum("bhnik,bhnjk->bhnij", q, k,
                                     precision=p) * decay, 0.0)
    outs = []
    for i in range(n):
        v_new = u[:, :, i] - jnp.einsum("bhck,bhkv->bhcv", w[:, :, i], state,
                                        precision=p)
        outs.append(
            jnp.einsum("bhck,bhkv->bhcv",
                       q[:, :, i] * jnp.exp(gc[:, :, i])[..., None], state,
                       precision=p)
            + jnp.einsum("bhij,bhjv->bhiv", qk[:, :, i], v_new, precision=p))
        last = gc[:, :, i, -1]
        state = state * jnp.exp(last)[:, :, None, None] + jnp.einsum(
            "bhck,bhcv->bhkv",
            k[:, :, i] * jnp.exp(last[..., None] - gc[:, :, i])[..., None],
            v_new, precision=p)
    o = jnp.stack(outs, axis=2)                                 # (B, H, N, cs, dv)
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * cs, h, -1)
    return o[:, :c], state


def gdn_output(params, o, z, cfg: TransformerConfig):
    """The rule's output (B, S, Hv, dv) float32 under its gated norm, a head
    at a time: rmsnorm(o) w_n silu(z); then the output projection. Treats
    every position alike."""
    dt = cfg.act_dtype
    with jax.named_scope("gdn_norm_gate"):
        o = o.astype(jnp.float32)
        y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg.norm_eps)
        y = y * bcast(params["norm"]["scale"].astype(jnp.float32), y.ndim)
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
    with jax.named_scope("gdn_out"):
        return jnp.einsum("bsf,fe->bse", y.reshape(y.shape[:2] + (-1,)),
                          dq(params["w_out"], dt))


def apply_qk_norm(norm_params, x, cfg: TransformerConfig):
    """Normalize q or k heads: x (B, S, H, D).

    "full" normalizes the flattened per-token (H*D) vector (MPT qk_ln:
    LayerNorm(d_model) before the head split); "head_dim"/"per_head"
    normalize each head's D dims (Phi shares one (D,) weight, StableLM
    stacks (H, D)) — the stats are per-head either way, only the weight
    sharing differs, and both weight shapes broadcast over (B, S, H, D).
    """
    b, s, h, d = x.shape
    x32 = x.astype(jnp.float32)
    if cfg.qk_norm == "full":
        x32 = x32.reshape(b, s, h * d)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * bcast(norm_scale(norm_params, cfg), y.ndim)
    if "bias" in norm_params:
        y = y + bcast(norm_params["bias"].astype(jnp.float32), y.ndim)
    return y.reshape(b, s, h, d).astype(x.dtype)


def apply_attention(params, x, cfg: TransformerConfig, *, positions=None, inv_freq=None,
                    segment_ids=None, kv_cache=None, cache_len=None, attn_bias=None,
                    window=None, rope_factor=None):
    """x: (B, S, E). Returns (out, new_kv_cache).

    Training: kv_cache None. Decode: kv_cache = (k, v) with shape
    (B, S_max, KVH, D); new tokens are written at ``cache_len`` offsets.
    ``attn_bias``: precomputed additive bias (ALiBi) — layer-invariant, so
    callers scanning over layers build it ONCE and pass it down (computed
    here only as a standalone-call fallback).
    ``window``: sliding-window width for this layer (static int, or traced
    scalar under a scan over mixed local/global layers; <= 0 = global).
    ``rope_factor``: this layer's cos/sin factor beside its ``inv_freq``
    (``rope_by_layer``).
    """
    if window is None and cfg.sliding_window is not None and cfg.local_attention_every is None:
        window = cfg.sliding_window   # uniform window (Mistral)
    dt = cfg.act_dtype
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(dt))
        k = jnp.einsum("bse,ehd->bshd", x, params["wk"].astype(dt))
        v = jnp.einsum("bse,ehd->bshd", x, params["wv"].astype(dt))
        if cfg.use_bias or cfg.qkv_bias:
            q = q + bcast(params["bq"].astype(dt), q.ndim)
            k = k + bcast(params["bk"].astype(dt), k.ndim)
            v = v + bcast(params["bv"].astype(dt), v.ndim)
        if cfg.qk_norm:
            q = apply_qk_norm(params["q_norm"], q, cfg)
            k = apply_qk_norm(params["k_norm"], k, cfg)
        if cfg.position == "rope":
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            q = apply_rope(q, positions, inv_freq, interleaved=cfg.rope_interleaved,
                           factor=rope_factor)
            k = apply_rope(k, positions, inv_freq, interleaved=cfg.rope_interleaved,
                           factor=rope_factor)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        # write the S new entries at cache_len offset (decode S is typically 1)
        b, s = x.shape[:2]
        idx = cache_len[:, None] + jnp.arange(s)[None, :]  # (B, S)
        ck = _scatter_cache(ck, k, idx)
        cv = _scatter_cache(cv, v, idx)
        new_cache = (ck, cv)
        bias = attn_bias
        if cfg.position == "alibi" and bias is None:
            k_pos = jnp.arange(ck.shape[1])
            bias = alibi_bias(cfg.num_heads, idx, k_pos)   # (B, H, S, S_max)
        out = decode_attention(q, ck, cv, cache_len + s, bias=bias, window=window,
                               scale=cfg.attn_scale, softcap=cfg.attn_softcap)
    else:
        impl = None if cfg.attn_impl == "auto" else cfg.attn_impl
        slopes = None
        if cfg.position == "alibi" and attn_bias is None:
            # slopes, not a bias tensor: the flash kernel computes the
            # ALiBi term in-kernel; XLA fallbacks expand it themselves
            slopes = alibi_slopes(cfg.num_heads)
        out = multihead_attention(q, k, v, causal=cfg.causal, segment_ids=segment_ids,
                                  bias=attn_bias, alibi_slopes=slopes,
                                  window=window, impl=impl, scale=cfg.attn_scale,
                                  softcap=cfg.attn_softcap)

    with jax.named_scope("attn_out"):
        y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(dt))
        if "bo" in params:
            y = y + bcast(params["bo"].astype(dt), y.ndim)
    return y, new_cache


def _scatter_cache(cache, new, idx):
    """cache: (B, S_max, H, D); new: (B, S, H, D); idx: (B, S) positions."""
    b = cache.shape[0]
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], idx.shape)
    return cache.at[bidx, idx].set(new.astype(cache.dtype))


# ---- MLP ----------------------------------------------------------------

def init_mlp(rng, cfg: TransformerConfig):
    e, f = cfg.hidden_size, cfg.ffn_size
    r = jax.random.split(rng, 3)
    std = 0.02
    if cfg.activation in ("swiglu", "geglu"):
        params = {
            "wi_gate": _normal(r[0], (e, f), cfg.p_dtype, std),
            "wi_up": _normal(r[1], (e, f), cfg.p_dtype, std),
            "wo": _normal(r[2], (f, e), cfg.p_dtype, std / math.sqrt(2 * cfg.num_layers)),
        }
        axes = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"), "wo": ("mlp", "embed")}
    else:
        params = {
            "wi": _normal(r[0], (e, f), cfg.p_dtype, std),
            "wo": _normal(r[2], (f, e), cfg.p_dtype, std / math.sqrt(2 * cfg.num_layers)),
        }
        axes = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    mlp_bias = cfg.use_bias if cfg.mlp_bias is None else cfg.mlp_bias
    if mlp_bias:
        params.update(bi=_zeros((f,), cfg.p_dtype), bo=_zeros((e,), cfg.p_dtype))
        axes.update(bi=("mlp",), bo=("embed",))
    return params, axes


@jax.named_scope("mlp")
def apply_mlp(params, x, cfg: TransformerConfig, reduce=None):
    """``reduce`` (tensor-parallel serving): applied to the w_out product
    BEFORE the output bias — with the intermediate dim sharded, the product
    is a partial sum the caller all-reduces, and the replicated bias must
    be added exactly once (after the reduce), not once per shard."""
    dt = cfg.act_dtype
    mlp_bias = cfg.use_bias if cfg.mlp_bias is None else cfg.mlp_bias
    if cfg.activation in ("swiglu", "geglu"):
        g = jnp.einsum("bse,ef->bsf", x, dq(params["wi_gate"], dt))
        u = jnp.einsum("bse,ef->bsf", x, dq(params["wi_up"], dt))
        gate = (jax.nn.gelu(g, approximate=True) if cfg.activation == "geglu"
                else jax.nn.silu(g))
        h = gate * u
    else:
        h = jnp.einsum("bse,ef->bsf", x, dq(params["wi"], dt))
        if mlp_bias:
            h = h + bcast(params["bi"].astype(dt), h.ndim)
        if cfg.activation == "relu":
            h = jax.nn.relu(h)
        else:  # "gelu" = tanh approximation (gelu_new); "gelu_exact" = erf
            h = jax.nn.gelu(h, approximate=cfg.activation != "gelu_exact")
    y = jnp.einsum("bsf,fe->bse", h, dq(params["wo"], dt))
    if reduce is not None:
        y = reduce(y)
    if mlp_bias:
        y = y + bcast(params["bo"].astype(dt), y.ndim)
    return y


# ---- MoE MLP ------------------------------------------------------------

#: the routed experts' weights, stacked over experts: (X, E, F) or (X, F, E)
EXPERT_MATRICES = ("wi_gate", "wi_up", "wo")


def init_moe_mlp(rng, cfg: TransformerConfig):
    """Mixtral-style top-k routed experts with swiglu experts (+ optional
    always-on shared expert: Qwen2-MoE's behind its own sigmoid gate,
    DeepSeek-V3's family's without one, ``moe_shared_expert_gate``)."""
    e, f, x = cfg.hidden_size, cfg.moe_ffn_size, cfg.num_experts
    r = jax.random.split(rng, 8)
    std = 0.02
    params = {
        "router": _normal(r[0], (e, cfg.moe_router_width), cfg.p_dtype, std),
        "wi_gate": _normal(r[1], (x, e, f), cfg.p_dtype, std),
        "wi_up": _normal(r[2], (x, e, f), cfg.p_dtype, std),
        "wo": _normal(r[3], (x, f, e), cfg.p_dtype, std / math.sqrt(2 * cfg.num_layers)),
    }
    axes = {
        "router": ("embed", "unmodeled"),
        "wi_gate": ("expert", "embed", "mlp"),
        "wi_up": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }
    if cfg.moe_router_bias:
        # the score correction that chooses the experts (a trained
        # parameter): drawn small beside the top scores (~4 / width), so it
        # changes some of a token's picks and not most. Sigmoid scores of
        # unit-variance logits lie ~0.02 apart around a token's 4th pick
        params["router_bias"] = _normal(
            jax.random.fold_in(rng, 8), (cfg.moe_router_width,), jnp.float32,
            0.02 if cfg.moe_router_score == "sigmoid"
            else 1.0 / cfg.moe_router_width)
        axes["router_bias"] = ("unmodeled",)
    if cfg.moe_shared_expert_size:
        s = cfg.moe_shared_expert_size
        params.update(
            shared_wi_gate=_normal(r[4], (e, s), cfg.p_dtype, std),
            shared_wi_up=_normal(r[5], (e, s), cfg.p_dtype, std),
            shared_wo=_normal(r[6], (s, e), cfg.p_dtype,
                              std / math.sqrt(2 * cfg.num_layers)))
        axes.update(shared_wi_gate=("embed", "mlp"), shared_wi_up=("embed", "mlp"),
                    shared_wo=("mlp", "embed"))
        if cfg.moe_shared_expert_gate:
            params["shared_gate"] = _normal(r[7], (e, 1), cfg.p_dtype, std)
            axes["shared_gate"] = ("embed", "unmodeled")
    return params, axes


@jax.named_scope("moe_shared")
def _apply_shared_expert(params, x, cfg: TransformerConfig):
    """The shared expert every token passes beside its routed ones: a
    swiglu MLP, weighted by a sigmoid gate where the model has one
    (``shared_gate``: Qwen2-MoE's; DeepSeek-V3's family adds it as it is)."""
    dt = cfg.act_dtype
    g = jnp.einsum("...e,ef->...f", x, dq(params["shared_wi_gate"], dt))
    u = jnp.einsum("...e,ef->...f", x, dq(params["shared_wi_up"], dt))
    sh = jnp.einsum("...f,fe->...e", jax.nn.silu(g) * u,
                    dq(params["shared_wo"], dt))
    if "shared_gate" not in params:
        return sh
    gate = jax.nn.sigmoid(
        jnp.einsum("...e,eo->...o", x, params["shared_gate"].astype(dt)))
    return gate * sh


#: about what a trip of the bounded dispatch and combine moves, in whole row
#: tiles of 128: past it the chip's scatter-add of a block costs more a row
#: (``benchmarks/moe_bench.py --bookkeeping-sweep``: 256 rows of 6,144 take
#: 2.5 times what 128 take a row, 512 of 2,048 twice what 256 take)
MOVE_BYTES = 1 << 20


def _move_block(cfg: TransformerConfig, rows: int):
    """Rows a trip of the bounded dispatch and combine moves, from static
    shapes alone (``rows`` = T x k of the sorted buffer): the row tiles
    nearest ``MOVE_BYTES``. None where the bound could save next to nothing
    and the unbounded lines run: two blocks hold every row (every narrow
    program), or the experts are all held (not ``cfg.moe_is_share``), where
    the rows in groups are the live positions', over half of any rung
    (``pack_ladder``): there one gather each way over every row is the
    cheaper form (``_sum_by_token``)."""
    tile = 128 * cfg.hidden_size * jnp.dtype(cfg.act_dtype).itemsize
    block = 128 * max(1, round(MOVE_BYTES / tile))
    return block if cfg.moe_is_share and rows > 2 * block else None


def _move_trips(n, block: int):
    """Blocks that hold the ``n`` rows of the groups, which sort first: the
    trip count of the two loops below, taken in the graph."""
    return (n + block - 1) // block


def moe_rows_moved(cfg: TransformerConfig, group_sizes, rows: int):
    """Rows one routed block's dispatch gathered (= its combine took back) for
    ``group_sizes`` out of ``rows`` = T x k selections (serving): whole
    blocks over the groups' rows, every row where the bound cannot bind
    (``_move_block``) or the dispatch is not the dropless one."""
    block = _move_block(cfg, rows) if cfg.moe_impl == "grouped" else None
    if block is None:
        return jnp.asarray(rows, jnp.int32)
    return (_move_trips(jnp.sum(group_sizes), block)
            * block).astype(jnp.int32)


def _block_at(i, block: int, rows: int):
    """First row of block ``i`` of a buffer of ``rows``: the last block of
    a buffer that is no multiple of ``block`` ends with the buffer, over
    rows the block before it holds too."""
    return jnp.minimum(i * block, rows - block)


def _gather_held(tokens, tok_of_sorted, trips, block: int):
    """``take(tokens, tok_of_sorted)`` for the first ``trips`` blocks of
    rows, a block a trip of one loop; the rows behind them belong to no
    group and no trip writes them (``grouped_gemm.unwritten``: on the chip
    they hold anything)."""
    from ..ops.pallas.grouped_gemm import unwritten
    rows = tok_of_sorted.shape[0]

    def trip(i, buf):
        at = _block_at(i, block, rows)
        idx = jax.lax.dynamic_slice(tok_of_sorted, (at,), (block,))
        return jax.lax.dynamic_update_slice(
            buf, tokens.at[idx].get(mode="promise_in_bounds"), (at, 0))

    return jax.lax.fori_loop(0, trips, trip, unwritten(rows, tokens))


def _add_held(rows, w_sorted, tok_of_sorted, n, block: int, t: int):
    """The weighted rows of the groups (the first ``n``) added to their
    tokens' (t, E) outputs, a block a trip. A row past ``n`` is unwritten on
    the chip and may hold anything: the last block's tail is taken out by
    ``where``, never by a product, and no block behind it is read."""
    total, e = rows.shape

    def trip(i, out):
        at = _block_at(i, block, total)
        row = at + jnp.arange(block)
        mine = (row >= i * block) & (row < n)
        blk = jnp.where(
            mine[:, None],
            jax.lax.dynamic_slice(rows, (at, 0), (block, e)),
            jnp.zeros((), rows.dtype))
        w = jax.lax.dynamic_slice(w_sorted, (at,), (block,))
        tok = jax.lax.dynamic_slice(tok_of_sorted, (at,), (block,))
        return out.at[tok].add(blk * w[:, None], mode="promise_in_bounds")

    return jax.lax.fori_loop(0, _move_trips(n, block), trip,
                             jnp.zeros((t, e), rows.dtype))


def _sum_by_token(rows, order, w, keep):
    """The (T, E) outputs of ``rows`` (T x k, E), the experts' results in
    sorted order, by a gather: selection ``j`` of token ``i`` sits at the
    sorted row ``r`` with ``order[r] == i * k + j``, so the rows are taken
    back j-major, as k slabs of (T, E) (the inverse permutation by a second
    sort, its keys the selections' j-major places), and summed over j under
    the tokens' weights ``w`` (T, k), in float32, rounded once. No
    scatter-add, no (T, E) of zeros; the derivative for ``rows`` is a scatter
    over rows that are all different. ``keep`` (T, k) bool or None: the
    selections whose row is in a group; another's is unwritten on the chip
    and may hold anything, so it is taken out by ``where``, never by a
    product. On the chip (``benchmarks/moe_bench.py --bookkeeping-sweep``,
    PERF.md Findings PR 52) a sort reads cheaper for the inverse than a
    scatter of T x k scalars, and slabs over j cheaper to sum than a
    token's k rows side by side in a tile."""
    t, k = w.shape
    at = jnp.argsort(order % k * t + order // k)
    picked = rows.at[at].get(mode="promise_in_bounds",
                             unique_indices=True).reshape(k, t, -1)
    if keep is not None:
        picked = jnp.where(keep.T[..., None], picked,
                           jnp.zeros((), rows.dtype))
    return jnp.sum(picked.astype(jnp.float32) * w.T[..., None],
                   axis=0).astype(rows.dtype)


def apply_moe_grouped(params, x, cfg: TransformerConfig, live=None,
                      layer=None):
    """Dropless grouped-GEMM MoE (megablox pattern; reference analog:
    ``inference/v2/kernels/cutlass_ops/moe_gemm``): tokens are sorted by
    assigned expert and each expert's contiguous row group is multiplied by
    that expert's matrices in ``ops/pallas/grouped_gemm.py`` (on the chip
    its own kernel, which reads an expert once a product; ``ragged_dot``
    elsewhere) — no capacity buffers, no dense (T, X, C) dispatch
    einsums, no token dropping. Selected by ``moe_impl: "grouped"``;
    requires an unsharded expert axis (EP uses the einsum/all-to-all path).

    ``live`` (B, S) bool, serving: the positions that hold a token. A dead
    position (an idle slot, a packed buffer's padding) reaches no expert:
    its rows sort behind the last expert and belong to no group, so the
    product skips them, they read no expert's weights and a live
    token's output does not depend on them. Rows past the groups are not
    written (zero on the CPU, whatever the buffer held on the chip), so the
    combine takes them out by ``where``. With ``live`` the group sizes come
    back as a third value (the rows each expert computed).
    The rows move by one of two forms, chosen from static shapes and
    ``cfg.moe_is_share`` alone (``_move_block``). Everywhere but a share's
    wide rungs every selection row moves once each way, by a gather each
    way: token rows into sorted order, then a token's k result rows back
    over the inverse permutation and summed under its weights
    (``_sum_by_token``: no scatter-add, no (T, E) of zeros). Where the chip
    holds a share of the router's experts (``cfg.moe_is_share``) the rows of
    the groups, which sort first, are few of the T x k: the gather of token
    rows and a weighted scatter-add then run over the blocks that hold them
    alone (``_gather_held``, ``_add_held``: a trip count taken in the graph
    from ``group_sizes``; ``moe_rows_moved`` counts them).
    ``layer``: the three expert matrices in ``params`` are stacked over
    layers and this is the one to use (``grouped_gemm`` says why).
    """
    from ..moe.sharded_moe import topk_gating_grouped
    from ..ops.pallas.grouped_gemm import moe_expert_ffn
    dt = cfg.act_dtype
    b, s, e = x.shape
    k = cfg.num_experts_per_tok
    n_exp = cfg.num_experts
    tokens = x.reshape(b * s, e)
    t = tokens.shape[0]

    with jax.named_scope("moe_route"):
        logits = jnp.einsum("te,ex->tx", tokens.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        topk_idx, w, aux_loss = topk_gating_grouped(
            logits, k=k, normalize=cfg.moe_norm_topk,
            bias=params.get("router_bias"), scale=cfg.moe_routed_scale,
            score=cfg.moe_router_score, eps=cfg.moe_norm_eps)

    share = cfg.moe_is_share
    with jax.named_scope("moe_dispatch"):
        expert_of_row = topk_idx.reshape(-1)                  # (T*k,)
        if share:
            # held: [first, first + n_exp) of the router's outputs; the
            # zero experts are its last
            local = topk_idx - cfg.moe_expert_first
            held = (local >= 0) & (local < n_exp)
            is_zero = topk_idx >= cfg.moe_router_width - cfg.moe_zero_experts
            expert_of_row = jnp.where(held, local, n_exp).reshape(-1)
        if live is not None:
            expert_of_row = jnp.where(jnp.repeat(live.reshape(-1), k),
                                      expert_of_row, n_exp)
        order = jnp.argsort(expert_of_row, stable=True)
        tok_of_sorted = order // k                            # token each row copies
        block = _move_block(cfg, t * k)
        if block is None:   # (before the group sizes: the trace it was)
            sorted_tokens = jnp.take(tokens, tok_of_sorted, axis=0)  # (T*k, E)
        # bincount drops what lies past ``length``: the dead rows
        group_sizes = jnp.bincount(expert_of_row,
                                   length=n_exp).astype(jnp.int32)
        if block is not None:
            in_groups = jnp.sum(group_sizes)
            sorted_tokens = _gather_held(tokens.astype(dt), tok_of_sorted,
                                         _move_trips(in_groups, block), block)

    with jax.named_scope("moe_experts"):
        rows = moe_expert_ffn(sorted_tokens.astype(dt), params["wi_gate"],
                              params["wi_up"], params["wo"], group_sizes,
                              layer)
    with jax.named_scope("moe_combine"):
        if block is None:
            # without ``live``, every expert held: every row is in a group
            keep = ((expert_of_row < n_exp).reshape(t, k)
                    if live is not None or share else None)
            out = _sum_by_token(rows, order, w, keep)
        else:
            out = _add_held(
                rows, jnp.take(w.reshape(-1), order, axis=0).astype(dt),
                tok_of_sorted, in_groups, block, t)
        if cfg.moe_zero_experts:
            out = out + tokens.astype(dt) * jnp.sum(
                jnp.where(is_zero, w, 0.0), axis=-1, keepdims=True).astype(dt)
    if cfg.moe_shared_expert_size:
        out = out + _apply_shared_expert(params, tokens.astype(dt), cfg)
    out = out.reshape(b, s, e)
    if live is None:
        return out, aux_loss
    if not share:
        return out, aux_loss, group_sizes
    picked = live.reshape(-1, 1) & jnp.ones_like(topk_idx, bool)
    return out, aux_loss, group_sizes, jnp.stack(
        [jnp.sum(picked), jnp.sum(picked & is_zero),
         jnp.sum(picked & ~held & ~is_zero)]).astype(jnp.int32)


def apply_moe_grouped_ep(params, x, cfg: TransformerConfig, mesh):
    """Dropless grouped MoE under a SHARDED expert axis (megablox-under-EP;
    reference analog: ``inference/v2/kernels/cutlass_ops/moe_gemm`` +
    ``deepspeed/moe/sharded_moe.py:533 _AllToAll``).

    A shard_map manual over the token-carrying axes + ``expert``:
    each device routes its local tokens, lays rows destined to expert-shard
    ``s`` into slot block ``s`` of a static (ep, R, E) buffer, all-to-all
    over the expert axis, runs ONE local grouped product
    (``ops/pallas/grouped_gemm.py``) over the received rows sorted by local
    expert (the empty tail belongs to no group: it is skipped and, on the
    chip, left unwritten; nothing gathers it, so compute scales with the
    rows actually routed here), and all-to-alls results back for the
    weighted combine. R = T_local * k — the
    worst case, so NO token is ever dropped regardless of routing imbalance
    (the capacity-einsum path drops at C); memory is over-provisioned
    instead, the standard static-shape tradeoff on XLA.
    """
    from jax.sharding import PartitionSpec as P
    from ..moe.sharded_moe import topk_gating_grouped
    from ..ops.pallas.grouped_gemm import moe_expert_ffn

    from ..utils import groups as _groups

    dt = cfg.act_dtype
    k = cfg.num_experts_per_tok
    n_exp = cfg.num_experts
    ep = mesh.shape["expert"]
    n_local = n_exp // ep
    # tokens' batch dim is sharded over ALL data-like axes (expert included:
    # EP groups split the batch, reference groups.py expert_parallel groups)
    batch_axes = tuple(a for a in _groups.BATCH_AXES
                       if mesh.shape.get(a, 1) > 1)
    seq_axis = "seq" if mesh.shape.get("seq", 1) > 1 else None
    token_axes = (set(batch_axes) | {"expert"}
                  | ({seq_axis} if seq_axis else set()))
    # Mosaic lowers a call (the grouped product's kernel on the chip) only
    # where EVERY mesh axis is manual: name the rest too where they hold one
    # device each, which changes nothing else
    manual = (set(mesh.shape) if all(
        mesh.shape[a] == 1 for a in mesh.shape if a not in token_axes)
        else token_axes)

    def body(router, wi_gate, wi_up, wo, x):
        b, s, e = x.shape
        tokens = x.reshape(b * s, e)
        t_loc = tokens.shape[0]
        r_buf = t_loc * k

        logits = jnp.einsum("te,ex->tx", tokens.astype(jnp.float32),
                            router.astype(jnp.float32))
        topk_idx, w, _ = topk_gating_grouped(logits, k=k,
                                             normalize=cfg.moe_norm_topk)
        # GShard aux over the GLOBAL token set, from psum'd sufficient
        # statistics (per-shard means of products != products of global
        # means; the einsum path aggregates globally, so must this one)
        gates = jax.nn.softmax(logits, axis=-1)
        mask_tx = jnp.sum(jax.nn.one_hot(topk_idx, n_exp, dtype=jnp.float32),
                          axis=1)
        stats = jax.lax.pmean(
            jnp.stack([jnp.mean(gates, axis=0), jnp.mean(mask_tx, axis=0)]),
            tuple(sorted(token_axes)))
        aux = n_exp * jnp.sum(stats[0] * stats[1])
        er = topk_idx.reshape(-1)                       # (T*k,) global expert
        ts = er // n_local                              # target expert shard
        le = er % n_local                               # local id on target

        order = jnp.argsort(ts, stable=True)
        ts_s = jnp.take(ts, order)
        counts = jnp.bincount(ts_s, length=ep)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(r_buf) - jnp.take(starts, ts_s)   # rank within shard
        slot = ts_s * r_buf + pos                          # (T*k,) send slot
        tok_of_sorted = order // k
        send = jnp.zeros((ep * r_buf, e), dt).at[slot].set(
            jnp.take(tokens, tok_of_sorted, axis=0).astype(dt))
        send_le = jnp.full((ep * r_buf,), n_local, jnp.int32).at[slot].set(
            jnp.take(le, order))

        recv = jax.lax.all_to_all(send.reshape(ep, r_buf, e), "expert", 0, 0,
                                  tiled=False).reshape(ep * r_buf, e)
        recv_le = jax.lax.all_to_all(send_le.reshape(ep, r_buf), "expert",
                                     0, 0, tiled=False).reshape(ep * r_buf)

        order2 = jnp.argsort(recv_le, stable=True)
        rows = jnp.take(recv, order2, axis=0)
        group_sizes = jnp.bincount(recv_le, length=n_local).astype(jnp.int32)
        ffn = moe_expert_ffn(rows, wi_gate.astype(dt), wi_up.astype(dt),
                             wo.astype(dt), group_sizes)
        back = jnp.zeros_like(ffn).at[order2].set(ffn)
        back = jax.lax.all_to_all(back.reshape(ep, r_buf, e), "expert", 0, 0,
                                  tiled=False).reshape(ep * r_buf, e)

        row_out = jnp.take(back, slot, axis=0)          # sorted-row results
        w_sorted = jnp.take(w.reshape(-1), order).astype(dt)
        out = jnp.zeros((t_loc, e), dt).at[tok_of_sorted].add(
            row_out * w_sorted[:, None])
        return out.reshape(b, s, e), aux

    tok_spec = P(batch_axes or None, seq_axis, None)
    specs = dict(mesh=mesh,
                 in_specs=(P(), P("expert"), P("expert"), P("expert"),
                           tok_spec),
                 out_specs=(tok_spec, P()))
    fn = jax.shard_map(body, axis_names=manual, **specs)
    out, aux = fn(params["router"], params["wi_gate"], params["wi_up"],
                  params["wo"], x)
    if cfg.moe_shared_expert_size:
        out = out + _apply_shared_expert(params, x.astype(dt), cfg)
    return out, aux


@jax.named_scope("moe_mlp")
def apply_moe_mlp(params, x, cfg: TransformerConfig, live=None, layer=None):
    """Dispatch/combine via one-hot einsum (GShard-style, reference
    ``deepspeed/moe/sharded_moe.py:96 MOELayer``). Capacity-bounded, dropless
    within capacity; aux load-balancing loss returned alongside.

    ``moe_impl: "grouped"`` routes to ``apply_moe_grouped`` (sort-by-expert
    + ragged_dot) when the expert mesh axis is unsharded.

    ``live`` (B, S) bool is the serving runner's mask of positions that
    hold a token; with it a third value comes back, the rows each expert
    computed (X,). Only the dropless path keeps dead positions from the
    experts (``apply_moe_grouped``); the capacity buffers below take every
    position they are handed, and count them. ``layer`` is the dropless
    path's (``apply_moe_grouped``).
    """
    from ..moe.sharded_moe import topk_gating_einsum
    dt = cfg.act_dtype
    b, s, e = x.shape

    if cfg.moe_impl == "grouped":
        from ..utils import groups as _g
        from ..parallel.sharding import current_manual_axes as _cma
        ep = (_g.get_mesh().shape.get("expert", 1)
              if _g.mesh_is_initialized() else 1)
        if ep == 1:
            return apply_moe_grouped(params, x, cfg, live, layer)
        assert live is None, "serving shards no expert axis"
        if not _cma():
            # sharded expert axis: dropless grouped path with an explicit
            # all-to-all ring (cannot nest inside an existing manual region
            # — the ZeRO++ step falls through to the einsum dispatch).
            # Guard the manual region's static divisibility contracts: the
            # einsum dispatch tolerates anything via GSPMD padding, so odd
            # shapes (v1 serving with b=1, ragged expert counts) fall back
            # loudly-documented rather than mis-routing.
            import math as _math
            mesh = _g.get_mesh()
            bsz, slen, _ = x.shape
            bdiv = _math.prod(mesh.shape.get(a, 1) for a in _g.BATCH_AXES)
            sdiv = mesh.shape.get("seq", 1)
            if (cfg.num_experts % ep == 0 and bsz % bdiv == 0
                    and slen % sdiv == 0):
                return apply_moe_grouped_ep(params, x, cfg, mesh)

    assert layer is None, "only the dropless path reads a stack of layers"
    # Explicit dispatch/combine layouts (the reference's all-to-all
    # semantics, sharded_moe.py:533 _AllToAll): tokens ride the batch axes,
    # expert buffers ride the expert axis. Without these anchors XLA's
    # propagation can demand embed-sharded activations inside the layer scan
    # (involuntary full rematerialization).
    constrain_tok = lambda t: t
    constrain_exp = lambda t: t
    from ..utils import groups as _groups
    from ..parallel.sharding import current_manual_axes
    if _groups.mesh_is_initialized() and not current_manual_axes():
        mesh = _groups.get_mesh()
        if mesh.devices.size > 1:
            import jax.sharding as _js
            batch_axes = tuple(a for a in _groups.BATCH_AXES
                               if mesh.shape.get(a, 1) > 1) or None
            exp_axis = "expert" if mesh.shape.get("expert", 1) > 1 else None
            tok_sh = _js.NamedSharding(mesh, _js.PartitionSpec(batch_axes, None))
            exp_sh = _js.NamedSharding(
                mesh, _js.PartitionSpec(exp_axis, None, None))
            constrain_tok = lambda t: jax.lax.with_sharding_constraint(t, tok_sh)
            constrain_exp = lambda t: jax.lax.with_sharding_constraint(t, exp_sh)

    tokens = constrain_tok(x.reshape(b * s, e))
    logits = jnp.einsum("te,ex->tx", tokens.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    combine, dispatch, aux_loss = topk_gating_einsum(
        logits, k=cfg.num_experts_per_tok, capacity_factor=cfg.moe_capacity_factor,
        normalize=cfg.moe_norm_topk)
    # dispatch: (T, X, C) bool → expert inputs (X, C, E); the einsum against
    # batch-sharded tokens with expert-sharded output IS the all-to-all
    expert_in = constrain_exp(jnp.einsum("txc,te->xce", dispatch.astype(dt), tokens))
    g = jnp.einsum("xce,xef->xcf", expert_in, params["wi_gate"].astype(dt))
    u = jnp.einsum("xce,xef->xcf", expert_in, params["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    expert_out = constrain_exp(jnp.einsum("xcf,xfe->xce", h, params["wo"].astype(dt)))
    out = constrain_tok(jnp.einsum("txc,xce->te", combine.astype(dt), expert_out))
    if cfg.moe_shared_expert_size:
        out = out + _apply_shared_expert(params, tokens, cfg)
    out = out.reshape(b, s, e)
    if live is None:
        return out, aux_loss
    return out, aux_loss, jnp.sum(dispatch, axis=(0, 2), dtype=jnp.int32)


# ---- embeddings ---------------------------------------------------------

def init_embeddings(rng, cfg: TransformerConfig):
    r = jax.random.split(rng, 3)
    params = {"tok": _normal(r[0], (cfg.vocab_size, cfg.hidden_size), cfg.p_dtype, 0.02)}
    axes = {"tok": ("vocab", "embed")}
    if cfg.position == "learned":
        params["pos"] = _normal(r[1], (cfg.max_seq_len, cfg.hidden_size), cfg.p_dtype, 0.02)
        axes["pos"] = ("unmodeled", "embed")
    if cfg.type_vocab_size:
        params["type"] = _normal(r[1] if cfg.position != "learned" else
                                 jax.random.fold_in(r[1], 1),
                                 (cfg.type_vocab_size, cfg.hidden_size), cfg.p_dtype, 0.02)
        axes["type"] = ("unmodeled", "embed")
    if cfg.embedding_norm:
        en, en_axes = init_norm(cfg)
        params["emb_norm"] = en
        axes["emb_norm"] = en_axes
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(r[2], (cfg.hidden_size, cfg.vocab_size), cfg.p_dtype,
                                    cfg.hidden_size ** -0.5)
        axes["lm_head"] = ("embed", "vocab")
        if cfg.lm_head_bias:
            params["lm_head_bias"] = _zeros((cfg.vocab_size,), cfg.p_dtype)
            axes["lm_head_bias"] = ("vocab",)
    return params, axes
