"""Causal transformer LM: the framework's native model family.

Functional design: ``CausalLM(cfg)`` exposes ``init(rng) -> params``,
``apply(params, input_ids, ...) -> logits``, ``loss(params, batch) -> scalar``
and ``logical_axes()`` — a parallel pytree of logical-axis tuples consumed by
``parallel/sharding.py`` to derive ZeRO/TP/EP shardings.

Layers are stacked along a leading "layers" dim and executed with
``lax.scan`` (one compile of one layer regardless of depth — the XLA analog
of the reference's per-layer module loop). Activation checkpointing is
``jax.checkpoint`` on the scan body (reference
``runtime/activation_checkpointing/checkpointing.py:486``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.logging import logger
from . import layers as L
from .config import TransformerConfig, get_config


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(i, (str, type(None))) for i in x)


def _axes_of(init_fn):
    """Extract the logical-axes tree of an ``init_fn(rng) -> (params, axes)``
    without allocating parameter memory (shapes traced via eval_shape; the
    axes dict escapes through a side channel)."""
    box = []

    def wrapped(rng):
        out = init_fn(rng)
        params, axes = out if isinstance(out, tuple) else (out, {})
        box.append(axes)
        return params

    jax.eval_shape(wrapped, jax.random.PRNGKey(0))
    return box[0]


def _activation_constraint(partition: bool = False):
    """Pin the (B, S, E) scan-carried activation to batch/seq sharding.

    Without this, XLA's sharding propagation can derive an embed-dim
    sharding for the loop carry from ZeRO gradient constraints and emit an
    'involuntary full rematerialization' reshard inside the layer scan."""
    from ..utils import groups
    if not groups.mesh_is_initialized():
        return lambda h: h
    mesh = groups.get_mesh()
    if mesh.devices.size == 1:
        return lambda h: h
    from ..parallel import sharding as shd
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = shd.batch_spec(mesh)
    if partition and mesh.shape.get("tensor", 1) > 1 and spec[1] is None:
        # partitioned activations (reference checkpointing.py:486): the
        # checkpoint-boundary residual IS this scan carry — anchoring its
        # sequence dim to the tensor axis makes XLA STORE each rank's slice
        # and all-gather only on use (forward compute + backward recompute)
        spec = P(spec[0], "tensor", *spec[2:])

    sharding = NamedSharding(mesh, spec)

    def constrain(h):
        # decided at trace time: inside shard_map manual regions (ZeRO++
        # quantized-collective step) sharding constraints on values varying
        # over manual axes are invalid — the anchor is only needed for the
        # plain-SPMD propagation anyway
        if shd.current_manual_axes():
            return h
        return jax.lax.with_sharding_constraint(h, sharding)

    return constrain


def _remat_policy(name: str):
    if name == "full":
        return None  # jax.checkpoint default: save nothing
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "dots_no_batch":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if name == "dots_offload":
        # the reference's cpu_checkpointing (activation checkpoints parked
        # in host memory, runtime/activation_checkpointing/checkpointing.py
        # partition+cpu variants): matmul outputs are saved but OFFLOADED to
        # pinned host memory, streamed back for the backward — activation
        # residency on device drops to the live layer
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    return None


def layer_plan(cfg):
    """Execution plan for the layer stack (None = homogeneous single scan).

    Heterogeneous stacks (cfg.layer_types, e.g. Qwen2-MoE's interleaved
    dense-MLP layers — reference ``model_implementations/qwen_v2_moe``) are
    compiled as:
      ("periodic", p) — tags repeat with period p (decoder_sparse_step):
        ONE scan over L/p super-layers whose body applies p sublayers; still
        one compiled body regardless of depth.
      ("segments", [(tag, start, length), ...]) — contiguous runs
        (mlp_only_layers prefixes): one scan per run.
    """
    if cfg.mixer_pattern is not None:
        # layers of different mixers hold different weights: always the
        # pattern's period, one scan step a period even where the stack is
        # one period deep, so every depth lays its weights out alike
        # (``layer_types`` beside it is not written; leading dense layers,
        # ``moe_first_dense``, only where the stack is ONE period: which
        # MLP a period's layer holds must not depend on the period)
        assert cfg.layer_types is None, "mixer_pattern beside layer_types"
        cfg.layer_mixers()      # the pattern tiles the stack
        assert not cfg.moe_first_dense \
            or len(cfg.mixer_pattern) == cfg.num_layers, \
            "leading dense layers in a stack of several mixer periods"
        return ("periodic", len(cfg.mixer_pattern))
    tags = cfg.layer_tags
    if tags is None or len(set(tags)) <= 1:
        return None
    n = len(tags)
    # a period must leave >= 2 scan steps (p == n is the fully-unrolled
    # degenerate "period"; contiguous runs handle those stacks better)
    for p in range(2, min(8, n // 2) + 1):
        if n % p == 0 and all(tags[i] == tags[i % p] for i in range(n)):
            return ("periodic", p)
    runs = []
    start = 0
    for i in range(1, n + 1):
        if i == n or tags[i] != tags[start]:
            runs.append((tags[start], start, i - start))
            start = i
    return ("segments", runs)


def layer_groups(cfg):
    """None (homogeneous) or the ordered param groups of the plan:
    [(tag, (layer indices...)), ...] — group i becomes params["layers"]["g{i}"]
    stacked over its indices. Shared by the model and the HF checkpoint
    containers so both lay out the same tree."""
    plan = layer_plan(cfg)
    if plan is None:
        return None
    if plan[0] == "periodic":
        p = plan[1]
        tags = cfg.layer_mixers() or cfg.layer_tags
        if cfg.mixer_pattern is not None:
            # a mixer's tag, and ".dense" behind it where the layer is one
            # of a routed stack's leading dense ones
            tags = [t + ".dense" * (cfg.is_moe and i < cfg.moe_first_dense)
                    for i, t in enumerate(tags)]
        return [(tags[i], tuple(range(i, cfg.num_layers, p)))
                for i in range(p)]
    return [(tag, tuple(range(start, start + ln)))
            for tag, start, ln in plan[1]]


def walk_layer_plan(plan, groups_, layers_params, xs, carry, body, wrap=None):
    """Single driver for the layer-plan walk — train forward, cached decode,
    and the paged serving runner all follow the same three shapes, so the
    group ordering/slicing logic lives exactly once.

    ``plan``/``groups_``: the model's ``layer_plan``/``layer_groups``
    (None = homogeneous). ``layers_params``: the (possibly grouped) stacked
    layer tree. ``xs``: pytree of per-layer inputs with leading axis L in
    ORIGINAL layer order (None leaves pass through). ``body(carry, lp, xs_t,
    tag) -> (carry, ys_t)`` applies one layer (ys_t may be None).
    ``wrap``: optional transform applied to each scan-step function (remat);
    for the periodic plan it wraps the whole super-layer step, matching the
    one-checkpoint-per-scan-step policy of the homogeneous path.

    Returns (carry, ys) with ys leaves stacked back in original layer order.
    """
    wrap = wrap or (lambda f: f)
    if groups_ is None:
        def step(carry, t):
            lp, xs_t = t
            return body(carry, lp, xs_t, None)
        return jax.lax.scan(wrap(step), carry, (layers_params, xs))
    if plan[0] == "periodic":
        p = plan[1]
        xs_rs = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // p, p) + a.shape[1:]), xs)

        def super_step(carry, t):
            groups_t, xs_t = t
            ys = []
            for j, (tag, _) in enumerate(groups_):
                xj = jax.tree.map(lambda a: a[j], xs_t)
                carry, y = body(carry, groups_t[f"g{j}"], xj, tag)
                ys.append(y)
            stacked = (None if ys[0] is None
                       else jax.tree.map(lambda *z: jnp.stack(z), *ys))
            return carry, stacked

        carry, ys = jax.lax.scan(wrap(super_step), carry, (layers_params, xs_rs))
        ys = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), ys)
        return carry, ys
    # contiguous segments: one scan per run, ys re-concatenated in order
    parts = []
    for gi, (tag, idxs) in enumerate(groups_):
        lo, n = idxs[0], len(idxs)
        xs_seg = jax.tree.map(lambda a: a[lo:lo + n], xs)

        def step(carry, t, _tag=tag):
            lp, xs_t = t
            return body(carry, lp, xs_t, _tag)

        carry, y = jax.lax.scan(wrap(step), carry,
                                (layers_params[f"g{gi}"], xs_seg))
        parts.append(y)
    ys = (None if parts[0] is None
          else jax.tree.map(lambda *z: jnp.concatenate(z), *parts))
    return carry, ys


def lm_head_logits(h, w, transpose, dt, bias=None, softcap=0.0):
    """logits = h @ (w if transpose else w.T) (+ bias): (B, S, E) → (B, S, V).

    ``softcap``: Gemma-2 final_logit_softcapping (cap * tanh(logits/cap))."""
    eq = "bse,ev->bsv" if transpose else "bse,ve->bsv"
    logits = jnp.einsum(eq, h, w.astype(dt))
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def masked_token_nll(logits, labels, loss_mask=None):
    """Mean fp32 cross-entropy over (B, S) tokens; loss_mask weights (or
    drops) positions. Avoids materializing a full fp32 log-softmax."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logits
    if loss_mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def logit_buffer_bytes(n_tokens, cfg):
    """Size of the (B, S, V) logits the dense loss would materialize —
    the chunked-CE engagement test shared by decoder and encoder heads."""
    return n_tokens * cfg.vocab_size * (2 if cfg.act_dtype != jnp.float32 else 4)


class CausalLM:
    """Decoder-only LM covering GPT-2 / Llama / Mixtral families."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self._inv_freq = L.rope_frequencies(cfg) if cfg.position == "rope" else None
        # RoPE that differs from the plain one by layer (YaRN on the layers
        # of full attention): (L, d/2) frequencies and (L,) factors,
        # walked beside the layers; None for every other model
        self._rope_layers = L.rope_by_layer(cfg)
        self._plan = layer_plan(cfg)
        self._groups = layer_groups(cfg)

    # -- init --

    def _init_layer(self, rng, layer_type=None):
        cfg = self.cfg
        if cfg.shortcut_moe:
            return self._init_double_layer(rng)
        r_attn, r_mlp = jax.random.split(rng)
        # a group's tag names its MLP ("dense" | "moe") or, in a stack of
        # mixed mixers, its mixer ("linear" | "conv" | "full"; the MLP is
        # the config's, or dense where ".dense" follows: ``layer_groups``):
        # a linear or a conv layer holds its mixer under "attn" too
        mixer, _, dense = (layer_type or "").partition(".") \
            if cfg.mixer_pattern is not None else (None, "", "")
        attn, attn_axes = (L.init_gdn if mixer == "linear" else
                           L.init_conv_mixer if mixer == "conv" else
                           L.init_mla if cfg.kv_lora_rank
                           else L.init_attention)(r_attn, cfg)
        if (cfg.is_moe and not dense if layer_type is None or mixer
                else layer_type == "moe"):
            mlp, mlp_axes = L.init_moe_mlp(r_mlp, cfg)
        else:
            mlp, mlp_axes = L.init_mlp(r_mlp, cfg)
        r_norm = (jax.random.split(jax.random.fold_in(rng, 2))
                  if cfg.norm_unit_offset else (None, None))
        norm1, norm1_axes = L.init_norm(cfg, r_norm[0])
        norm2, norm2_axes = L.init_norm(cfg, r_norm[1])
        params = {"attn": attn, "mlp": mlp, "norm1": norm1, "norm2": norm2}
        axes = {"attn": attn_axes, "mlp": mlp_axes, "norm1": norm1_axes, "norm2": norm2_axes}
        if cfg.sandwich_norm:   # Gemma-2 post-attn / post-ffw output norms
            for nm in ("norm3", "norm4"):
                params[nm], axes[nm] = L.init_norm(cfg)
        return params, axes

    def _init_double_layer(self, rng):
        """A shortcut-connected layer (LongCat-Flash): two latent
        attentions, two dense MLPs and their four norms, each leaf stacked
        over the pair (axis 0), and one routed block under ``moe``."""
        cfg = self.cfg
        r_attn, r_mlp, r_moe = jax.random.split(rng, 3)

        def pair(init, rng=None):
            if rng is None:
                params, axes = init(cfg)
                params = jax.tree.map(lambda a: jnp.stack([a, a]), params)
            else:
                axes = init(rng, cfg)[1]
                params = jax.vmap(lambda r: init(r, cfg)[0])(
                    jax.random.split(rng))
            return params, jax.tree.map(lambda a: ("unmodeled",) + a, axes,
                                        is_leaf=_is_axes_leaf)

        parts = {"attn": pair(L.init_mla, r_attn),
                 "mlp": pair(L.init_mlp, r_mlp),
                 "norm1": pair(L.init_norm), "norm2": pair(L.init_norm),
                 "moe": L.init_moe_mlp(r_moe, cfg)}
        return ({n: p for n, (p, _) in parts.items()},
                {n: a for n, (_, a) in parts.items()})

    def init(self, rng):
        cfg = self.cfg
        r_emb, r_layers = jax.random.split(rng)
        emb, _ = L.init_embeddings(r_emb, cfg)
        layer_rngs = jax.random.split(r_layers, cfg.num_layers)
        # vmap over the per-layer keys: the same values as a Python loop
        # over layers, but ONE layer's init in the program (a 24-layer
        # loop compiled for 90 s on the chip) and no second copy of every
        # layer held while stacking
        if self._groups is None:
            stacked = jax.vmap(lambda r: self._init_layer(r)[0])(layer_rngs)
        else:
            stacked = {}
            for gi, (tag, idxs) in enumerate(self._groups):
                stacked[f"g{gi}"] = jax.vmap(
                    lambda r, _tag=tag: self._init_layer(r, _tag)[0])(
                        layer_rngs[jnp.asarray(idxs)])
        out = {"embed": emb, "layers": stacked}
        if not cfg.post_norm:   # post-norm (BERT) normalizes inside each layer
            out["final_norm"] = L.init_norm(
                cfg, jax.random.fold_in(rng, 3)
                if cfg.norm_unit_offset else None)[0]
        if cfg.num_nextn_predict_layers:
            # a key of its own: the stack's weights do not depend on
            # whether the model has its prediction modules
            out["mtp"] = self._init_mtp(jax.random.fold_in(rng, 2))[0]
        return out

    def _init_mtp(self, rng):
        """The prediction modules (DeepSeek-V3's MTP), each leaf stacked
        over them: ``enorm`` / ``hnorm`` on the next token's embedding and
        on the stack's hidden state, ``eh_proj`` (2E -> E) over the two
        side by side, ``layer`` one layer of the stack's last kind with an
        attention of its own, ``norm`` before the head. The embedding and
        the head are the model's own."""
        cfg = self.cfg
        e = cfg.hidden_size
        tag = cfg.layer_type(cfg.num_layers - 1)

        def one(r):
            r_proj, r_layer = jax.random.split(r)
            return {"enorm": L.init_norm(cfg)[0], "hnorm": L.init_norm(cfg)[0],
                    "eh_proj": L._normal(r_proj, (2 * e, e), cfg.p_dtype, 0.02),
                    "layer": self._init_layer(r_layer, tag)[0],
                    "norm": L.init_norm(cfg)[0]}

        norm_axes = L.init_norm(cfg)[1]
        axes = {"enorm": norm_axes, "hnorm": norm_axes,
                "eh_proj": ("unmodeled", "embed"),
                "layer": _axes_of(lambda r: self._init_layer(r, tag)),
                "norm": norm_axes}
        params = jax.vmap(one)(
            jax.random.split(rng, cfg.num_nextn_predict_layers))
        return params, jax.tree.map(lambda a: ("layers",) + a, axes,
                                    is_leaf=_is_axes_leaf)

    def abstract_params(self):
        """Shape/dtype tree without allocating (for sharded init)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def logical_axes(self):
        """Pytree of logical-axis tuples mirroring ``init``'s output; stacked
        layer params get a leading "layers" axis."""
        cfg = self.cfg
        emb_axes = _axes_of(lambda r: L.init_embeddings(r, cfg))

        def stack_axes(tag=None):
            layer_axes = _axes_of(lambda r: self._init_layer(r, tag))
            return jax.tree.map(lambda a: ("layers",) + a, layer_axes,
                                is_leaf=_is_axes_leaf)

        if self._groups is None:
            stacked_axes = stack_axes()
        else:
            stacked_axes = {f"g{gi}": stack_axes(tag)
                            for gi, (tag, _) in enumerate(self._groups)}
        out = {"embed": emb_axes, "layers": stacked_axes}
        if not cfg.post_norm:
            out["final_norm"] = _axes_of(lambda r: L.init_norm(cfg))
        if cfg.num_nextn_predict_layers:
            out["mtp"] = self._init_mtp(jax.random.PRNGKey(0))[1]
        return out

    # -- forward --

    def _layer_windows(self):
        """(L,)-int32 per-layer window array for mixed local/global patterns
        (GPT-Neo alternation via ``local_attention_every``, Gemma-2's
        even-layers-windowed via an explicit ``window_pattern``), or None
        when layers are homogeneous (uniform windows flow through
        cfg.sliding_window inside apply_attention)."""
        windows = self.cfg.layer_windows()
        return None if windows is None else jnp.asarray(windows, jnp.int32)

    def _rope_args(self, rope):
        """``apply_attention``'s RoPE arguments for a layer: the model's
        plain frequencies, or the layer's own (``_rope_layers``)."""
        if rope is None:
            if self._rope_layers is not None:
                raise NotImplementedError(
                    "this model's RoPE differs by layer (rope_yarn) and the "
                    "caller walks its layers without it: only the model's "
                    "own forward passes and the paged serving runner do")
            return {"inv_freq": self._inv_freq}
        return {"inv_freq": rope[0], "rope_factor": rope[1]}

    def _layer_fn(self, lp, h, positions, segment_ids, attn_bias=None, window=None,
                  layer_type=None, rope=None):
        cfg = self.cfg
        if cfg.kv_lora_rank or cfg.shortcut_moe or cfg.mixer_pattern:
            raise NotImplementedError(
                "latent attention, shortcut-connected layers and mixers "
                "that keep a state a sequence (this stack's: "
                f"{', '.join(cfg.recurrent_kinds) or 'none'}) run on the "
                "paged serving path (inference/v2) only: no training or "
                "cache-less forward is written for them")
        rope = self._rope_args(rope)
        is_moe = cfg.is_moe if layer_type is None else layer_type == "moe"
        if cfg.act_quant_bits:
            # QAT activation quantization (compression QuantAct analog):
            # the layer input round-trips the int grid, STE backward
            from ..compression.compress import fake_quantize_activation
            h = fake_quantize_activation(h, cfg.act_quant_bits)
        if cfg.post_norm:
            # BERT block: norm AFTER each residual add, attention reads the
            # raw stream
            with jax.named_scope("attn"):
                attn_out, _ = L.apply_attention(
                    lp["attn"], h, cfg, positions=positions,
                    segment_ids=segment_ids, attn_bias=attn_bias,
                    window=window, **rope)
                h = L.apply_norm(lp["norm1"], h + attn_out, cfg)
            with jax.named_scope("mlp"):
                mlp_out = L.apply_mlp(lp["mlp"], h, cfg)
                return (L.apply_norm(lp["norm2"], h + mlp_out, cfg),
                        jnp.zeros((), jnp.float32))
        with jax.named_scope("attn"):
            a_in = L.apply_norm(lp["norm1"], h, cfg)
            attn_out, _ = L.apply_attention(
                lp["attn"], a_in, cfg, positions=positions,
                segment_ids=segment_ids, attn_bias=attn_bias, window=window,
                **rope)
            if cfg.sandwich_norm:   # Gemma-2: norm the sublayer OUTPUT pre-residual
                attn_out = L.apply_norm(lp["norm3"], attn_out, cfg)
        with jax.named_scope("mlp"):
            if cfg.parallel_block:
                # NeoX/Falcon parallel residual: attn and mlp both read the
                # pre-attention stream; one residual add
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            else:
                h = h + attn_out
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            if is_moe:
                mlp_out, aux = L.apply_moe_mlp(lp["mlp"], m_in, cfg)
            else:
                mlp_out, aux = (L.apply_mlp(lp["mlp"], m_in, cfg),
                                jnp.zeros((), jnp.float32))
            if cfg.sandwich_norm:
                mlp_out = L.apply_norm(lp["norm4"], mlp_out, cfg)
            if cfg.parallel_block:
                return h + attn_out + mlp_out, aux
            return h + mlp_out, aux

    @jax.named_scope("embed")
    def embed_fwd(self, embed_params, input_ids, positions=None, token_type_ids=None):
        """Token (+ learned position, + token-type) embedding lookup:
        (B, S) → (B, S, E)."""
        cfg = self.cfg
        dt = cfg.act_dtype
        h = embed_params["tok"].astype(dt)[input_ids]
        if cfg.embed_scale != 1.0:   # Gemma: sqrt(E), cast like HF's normalizer
            h = h * jnp.asarray(cfg.embed_scale, dt)
        if cfg.position == "learned":
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
            h = h + embed_params["pos"].astype(dt)[positions + cfg.position_offset]
        if cfg.type_vocab_size:   # BERT segment embeddings
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros_like(input_ids))
            h = h + embed_params["type"].astype(dt)[tt]
        if cfg.embedding_norm:   # BLOOM/BERT post-embedding layernorm
            h = L.apply_norm(embed_params["emb_norm"], h, cfg)
        return h

    @jax.named_scope("lm_head_loss")
    def head_loss(self, head_params, h, labels, loss_mask=None):
        """Final norm + lm head + cross-entropy from hidden states.

        ``head_params``: {"embed": ..., "final_norm": ...} — the persistent
        (non-layer) params. Used by the ZeRO-Infinity layer-streaming runner
        which never materializes the full param tree on device.
        """
        cfg = self.cfg
        if "final_norm" in head_params:   # absent for post-norm encoders
            h = L.apply_norm(head_params["final_norm"], h, cfg)
        w, transpose = self._lm_head_weight(head_params)
        if (cfg.loss_chunks > 0 and cfg.vocab_size >= 4096
                and logit_buffer_bytes(labels.size, cfg) > cfg.loss_chunk_threshold_bytes):
            from ..ops.cross_entropy import lm_cross_entropy
            return lm_cross_entropy(h, w.astype(h.dtype), labels, loss_mask=loss_mask,
                                    n_chunks=cfg.loss_chunks, transpose_w=transpose,
                                    softcap=cfg.logit_softcap)
        logits = lm_head_logits(h, w, transpose, cfg.act_dtype,
                                softcap=cfg.logit_softcap)
        return masked_token_nll(logits, labels, loss_mask)

    def hidden_states(self, params, input_ids, *, positions=None, segment_ids=None,
                      token_type_ids=None):
        """Embed + layer stack + final norm: (B, S) → ((B, S, E), aux_loss)."""
        cfg = self.cfg
        dt = cfg.act_dtype
        h = self.embed_fwd(params["embed"], input_ids, positions, token_type_ids)
        if cfg.position == "learned" and positions is None:
            positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)

        constrain = _activation_constraint(cfg.partition_activations)

        # ALiBi needs no precomputed bias: apply_attention passes the
        # per-head slopes down and the flash kernel builds the term
        # in-kernel; XLA fallbacks expand slopes per layer (cheap next to
        # the O(S^2) attention math they already do).
        attn_bias = None

        windows = self._layer_windows()
        aux0 = jnp.zeros((), jnp.float32)
        # inside a partial-manual shard_map (ZeRO++ quantized-collective
        # step) the MoE aux loss becomes data-varying through the routed
        # dispatch; the scan carry's initial value must match that vma type
        from ..parallel.sharding import current_manual_axes
        manual = current_manual_axes()
        if manual:
            aux0 = jax.lax.pcast(aux0, tuple(manual), to="varying")
        carry = (h, aux0)

        def make_body(fn):
            return (jax.checkpoint(fn, policy=_remat_policy(cfg.remat))
                    if cfg.remat != "none" else fn)

        def body(carry, lp, xs_t, tag):
            h, aux_sum = carry
            win, rope = xs_t
            h, aux = self._layer_fn(lp, h, positions, segment_ids, attn_bias,
                                    win, layer_type=tag, rope=rope)
            return (constrain(h), aux_sum + aux), None

        carry, _ = walk_layer_plan(self._plan, self._groups, params["layers"],
                                   (windows, self._rope_layers), carry, body,
                                   wrap=make_body)
        h, aux_total = carry
        if not cfg.post_norm:
            with jax.named_scope("lm_head_loss"):
                h = L.apply_norm(params["final_norm"], h, cfg)
        # average the load-balancing aux over layers that HAVE routers
        # (dense interleave layers contribute 0 and must not dilute it)
        n_moe = sum(1 for i in range(cfg.num_layers)
                    if cfg.layer_type(i) == "moe") or 1
        return h, aux_total / n_moe

    def _lm_head_weight(self, params):
        """Returns (w, transpose): logits = h @ (w.T if not transpose else w)."""
        if self.cfg.tie_embeddings:
            return params["embed"]["tok"], False
        return params["embed"]["lm_head"], True

    def apply(self, params, input_ids, *, positions=None, segment_ids=None,
              return_aux_loss=False):
        """input_ids: (B, S) int32 → logits (B, S, V)."""
        dt = self.cfg.act_dtype
        h, aux_total = self.hidden_states(params, input_ids, positions=positions,
                                          segment_ids=segment_ids)
        w, transpose = self._lm_head_weight(params)
        with jax.named_scope("lm_head_loss"):
            logits = lm_head_logits(h, w, transpose, dt,
                                    bias=params["embed"].get("lm_head_bias"),
                                    softcap=self.cfg.logit_softcap)
        if return_aux_loss:
            return logits, aux_total
        return logits

    # -- decode (KV-cache) --

    def init_cache(self, batch_size, max_len, dtype=None):
        """Stacked KV cache: {"k","v"}: (L, B, S_max, KVH, D) — scan-able."""
        cfg = self.cfg
        dt = dtype or cfg.act_dtype
        shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, cfg.dims_per_head)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def apply_decode(self, params, input_ids, cache, cache_len):
        """Incremental forward: input_ids (B, S_new); returns (logits, cache).

        ``lax.scan`` zips the stacked layer params with the stacked cache —
        one compiled layer regardless of depth, updated cache as scan ys.
        """
        cfg = self.cfg
        dt = cfg.act_dtype
        b, s = input_ids.shape
        positions = cache_len[:, None] + jnp.arange(s)[None, :]
        h = self.embed_fwd(params["embed"], input_ids, positions)

        attn_bias = None
        if cfg.position == "alibi":
            attn_bias = L.alibi_bias(cfg.num_heads, positions,
                                     jnp.arange(cache["k"].shape[2]))

        windows = self._layer_windows()

        def dec_layer(lp, h, ck, cv, win, tag=None, rope=None):
            is_moe = cfg.is_moe if tag is None else tag == "moe"
            if cfg.act_quant_bits:   # QAT: decode must match the forward
                from ..compression.compress import fake_quantize_activation
                h = fake_quantize_activation(h, cfg.act_quant_bits)
            a_in = L.apply_norm(lp["norm1"], h, cfg)
            attn_out, kv = L.apply_attention(lp["attn"], a_in, cfg, positions=positions,
                                             kv_cache=(ck, cv), cache_len=cache_len,
                                             attn_bias=attn_bias, window=win,
                                             **self._rope_args(rope))
            if cfg.sandwich_norm:
                attn_out = L.apply_norm(lp["norm3"], attn_out, cfg)
            if cfg.parallel_block:
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            else:
                h = h + attn_out
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            if is_moe:
                mlp_out, _ = L.apply_moe_mlp(lp["mlp"], m_in, cfg)
            else:
                mlp_out = L.apply_mlp(lp["mlp"], m_in, cfg)
            if cfg.sandwich_norm:
                mlp_out = L.apply_norm(lp["norm4"], mlp_out, cfg)
            if cfg.parallel_block:
                return h + attn_out + mlp_out, kv
            return h + mlp_out, kv

        def body(h, lp, xs_t, tag):
            ck, cv, win, rope = xs_t
            return dec_layer(lp, h, ck, cv, win, tag, rope)

        h, (new_k, new_v) = walk_layer_plan(
            self._plan, self._groups, params["layers"],
            (cache["k"], cache["v"], windows, self._rope_layers), h, body)
        h = L.apply_norm(params["final_norm"], h, cfg)
        w, transpose = self._lm_head_weight(params)
        logits = lm_head_logits(h, w, transpose, dt,
                                bias=params["embed"].get("lm_head_bias"),
                                softcap=cfg.logit_softcap)
        return logits, {"k": new_k, "v": new_v}

    # -- loss --

    def loss(self, params, batch):
        """batch: dict(input_ids (B, S), labels (B, S), optional loss_mask).

        Cross-entropy in fp32 (reference models compute loss in fp32 under
        fp16 training too); adds MoE aux loss when configured.
        """
        cfg = self.cfg
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        # The fused path trades one extra lm-head matmul (bwd recompute) for
        # never materializing (B, S, V): a win only once the logits are
        # actually big. Shapes are static under jit, so decide here.
        if (cfg.loss_chunks > 0 and cfg.vocab_size >= 4096
                and logit_buffer_bytes(batch["input_ids"].size, cfg)
                > cfg.loss_chunk_threshold_bytes):
            # fused vocab-chunked path: the (B, S, V) logits never exist
            from ..ops.cross_entropy import lm_cross_entropy
            h, aux = self.hidden_states(params, batch["input_ids"],
                                        positions=batch.get("positions"),
                                        segment_ids=batch.get("segment_ids"))
            w, transpose = self._lm_head_weight(params)
            with jax.named_scope("lm_head_loss"):
                loss = lm_cross_entropy(
                    h, w.astype(h.dtype), labels, loss_mask=mask,
                    n_chunks=cfg.loss_chunks, transpose_w=transpose,
                    softcap=cfg.logit_softcap)
        else:
            logits, aux = self.apply(params, batch["input_ids"],
                                     positions=batch.get("positions"),
                                     segment_ids=batch.get("segment_ids"),
                                     return_aux_loss=True)
            with jax.named_scope("lm_head_loss"):
                loss = masked_token_nll(logits, labels, mask)
        if cfg.is_moe:
            loss = loss + cfg.moe_aux_loss_coef * aux
        return loss

    def param_count(self):
        import math
        return sum(math.prod(x.shape) for x in jax.tree.leaves(self.abstract_params()))


def build_model(name_or_cfg, **overrides) -> CausalLM:
    if isinstance(name_or_cfg, str):
        cfg = get_config(name_or_cfg, **overrides)
    elif isinstance(name_or_cfg, TransformerConfig):
        cfg = name_or_cfg.replace(**overrides) if overrides else name_or_cfg
    else:
        raise TypeError(
            f"build_model expects preset name or TransformerConfig, got {type(name_or_cfg)}")
    if cfg.mlm_head or not cfg.causal:
        from .bert import EncoderLM
        return EncoderLM(cfg)
    return CausalLM(cfg)
