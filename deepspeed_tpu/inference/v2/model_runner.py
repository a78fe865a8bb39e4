"""Paged forward execution for ragged inference.

Analog of the FastGen model pass (``inference/v2/model_implementations/
inference_transformer_base.py`` + ``kernels/ragged_ops/linear_blocked_kv_rotary``
+ ``blocked_flash``): one compiled function handles a batch of sequence
chunks — prefill chunks (C>1) and decode steps (C=1) are the same program at
different chunk widths, which is the Dynamic-SplitFuse unification.

Per layer, inside the layer walk (the full (L, KVH, NB, bs, D) kv-head-major
pools stay loop-invariant, read through the layer's index): project q/k/v,
RoPE at absolute positions, attend over the row's pages and the chunk's own
KV; after the walk ONE commit writes every layer's chunk KV into its pages.
On the chip BOTH decode steps (C=1) and prefill chunks (C>1) run the unified
Pallas paged kernel (``ops/pallas/paged_attention.py``), which reads pages
IN PLACE via the block table and handles causal masks, sliding windows,
ALiBi, and attention softcapping in-kernel, and the commit is its twin
(``ops/pallas/kv_commit.py``), which writes the touched pages IN PLACE in
the layout the first reads; the XLA gather attention and scatter commit
remain as the non-TPU / int8-pool / escape-hatch path. Pools are donated.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models import layers as L
from ...models.transformer import CausalLM
from ...ops.attention import decode_attention
from ...ops.pallas import gated_delta_rule
from ..sampling import (block_unmask, sample_logits_per_row,
                        speculative_verify_per_row)
from .kv_cache import dequantize_kv_lanes, quantize_kv_lanes
from .telemetry import (CONV_STAT_NAMES, LATENT_STAT_NAMES,    # in-graph
                        LAYER_STAT_NAMES, MAX_RUNGS,           # counter
                        MOE_STAT_NAMES, MOVED_STAT_NAMES,      # layout
                        MTP_STAT_NAMES, RECURRENT_STAT_NAMES, n_stats,
                        pack_ladder)


def _use_pallas_paged() -> bool:
    if os.environ.get("DS_TPU_DISABLE_PALLAS", "0") == "1":
        return False
    return jax.default_backend() == "tpu"


class PagedModelRunner:
    def __init__(self, model: CausalLM, block_size: int, max_blocks_per_seq: int,
                 kinds=None):
        if model.cfg.post_norm or model.cfg.mlm_head or not model.cfg.causal:
            raise NotImplementedError(
                "the paged serving runner executes causal pre-norm decoder "
                "blocks; BERT-style encoders are not autoregressive — serve "
                "them with InferenceEngine (v1) forward passes")
        self.model = model
        self.cfg = model.cfg
        self.block_size = block_size
        self.max_blocks = max_blocks_per_seq
        # caches by layer kind (``kv_cache.cache_kinds``; None: one kind,
        # and every program below is what it always was). The pools and the
        # block tables of the serving loops are then tuples, one a kind
        self.kinds = kinds
        if kinds is not None and model._groups is not None:
            raise NotImplementedError(
                "a stack of mixed cache kinds whose layers also differ in "
                "structure (layer_types) is not walked yet")
        self._fns = {}
        # compiled programs that lived in since-evicted entry points (e.g.
        # the spec loops dropped by a draft re-attach): keeps the monotonic
        # total honest when _fns entries disappear
        self._evicted_programs = 0
        self._compile_base = 0
        # tensor-parallel serving context (tp.TPContext) — when set, the
        # serving loops compile under shard_map on its 1-D tp mesh and the
        # forward issues explicit per-layer collectives; None keeps every
        # path byte-identical to the unsharded runner
        self.tp = None

    @property
    def stat_window(self):
        """The sliding window the in-graph work counters (``STAT_KV_READ``,
        ``STAT_ATTN_PAIRS``) bound a row's KV reads by: the model's uniform
        window, or None (full context; per-layer local/global patterns
        count as full context — an upper bound)."""
        cfg = self.cfg
        if cfg.sliding_window is None or cfg.layer_windows() is not None:
            return None
        return int(cfg.sliding_window)

    @property
    def layer_work(self):
        """What the layered work counters of a model of mixed cache kinds
        (``telemetry.LAYER_STAT_NAMES``) are computed from: every layer's
        window (0: a global layer; the others have the ring); None for a
        model of one kind, whose stat vector has no such lanes. A model
        with linear or conv layers counts its full-attention layers so:
        they are the layers that read keys."""
        if self.recurrent_kinds:
            return (0,) * self.cfg.cache_layers
        return None if self.kinds is None else self.cfg.layer_windows()

    @property
    def linear_layers(self) -> int:
        """Layers whose mixer is a Gated DeltaNet (``cfg.mixer_pattern``):
        their states ride the frame programs' carry (``recurrent``) and
        their work is the stat vector's last lanes
        (``telemetry.RECURRENT_STAT_NAMES``); 0 for every other model."""
        return self.cfg.linear_layers

    @property
    def conv_layers(self) -> int:
        """Layers whose mixer is a gated short convolution: their tails
        ride the carry behind the linear layers' pair, and their work is
        ``telemetry.CONV_STAT_NAMES``; 0 for every other model."""
        return self.cfg.conv_layers

    @property
    def recurrent_kinds(self) -> tuple:
        """The mixer kinds of the stack that keep a state a slot
        (``cfg.recurrent_kinds``: "linear", "conv"); () for a model whose
        layers all attend, which carries nothing for them."""
        return self.cfg.recurrent_kinds

    @property
    def recurrent_stat_names(self) -> tuple:
        """The stat vector's last lanes of a model with a state a slot: by
        the kinds present, ``RECURRENT_STAT_NAMES`` for linear layers, then
        ``CONV_STAT_NAMES`` for conv layers."""
        return sum(({"linear": RECURRENT_STAT_NAMES,
                     "conv": CONV_STAT_NAMES}[kind]
                    for kind in self.recurrent_kinds), ())

    def recurrent_shapes(self, slots: int):
        """The (shape, dtype) of each array that ``slots`` rows carry, by
        what each mixer kind present keeps, in ``recurrent_kinds``' order.
        Linear (Gated DeltaNet) layers: the state, float32 (linear layers,
        slots, Hv, dk, dv), and the convolution tail (linear layers, K - 1,
        slots, channels) in the activations' dtype. Conv (gated short
        convolution) layers: the tail ALONE, (conv layers, K - 1, slots,
        hidden) in the activations' dtype; no state of zero size rides
        beside it. Layers first, and a tail's slots beside its channels:
        the order the chip's compiler gives both inside the wide program
        whatever it is handed (a frame relaid both, in and out, while the
        slots came first: ``tests/test_chip_compile.py``); the slots' axes
        are ``ragged_manager.RECURRENT_SLOT_AXES``, by rank."""
        cfg = self.cfg
        kept = {
            "linear": (((cfg.linear_layers, slots, cfg.linear_num_value_heads,
                         cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                        jnp.float32),
                       ((cfg.linear_layers, cfg.linear_conv_kernel - 1, slots,
                         cfg.linear_channels), cfg.act_dtype)),
            "conv": (((cfg.conv_layers, cfg.conv_kernel - 1, slots,
                       cfg.hidden_size), cfg.act_dtype),)}
        return sum((kept[kind] for kind in self.recurrent_kinds), ())

    @property
    def latent_layers(self):
        """Attention layers that read latent rows (``LATENT_STAT_NAMES``
        count their work summed over them), or None for a model that caches
        K and V by head, whose stat vector has no such lanes."""
        return self.cfg.attn_layers if self.cfg.latent_lanes else None

    @property
    def row_heads(self):
        """(kv heads, query rows a position a kv head) the row-tile counters
        (``telemetry.TILE_STAT_NAMES``) cut a wide step's chunk by; None for
        the latent format, whose kernel tiles the chunk's positions itself
        (the counters stay 0)."""
        cfg = self.cfg
        if cfg.latent_lanes:
            return None
        return cfg.kv_heads, cfg.num_heads // cfg.kv_heads

    def stat_context(self, max_seq_len: int, chunk: int) -> int:
        """The context ``telemetry.check_stat_range`` bounds a frame's
        largest work lane by: the uniform window or the longest sequence,
        and for a model of mixed kinds the layers' own, summed."""
        if self.latent_layers:
            return self.latent_layers * max_seq_len
        if self.recurrent_kinds:
            return self.cfg.cache_layers * (max_seq_len + chunk) - chunk
        if self.kinds is None:
            return self.stat_window or max_seq_len
        return sum((w or max_seq_len) + chunk
                   for w in self.cfg.layer_windows()) - chunk

    def pack_ladder(self, b: int, c: int):
        """The rungs a (b, c) step may pack its live tokens into
        (``telemetry.pack_ladder``), the last one the chunk whole. Two
        layers do not treat every position alike, and a model that has one
        keeps the chunk whole: per-tensor activation quantization reads its
        scale off every position, and capacity-routed experts (the
        ``einsum`` dispatch) let the pad positions compete for an expert's
        capacity. Dropless routing (``moe_impl="grouped"``) packs."""
        cfg = self.cfg
        ladder = pack_ladder(b, c)
        whole = cfg.act_quant_bits or (cfg.is_moe
                                       and cfg.moe_impl != "grouped")
        return ladder[-1:] if whole else ladder

    @property
    def experts_from_stack(self) -> bool:
        """Dropless experts run a grouped-product kernel, and a kernel's
        operand is materialized: a layer's slice of the stacked experts
        would be copied every layer and step. Such a model walks its layers
        by index at every width, and the product reads the stack."""
        return self.cfg.is_moe and self.cfg.moe_impl == "grouped"

    @property
    def has_mtp(self) -> bool:
        """The model has a prediction module (``params["mtp"]``): it can
        draft for itself, and its stat vector ends with the module's lanes
        (``telemetry.MTP_STAT_NAMES``)."""
        return bool(self.cfg.num_nextn_predict_layers)

    @property
    def n_stats(self) -> int:
        """Lanes of the stat vector this model's serving loops carry: a
        model with routed experts counts their work in lanes of its own
        (``telemetry.n_stats``)."""
        return n_stats(self.cfg.is_moe,
                       self.kinds is not None or bool(self.recurrent_kinds),
                       share=self.cfg.moe_is_share,
                       latent=bool(self.cfg.latent_lanes),
                       mtp=self.has_mtp,
                       recurrent=len(self.recurrent_stat_names),
                       block=bool(self.block_length))

    @property
    def block_length(self) -> int:
        """Positions of a block of a model that generates by diffusion over
        blocks (``cfg.block_length``): a row past its prompt holds one,
        tokens and masked flags, on the frame programs' carry (``block``),
        and the stat vector ends with ``telemetry.BLOCK_STAT_NAMES``; 0 for
        every other model."""
        return self.cfg.block_length

    @property
    def narrow_width(self) -> int:
        """Positions a row of a NARROW frame (no row prefills) may forward:
        one token, or for a model that generates by diffusion over blocks
        two blocks, the one a step commits and the next, which the same
        step begins to denoise (``_block_scan_body``)."""
        return max(1, 2 * self.cfg.block_length)

    def set_tp(self, tp_ctx) -> None:
        """Bind a ``tp.TPContext`` (engine setup, before any serving loop
        compiles). The serving entry points close over the context, so any
        already-compiled loops must go — same discipline as a draft
        re-attach."""
        self.evict(*list(self._fns))
        self.tp = tp_ctx

    def _build(self, chunk: int):
        fwd = self._forward

        @functools.partial(jax.jit, donate_argnums=(5, 6))
        def run(params, ids, positions, block_tables, valid_counts, kpool, vpool):
            return fwd(params, ids, positions, block_tables, valid_counts, kpool, vpool)

        return run

    def _forward(self, params, ids, positions, block_tables, valid_counts,
                 kpool, vpool, *, all_logits=False, tp=None, moe_work=False,
                 hidden=False, mtp=None, recurrent=None, head_at=None):
        """ids/positions: (B, C); block_tables: (B, MB);
        valid_counts: (B,) number of real (non-pad) tokens in the chunk;
        kpool/vpool: (L, KVH, NB, bs, D). Returns (last_logits (B, V),
        kpool, vpool) — or ((B, C, V) logits at EVERY chunk position when
        ``all_logits`` is set, which is how the speculative verify scores
        all gamma+1 positions in one batched ragged forward. With
        ``moe_work`` (the serving loops, for their stats vector) a fourth
        value: the routed experts' work summed over the layers, in the
        order of ``telemetry.MOE_STAT_NAMES``, or None for a model without
        routed experts, which computes and carries nothing for them.

        ``tp`` (a ``tp.TPContext``) marks a trace INSIDE a shard_map manual
        region: params and KV pools are this shard's slices (heads/kv_heads/
        mlp/vocab-sharded per ``parallel/sharding.py``), and the forward
        issues the explicit Megatron collectives — masked-lookup psum for
        the vocab-sharded embedding, a psum after the attention-output and
        MLP-output (row-parallel) projections, and a logit all-gather at the
        head. ``tp=None`` traces the exact pre-TP program.

        A model of mixed cache kinds (``self.kinds``) takes ``block_tables``,
        ``kpool`` and ``vpool`` as tuples, one a kind, and gives the pools
        back so. A model with latent attention (``cfg.latent_lanes``) takes
        its one pool of rows as ``kpool`` and None as ``vpool``.

        ``hidden``: one value more, last, the stack's hidden state before
        the final norm, (B, C, E): what the model's prediction module reads.
        ``mtp`` = (hidden states (B, C, E), rows only): the forward of the
        PREDICTION MODULE (``params["mtp"]``, the first) in place of the
        stack's: ``ids`` at ``positions`` are the tokens AFTER the hidden
        states' positions; the module's layer reads cache layer
        ``cfg.attn_layers`` of the same pool through the same tables and
        commits nothing. Returns (logits, rows (1, B, C, 1, lanes) for the
        caller to commit, the module's experts' work), or the rows alone
        (``rows only``: the module's cache at positions nobody drafts
        from: no attention, no experts, no head).

        ``recurrent`` (``recurrent_shapes``): a model with linear (Gated
        DeltaNet) layers (``cfg.mixer_pattern``) carries (state (linear
        layers, B, Hv, dk, dv) float32, tail (linear layers, K - 1, B,
        channels)), one with conv (gated short convolution) layers (tail
        (conv layers, K - 1, B, hidden),): every row's through the step,
        given back as the LAST value. Row b moves what it keeps by its
        ``valid_counts[b]`` live positions, which are the first of its
        chunk; a row with none keeps state and tail to the bit. The pools
        hold the full-attention layers alone.

        ``head_at`` (B,) int32, a model that generates by diffusion over
        blocks: the chunk index of the first of the ``block_length``
        positions whose logits a row's step reads (None: 0 in every row)."""
        cfg = self.cfg
        # a model that attends causally by block (``cfg.block_length``): a
        # position sees every key up to the last position of its block, in
        # prefill chunks and block steps alike, on either attention path;
        # RoPE and the pages keep the true positions. Its logits are those
        # AT ``block_length`` positions of each row's chunk, (B,
        # block_length, V): the block a row past its prompt denoises there,
        # the chunk's first positions or, behind a block the same step
        # commits, the next (``head_at``)
        see = None
        if cfg.block_length:
            blk = cfg.block_length
            see = jnp.where(positions >= 0, positions // blk * blk + blk - 1,
                            -1)
        if cfg.mixer_pattern is not None and recurrent is None:
            raise NotImplementedError(
                f"a model with {' and '.join(cfg.recurrent_kinds)} layers "
                "keeps a state a slot (a linear layer's recurrent state and "
                "convolution tail, a conv layer's tail), which rides the "
                "frame programs' carry: it is served by serve() and "
                "generate(), not by put() / step()")
        bs = self.block_size
        kinds = self.kinds
        for kind in kinds or ():
            # the ring's invariant (kv_cache.py), for this step's width
            assert kind.ring is None or \
                kind.ring * bs >= kind.window + ids.shape[1] + bs, \
                (kind, ids.shape, bs)
        model = self.model
        dt = cfg.act_dtype
        b = ids.shape[0]
        # some layer routes to experts: the layers then count that work and
        # learn which positions are live. A model without them traces none
        # of it
        routed = cfg.is_moe
        # the per-token layers (embedding, norms, projections, MLP) run on
        # the chunk's LIVE positions only, packed into the smallest rung of
        # a static ladder that holds them; attention, the commit and the
        # head keep the (B, C) layout. None = this shape does not pack.
        with jax.named_scope("frame_plan"):
            pack = _pack_plan(positions, self.pack_ladder(*ids.shape))
            # a wide step's linear layers run the delta rule on their rows
            # by what each holds (``_rule_by_rows``, or the chip's kernel)
            rows = _row_plan(positions) \
                if cfg.linear_layers and ids.shape[1] > 1 else None

        def embed(ids, positions):
            if tp is not None and tp.vocab_sharded:
                # Megatron vocab-parallel lookup: each shard holds rows
                # [r*V/tp, (r+1)*V/tp) — mask out-of-range ids, psum selects the
                # one shard holding each token's row
                tok = params["embed"]["tok"].astype(dt)
                vs = tok.shape[0]
                off = jax.lax.axis_index(tp.axis) * vs
                lid = jnp.clip(ids - off, 0, vs - 1)
                h = jnp.where(((ids >= off) & (ids < off + vs))[..., None],
                              tok[lid], jnp.zeros((), dt))
                h = tp.coll.psum_embed(h)
            else:
                h = params["embed"]["tok"].astype(dt)[ids]
            if cfg.embed_scale != 1.0:
                h = h * jnp.asarray(cfg.embed_scale, dt)
            if cfg.position == "learned":
                h = h + params["embed"]["pos"].astype(dt)[
                    jnp.clip(positions + cfg.position_offset, 0,
                             params["embed"]["pos"].shape[0] - 1)]
            if cfg.embedding_norm:   # BLOOM word_embeddings_layernorm
                h = L.apply_norm(params["embed"]["emb_norm"], h, cfg)
            return h

        with jax.named_scope("embed"):
            h = _on_live(pack, embed, ids, positions)
        if mtp is not None:
            module = params["mtp"]
            # the first module's norms (their leaves are small; its layer
            # and projection are read in place)
            enorm, hnorm, module_norm = (
                jax.tree.map(lambda a: a[0], module[n])
                for n in ("enorm", "hnorm", "norm"))

            def join(e, hid):
                """``eh_proj`` over the next token's embedding and the
                stack's hidden state, each under its own norm."""
                both = jnp.concatenate(
                    [L.apply_norm(enorm, e, cfg),
                     L.apply_norm(hnorm, hid.astype(dt), cfg)], axis=-1)
                return jnp.einsum("bsf,fe->bse", both,
                                  L.dq(module["eh_proj"], dt)[0])

            with jax.named_scope("mtp_proj"):
                h = _on_live(pack, join, h, mtp[0])
        inv_freq = model._inv_freq
        rope_layers = model._rope_layers   # RoPE that differs by layer
        # positions < 0 mark padding
        is_pad = positions < 0
        pos_safe = jnp.maximum(positions, 0)
        # first chunk position per row: pool slots >= this are stale (the
        # chunk's KV flows beside the pool, committed after the layer walk)
        chunk_start = jnp.min(jnp.where(is_pad, 1 << 30, positions),
                              axis=1).astype(jnp.int32)

        windows = model._layer_windows()   # (L,) for local/global patterns
        uniform_window = None
        if kinds is None and cfg.sliding_window is not None \
                and cfg.local_attention_every is None \
                and cfg.sliding_window < block_tables.shape[1] * bs:
            uniform_window = cfg.sliding_window   # binds within this pool

        slopes = None
        if cfg.position == "alibi":
            slopes = L.alibi_slopes(cfg.num_heads)
            if tp is not None:
                # each shard owns a contiguous head slice — its slopes too
                h_loc = cfg.num_heads // tp.degree
                slopes = jax.lax.dynamic_slice_in_dim(
                    slopes, jax.lax.axis_index(tp.axis) * h_loc, h_loc)

        def at(lp):
            """This layer's weights: the slice the layer walk made, or
            (stacked weights, layer index) sliced HERE, where they are used,
            so that the slice fuses into the product that reads it (a slice
            made outside the region that packs is an operand of that region,
            which XLA materializes: a copy of the layer's weights a step)."""
            if isinstance(lp, tuple):
                return jax.tree.map(lambda a: a[lp[1]], lp[0])
            return lp

        def qkv(lp, l, h, pos):
            lp = at(lp)
            a_in = L.apply_norm(lp["norm1"], h, cfg)
            # L.dq dequantizes int8 per-channel weight leaves in-graph (a
            # cast, like .astype for unquantized leaves — XLA fuses it into
            # the einsum read, so the resident copy stays int8)
            def proj(w):
                w = L.dq(w, dt)
                y = jnp.einsum("bse,ef->bsf", a_in, w.reshape(w.shape[0], -1))
                return y.reshape(y.shape[:2] + w.shape[1:])
            q, k, v = (proj(lp["attn"][n]) for n in ("wq", "wk", "wv"))
            gate = ()
            if cfg.attn_output_gate:
                # q_proj is doubled: a head's query, then its output gate
                d = cfg.dims_per_head
                q, gate = q[..., :d], (q[..., d:],)
            if cfg.use_bias or cfg.qkv_bias:
                q = q + L.bcast(lp["attn"]["bq"].astype(dt), q.ndim)
                k = k + L.bcast(lp["attn"]["bk"].astype(dt), k.ndim)
                v = v + L.bcast(lp["attn"]["bv"].astype(dt), v.ndim)
            if cfg.qk_norm:
                q = L.apply_qk_norm(lp["attn"]["q_norm"], q, cfg)
                k = L.apply_qk_norm(lp["attn"]["k_norm"], k, cfg)
            if cfg.position == "rope":
                freq, factor = inv_freq, None
                if rope_layers is not None:
                    # a row of a small constant table by the layer's index:
                    # no branch, whatever walks the layers
                    freq, factor = rope_layers[0][l], rope_layers[1][l]
                q = L.apply_rope(q, pos, freq, factor=factor,
                                 interleaved=cfg.rope_interleaved)
                k = L.apply_rope(k, pos, freq, factor=factor,
                                 interleaved=cfg.rope_interleaved)
            return (q, k, v) + gate

        def latent_qkv(lp, h, pos):
            """A latent layer's absorbed query and its cached row."""
            lp = at(lp)
            return L.mla_query_and_row(
                lp["attn"], L.apply_norm(lp["norm1"], h, cfg), pos, cfg,
                inv_freq)

        def attn_out(lp, out, gate=None):
            if cfg.latent_lanes:
                return L.mla_output(at(lp)["attn"], out, cfg)
            if gate is not None:
                with jax.named_scope("attn_gate"):
                    out = out * jax.nn.sigmoid(
                        gate.astype(jnp.float32)).astype(out.dtype)
            # row-parallel output projection: under tp the per-shard product
            # covers only the local heads — all-reduce BEFORE the replicated
            # bias, so the bias is added exactly once
            lp = at(lp)
            y = jnp.einsum("bshd,hde->bse", out, L.dq(lp["attn"]["wo"], dt))
            if tp is not None:
                y = tp.coll.psum_attn(y)
            if "bo" in lp["attn"]:   # presence-keyed: out_bias may differ from use_bias
                y = y + L.bcast(lp["attn"]["bo"].astype(dt), y.ndim)
            if cfg.sandwich_norm:   # Gemma-2 post-attn output norm
                y = L.apply_norm(lp["norm3"], y, cfg)
            return y

        def routed_block(stack, experts, m_in, live):
            """The routed block over ``m_in`` and its work in the order of
            the stat vector's expert lanes. ``stack``: the layer as the
            walk handed it; ``experts``: the layer's routed block."""
            at_layer = None
            if isinstance(stack, tuple):
                # the grouped product is a kernel: it takes the stacked
                # experts whole and the layer's index, never a slice
                at_layer = stack[1]
                whole = stack[0]["moe" if cfg.shortcut_moe else "mlp"]
                experts = {**experts, **{n: whole[n]
                                         for n in L.EXPERT_MATRICES}}
            out, _, groups, *picks = L.apply_moe_mlp(
                experts, m_in, cfg, live=live, layer=at_layer)
            work = jnp.stack([
                jnp.sum(groups), jnp.sum(groups > 0), jnp.max(groups),
                L.moe_rows_moved(cfg, groups, m_in.shape[0] * m_in.shape[1]
                                 * cfg.num_experts_per_tok)]).astype(jnp.int32)
            return out, jnp.concatenate([work] + picks) if picks else work

        def mlp(lp, h, y, moe, live=None):
            """The rest of the layer after attention and, in a model with
            routed experts (``routed``), their work in this layer
            (``MOE_STAT_NAMES``): rows sent through experts, experts
            touched, the largest group, and behind them the rows its
            dispatch moved (``MOVED_STAT_NAMES``)."""
            stack, lp = lp, at(lp)
            # a dense model drops this value unread: its traced programs
            # stay the ones they were, lanes and all
            work = jnp.zeros((len(MOE_STAT_NAMES) + (
                len(MOVED_STAT_NAMES) if routed else 0),), jnp.int32)
            if cfg.parallel_block:   # NeoX/Falcon: attn and mlp share input
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            else:
                h = h + y
                m_in = L.apply_norm(lp["norm2"], h, cfg)
            if moe:
                mlp_out, work = routed_block(stack, lp["mlp"], m_in, live)
            else:
                mlp_out = L.apply_mlp(
                    lp["mlp"], m_in, cfg,
                    reduce=tp.coll.psum_mlp if tp is not None else None)
            if cfg.sandwich_norm:
                mlp_out = L.apply_norm(lp["norm4"], mlp_out, cfg)
            h = h + y + mlp_out if cfg.parallel_block else h + mlp_out
            return (h, work) if routed else h

        # int8 pools carry packed scale-lane rows the Pallas kernels don't
        # decode: quantized KV takes the gather attention, where the page
        # rows are unpacked right after the gather, and the commit scatter.
        # Float pools on the chip are read (``paged_ragged_attention``) and
        # written (``kv_commit``) in place, in one layout
        quantized_kv = (kpool if kinds is None else kpool[0]).dtype == jnp.int8
        in_place = _use_pallas_paged() and not quantized_kv
        # KV heads that share a row of a page (``kv_cache.heads_per_row``:
        # heads of 64 lanes, two a 128-lane row, where the engine laid the
        # pool out so for the chip's kernels), read off the pool: 1 for
        # every pool of (kv heads, ..., head_dim) rows
        in_row = 1 if quantized_kv or cfg.latent_lanes else \
            (kpool if kinds is None else kpool[0]).shape[-1] \
            // cfg.dims_per_head
        assert in_place or in_row == 1, "only the chip's kernels read " \
            "heads that share a row of a page"

        def to_rows(x):
            """(B, C, kv heads, D) -> (B, C, kv heads / p, p D): head j p + r
            in lanes [r D, (r + 1) D) of row j, as the pool's pages hold
            them (a reshape)."""
            return x.reshape(x.shape[:2] + (x.shape[2] // in_row, -1))

        def queries_by_row(q):
            """(B, C, H, D) -> (B, C, H, p D): a query of KV head j p + r
            in lanes [r D, (r + 1) D) and zeros in the others, so that its
            product with row j's keys is its own head's score."""
            b_, c_, h_, d_ = q.shape
            eye = jnp.eye(in_row, dtype=q.dtype)
            q = q.reshape(b_, c_, -1, in_row, h_ // cfg.kv_heads, d_)
            return jnp.einsum("bcjrgd,rs->bcjrgsd", q, eye).reshape(
                b_, c_, h_, in_row * d_)

        def values_by_head(o):
            """(B, C, H, p D) -> (B, C, H, D): of a row's values, the lanes
            of the query's own KV head."""
            b_, c_, h_, _ = o.shape
            o = o.reshape(b_, c_, -1, in_row, h_ // cfg.kv_heads, in_row,
                          cfg.dims_per_head)
            return jnp.stack([o[:, :, :, r, :, r] for r in range(in_row)],
                             axis=3).reshape(b_, c_, h_, -1)
        if in_place:
            from ...ops.pallas.kv_commit import kv_commit as commit
        else:
            commit = commit_scatter

        def attend(q, k, v, kp, vp, tables, ring, at_pool, win):
            """The chunk's queries over the row's pages of layer ``at_pool``
            of the pools and over the chunk's own keys. The latent format:
            ``vp`` and ``v`` None, a value is its key's first lanes."""
            rkv = cfg.kv_lora_rank or None
            scale = cfg.attn_scale
            if rkv and scale is None:
                scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            if in_place:
                # decode AND chunked prefill read pages in place (no
                # gather); causal masking, sliding windows (uniform or
                # per-layer traced), ALiBi, and attention softcapping all
                # run in-kernel (the FastGen blocked-flash surface); the
                # kernel indexes (layer, head, page) in the full pool
                from ...ops.pallas.paged_attention import \
                    paged_ragged_attention
                if in_row > 1:
                    # the kernel sees kv heads / p heads of p D lanes
                    return values_by_head(paged_ragged_attention(
                        queries_by_row(q), kp, vp, tables, positions, k, v,
                        layer=at_pool,
                        scale=scale or cfg.dims_per_head ** -0.5, window=win,
                        softcap=cfg.attn_softcap, ring=ring,
                        **({} if see is None else {"visible_to": see})))
                return paged_ragged_attention(
                    q, kp, vp, tables, positions, k, v, layer=at_pool,
                    scale=scale, window=win, alibi_slopes=slopes,
                    softcap=cfg.attn_softcap, ring=ring,
                    **({"value_lanes": rkv} if rkv else {}),
                    **({} if see is None else {"visible_to": see}))
            kvh_loc = kp.shape[1]   # local KV heads (KVH/tp under tp)
            lanes = kp.shape[-1]    # D, or D + scale lanes when int8
            kl = jnp.take(kp, at_pool, axis=0)   # escape hatch: copies 1/L
            if ring is not None:
                # the ring as the table it stands for: logical page
                # p in ring slot p mod R. A slot holds the newest
                # page that maps to it; an older or a later one
                # reads that page under positions the window and
                # the staleness mask kill
                tables = tables[:, jnp.arange(self.max_blocks) % ring]
            kpages = kl[:, tables].reshape(
                kvh_loc, b, -1, lanes).transpose(1, 2, 0, 3)
            if rkv:
                return _paged_attention(
                    q, kpages, kpages[..., :rkv], positions, cfg,
                    chunk_k=k, chunk_v=k[..., :rkv],
                    chunk_start=chunk_start, scale=scale)
            vl = jnp.take(vp, at_pool, axis=0)
            vpages = vl[:, tables].reshape(
                kvh_loc, b, -1, lanes).transpose(1, 2, 0, 3)
            if quantized_kv:
                kpages = dequantize_kv_lanes(kpages, dt)
                vpages = dequantize_kv_lanes(vpages, dt)
            # per-query causal mask via positions: query at position p
            # sees cache slots [0, p]; masks by slot index. The chunk's
            # own k/v ride in raw (pre-quantization) — only pool pages
            # pay the quantize/dequantize round-trip.
            return _paged_attention(q, kpages, vpages, positions, cfg,
                                    window=win, chunk_k=k, chunk_v=v,
                                    chunk_start=chunk_start,
                                    alibi_slopes=slopes, visible_to=see)

        def layer(h, xs, tag=None, cache=None):
            """``cache`` (a model of mixed cache kinds): the layer's kind's
            (k pool, v pool, block tables, ring or None, the layer's index
            within those pools), all static but the index."""
            lp, l, win = xs
            if win is None:
                win = uniform_window
            kp, vp, tables, ring, at_pool = cache or (
                kpool, vpool, block_tables, None, l)
            if cfg.act_quant_bits:   # QAT models serve with quantized acts
                from ...compression.compress import fake_quantize_activation
                h = fake_quantize_activation(h, cfg.act_quant_bits)
            gate = ()       # the output gate, where the model has one
            with jax.named_scope("attn_qkv"):
                if cfg.latent_lanes:
                    (q, k), v = _on_live(
                        pack, functools.partial(latent_qkv, lp), h,
                        pos_safe), None
                else:
                    q, k, v, *gate = _on_live(
                        pack, functools.partial(qkv, lp, l), h, pos_safe)
                    if in_row > 1:
                        k, v = to_rows(k), to_rows(v)
            # the pools are LOOP-INVARIANT inside the layer scan: this
            # layer's chunk KV rides into the attention as separate blocks
            # and comes back out as scan ys; one commit after the walk
            # writes every layer's at once (``_run_layers``). Scanning
            # per-layer pool slices as xs/ys restacks the pools every step.
            with jax.named_scope("paged_attn"):
                out = attend(q, k, v, kp, vp, tables, ring, at_pool, win)
            def dense_out(h, out, *rest):
                # rest: the output gate where the model has one, then the
                # live mask where it routes
                rest = list(rest)
                gate = rest.pop(0) if cfg.attn_output_gate else None
                with jax.named_scope("attn_out"):
                    y = attn_out(lp, out, gate)
                # group tag overrides (a mixer's tag leaves the MLP the
                # config's; a leading dense layer of such a stack comes
                # tagged "dense")
                return mlp(lp, h, y, cfg.is_moe if tag in (None, "full")
                           else tag == "moe", *rest)
            with jax.named_scope("mlp"):
                h = _on_live(pack, dense_out, h, out, *gate,
                             live=~is_pad if routed else None)
                h, *work = h if routed else (h,)
            # quantize-at-append: the chunk's KV leaves the layer already in
            # pool representation, so the commit in _run_layers is
            # dtype-blind and the pool never holds a float row
            with jax.named_scope("kv_commit"):
                if quantized_kv:
                    return h, (quantize_kv_lanes(k), quantize_kv_lanes(v),
                               *work)
                return h, (k.astype(kp.dtype),
                           None if v is None else v.astype(vp.dtype), *work)

        def linear_layer(h, lp, li, state, tail):
            """A layer whose mixer is a Gated DeltaNet: ``state`` / ``tail``
            are every linear layer's (``recurrent``), ``li`` (traced) this
            layer's index among them. The projections, the gated norm, the
            output projection and the MLP treat every position alike and
            run on the live ones; the convolution runs on the (B, C) chunk,
            a row's live positions first, and the rule on the rows by what
            they hold: on the chip a wide step's is ONE kernel over the
            list of rows that hold anything, the states moved in place
            (``gated_delta_rule.gdn_rule_rows``, where ``_rule_kernel_runs``);
            elsewhere ``_rule_by_rows`` (a narrow step's is the recurrence
            on every row, on every backend)."""
            n_live = jnp.sum(~is_pad, axis=1).astype(jnp.int32)
            with jax.named_scope("attn"):
                def project(h):
                    p = at(lp)
                    return L.gdn_project(
                        p["attn"], L.apply_norm(p["norm1"], h, cfg), cfg)
                u, z, b_in, a_in = _on_live(pack, project, h)
                # the mixer's small leaves; its matrices are sliced where
                # they are used
                small = {n: lp[0]["attn"][n][lp[1]]
                         for n in ("conv", "A_log", "dt_bias")}
                tail_l = jnp.moveaxis(
                    jax.lax.dynamic_index_in_dim(tail, li, 0, False), 0, 1)
                # the kernel names the listed rows' states in the stack: no
                # layer is sliced out of it or put back
                kernel = rows is not None and _rule_kernel_runs(
                    cfg, is_pad.shape[1])
                state_l = None if kernel else \
                    jax.lax.dynamic_index_in_dim(state, li, 0, False)
                u, new_tail = L.gdn_conv(small, u, tail_l, n_live, cfg)
                if kernel:
                    with jax.named_scope("gdn_scan"):
                        beta, g = L.gdn_gates(small, b_in, a_in, ~is_pad)
                        out, state = gated_delta_rule.gdn_rule_rows(
                            u, beta, g, state, li, rows[4])
                else:
                    def rule(u, b_in, a_in, pad, state):
                        with jax.named_scope("gdn_scan"):
                            q, k, v = L.gdn_split(u, cfg)
                            beta, g = L.gdn_gates(small, b_in, a_in, ~pad)
                        return L.gdn_rule(q, k, v, beta, g, state)
                    out, new_state = _rule_by_rows(rows, rule, u, b_in, a_in,
                                                   is_pad, state_l)
                # a row that sat the step out keeps both to the bit (the
                # kernel touches no such row's state)
                moved = n_live > 0
                if not kernel:
                    state = jax.lax.dynamic_update_index_in_dim(
                        state, jnp.where(moved[:, None, None, None],
                                         new_state, state_l), li, 0)
                tail = jax.lax.dynamic_update_index_in_dim(
                    tail, jnp.moveaxis(jnp.where(
                        moved[:, None, None], new_tail, tail_l), 1, 0), li, 0)

            def dense_out(h, out, z, *live):
                with jax.named_scope("attn"):
                    y = L.gdn_output(at(lp)["attn"], out, z, cfg)
                return mlp(lp, h, y, cfg.is_moe, *live)
            with jax.named_scope("mlp"):
                h = _on_live(pack, dense_out, h, out, z,
                             live=~is_pad if routed else None)
                h, *work = h if routed else (h,)
            return h, state, tail, work

        def conv_layer(h, lp, li, tail, moe):
            """A layer whose mixer is a gated short convolution: ``tail`` is
            every conv layer's (``recurrent``'s last array), ``li``
            (traced) this layer's index among them, ``moe`` whether its MLP
            routes (a leading dense layer's does not). The projections and
            the MLP treat every position alike and run on the live ones;
            the convolution runs on the (B, C) chunk, a row's live
            positions first (a narrow step: one position a row)."""
            n_live = jnp.sum(~is_pad, axis=1).astype(jnp.int32)
            with jax.named_scope("attn"):
                def project(h):
                    p = at(lp)
                    return L.conv_project(
                        p["attn"], L.apply_norm(p["norm1"], h, cfg), cfg)
                g, gate = _on_live(pack, project, h)
                tail_l = jnp.moveaxis(
                    jax.lax.dynamic_index_in_dim(tail, li, 0, False), 0, 1)
                c, new_tail = L.conv_mix(lp[0]["attn"]["conv"][lp[1]], g,
                                         tail_l, n_live)
                # a row that sat the step out keeps its tail to the bit
                tail = jax.lax.dynamic_update_index_in_dim(
                    tail, jnp.moveaxis(jnp.where(
                        (n_live > 0)[:, None, None], new_tail, tail_l), 1, 0),
                    li, 0)

            def dense_out(h, c, gate, *live):
                with jax.named_scope("attn"):
                    y = L.conv_output(at(lp)["attn"], c, gate, cfg)
                return mlp(lp, h, y, moe, *live)
            with jax.named_scope("mlp"):
                h = _on_live(pack, dense_out, h, c, gate,
                             live=~is_pad if routed else None)
                h, *work = h if routed else (h,)
            return h, tail, work

        def sub(lp, j):
            """Attention, dense MLP and norms ``j`` of a double layer: one
            slice of the stack (a layer's pair sliced first is a copy of
            both)."""
            stack, at_j = (lp[0], (lp[1], j)) if isinstance(lp, tuple) \
                else (lp, j)
            return {n: jax.tree.map(lambda a: a[at_j], stack[n])
                    for n in ("attn", "mlp", "norm1", "norm2")}

        def half_qkv(lp, j, h, pos):
            p = sub(lp, j)
            return L.mla_query_and_row(
                p["attn"], L.apply_norm(p["norm1"], h, cfg), pos, cfg,
                inv_freq)

        def half(lp, j, h, out, s):
            """A double layer's half after its attention: the output
            projection, the dense MLP and, in the first half, the routed
            block, whose output ``s`` joins the stream at the end of the
            second. ``s``: the first half's mask of live positions, the
            second's routed output."""
            p = sub(lp, j)
            with jax.named_scope("attn_out"):
                h = h + L.mla_output(p["attn"], out, cfg)
            m_in = L.apply_norm(p["norm2"], h, cfg)
            if j == 0:
                s, work = routed_block(lp, at(lp)["moe"], m_in, s)
            h = h + L.apply_mlp(p["mlp"], m_in, cfg)
            return ((h, s), work) if j == 0 else h + s

        def double_layer(h, xs, tag=None):
            """A shortcut-connected layer (``cfg.shortcut_moe``): latent
            attention and a dense MLP twice over cache layers 2 l and
            2 l + 1, the routed block beside the second pair."""
            lp, l, _ = xs
            rows, s = [], ~is_pad
            for j in (0, 1):
                with jax.named_scope("attn_qkv"):
                    q, row = _on_live(
                        pack, functools.partial(half_qkv, lp, j), h,
                        pos_safe)
                with jax.named_scope("paged_attn"):
                    out = attend(q, row, None, kpool, None, block_tables,
                                 None, 2 * l + j, None)
                with jax.named_scope("mlp"):
                    if j == 0:
                        (h, s), work = _on_live(
                            pack, functools.partial(half, lp, j), h, out,
                            live=s)
                    else:
                        h = _on_live(pack, functools.partial(half, lp, j), h,
                                     out, s)
                rows.append(row.astype(kpool.dtype))
            with jax.named_scope("kv_commit"):
                return h, (jnp.stack(rows), None, work)

        if cfg.shortcut_moe:
            layer = double_layer
        if mtp is not None:
            # one layer of the stack's last kind over cache layer
            # ``attn_layers``; its row goes back to the caller
            lp = (module["layer"], 0)
            if mtp[1]:
                def row_of(h, pos):
                    p = at(lp)
                    return L.mla_row(p["attn"],
                                     L.apply_norm(p["norm1"], h, cfg), pos,
                                     cfg, inv_freq)
                with jax.named_scope("attn_qkv"):
                    row = _on_live(pack, row_of, h, pos_safe)
                return row.astype(kpool.dtype)[None]
            h, (row, _, *work) = layer(
                h, (lp, cfg.attn_layers, None),
                tag=cfg.layer_type(cfg.num_layers - 1))
            with jax.named_scope("lm_head"):
                h = L.apply_norm(module_norm, h, cfg)
                logits = self._head(params, h, valid_counts, all_logits,
                                    tp=tp)
            return logits, row[None], work[0] if work else None
        if recurrent is not None:
            h, kpool, vpool, work, recurrent = self._run_layers_recurrent(
                layer, {"linear": linear_layer, "conv": conv_layer}, h,
                params, kpool, vpool, block_tables,
                functools.partial(commit, block_tables=block_tables,
                                  positions=positions), recurrent)
        elif kinds is None:
            h, kpool, vpool, work = self._run_layers(
                layer, h, params, kpool, vpool, windows,
                functools.partial(commit, block_tables=block_tables,
                                  positions=positions),
                stacked=pack is not None or self.experts_from_stack)
        else:
            h, kpool, vpool, work = self._run_layers_by_kind(
                layer, h, params, kpool, vpool, block_tables,
                functools.partial(commit, positions=positions))
        with jax.named_scope("lm_head"):
            stack_h = h
            if cfg.block_length:
                # the head on a block's rows a slot, never on a chunk's
                all_logits = True
                if head_at is None:
                    head_at = jnp.zeros(h.shape[:1], jnp.int32)
                h = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
                    row, at, blk))(h, head_at)
            h = L.apply_norm(params["final_norm"], h, cfg)
            logits = self._head(params, h, valid_counts, all_logits, tp=tp)
        return (logits, kpool, vpool) + ((work,) if moe_work else ()) \
            + ((stack_h,) if hidden else ()) \
            + (() if recurrent is None else (recurrent,))

    def _run_layers(self, layer, h, params, kpool, vpool, windows, commit,
                    stacked=False):
        """Drive ``layer`` over the stack following the model's layer plan
        (heterogeneous stacks: Qwen2-MoE sparse steps, mlp_only prefixes).
        The full pools stay loop-invariant (read through a global layer
        index, never a materialized per-layer slice); each layer's chunk KV
        returns as scan ys and ``commit(kpool, vpool, chunk_k, chunk_v)``
        writes them all at once after the walk: for float pools on the chip
        the ``kv_commit`` kernel, which writes the pages the live positions
        land on in the layout the paged kernel reads; otherwise
        ``commit_scatter``, for which XLA on the chip relays both whole
        pools (two copies of a pool a step and a second pool of
        temporaries; PERF.md, PR 27).
        A third ys, the layers' routed experts' work, comes back summed
        (None where the layers give none).
        Per-layer xs are (layer index, window), which the shared
        ``walk_layer_plan`` driver slices to match the grouped param layout
        exactly like the train forward and the cached decode.

        ``stacked`` (a step that packs its live tokens): the walk is handed
        each group's layer INDICES in place of its weights, under the
        group's own key, and ``layer`` gets (the group's stacked weights,
        index) to slice where it uses them."""
        from ...models.transformer import walk_layer_plan
        model = self.model
        layer_ids = jnp.arange(self.cfg.num_layers, dtype=jnp.int32)
        layers = params["layers"]
        walked = layers
        if stacked:
            def indices(key, tree):
                return {key: jnp.arange(jax.tree.leaves(tree)[0].shape[0])}
            walked = (indices("", layers) if model._groups is None else
                      {g: indices(g, tree) for g, tree in layers.items()})

        def body(h, lp, xs_t, tag):
            l, win = xs_t
            if stacked:
                (key, i), = lp.items()
                lp = (layers[key] if key else layers, i)
            return layer(h, (lp, l, win), tag=tag)

        h, (ck_all, cv_all, *work) = walk_layer_plan(
            model._plan, model._groups, walked,
            (layer_ids, windows), h, body)
        if self.cfg.shortcut_moe:
            # (layers, 2, ...) -> the attention layers' rows, in order
            ck_all = ck_all.reshape((-1,) + ck_all.shape[2:])
        with jax.named_scope("kv_commit"):
            kpool, vpool = commit(kpool, vpool, ck_all, cv_all)
        return h, kpool, vpool, jnp.sum(work[0], axis=0) if work else None

    def _run_layers_recurrent(self, layer, mixers, h, params, kpool, vpool,
                              tables, commit, recurrent):
        """``_run_layers`` for a stack that mixes linear or conv layers
        with full attention layers (``cfg.mixer_pattern``): a scan over the
        pattern's PERIODS with a period's layers unrolled in its body, so
        that which mixer a layer has is static (a pattern as long as the
        stack: one period, the whole stack unrolled; only such a stack may
        begin with ``cfg.moe_first_dense`` dense layers, whose MLP is then
        static too). A period's layer j has its weights in group ``g{j}``
        (``layer_groups``), sliced where they are used; a full layer reads
        its index among the full layers of the one pool and its chunk KV
        comes back as scan ys for ONE commit after the walk; a linear layer
        (``mixers["linear"]``) reads and writes its index among the linear
        layers of ``recurrent``'s (state, tail), a conv layer
        (``mixers["conv"]``) its index among the conv layers of
        ``recurrent``'s last array, its tail: ``recurrent`` holds what the
        kinds present keep (``recurrent_shapes``), rides the scan's carry
        and is updated in place. Returns (h, kpool, vpool, the routed
        experts' work summed or None, recurrent)."""
        cfg = self.cfg
        pattern = tuple(cfg.mixer_pattern)
        p, n = len(pattern), cfg.num_layers
        per = {kind: pattern.count(kind) for kind in set(pattern)}
        rank = [pattern[:j].count(pattern[j]) for j in range(p)]
        layers = params["layers"]

        def period(carry, t):
            h, *kept = carry
            ys, work = [], []
            for j, kind in enumerate(pattern):
                lp = (layers[f"g{j}"], t)
                at_kind = t * per[kind] + rank[j]
                # (static: leading dense layers only in a one-period stack)
                moe = cfg.is_moe and j >= cfg.moe_first_dense
                if kind == "linear":
                    h, kept[0], kept[1], w = mixers[kind](
                        h, lp, at_kind, kept[0], kept[1])
                elif kind == "conv":
                    h, kept[-1], w = mixers[kind](h, lp, at_kind, kept[-1],
                                                  moe)
                else:
                    h, (k, v, *w) = layer(
                        h, (lp, t * p + j, None),
                        tag="full" if moe or not cfg.is_moe else "dense",
                        cache=(kpool, vpool, tables, None, at_kind))
                    ys.append((k, v))
                work += w
            return (h, *kept), (
                jax.tree.map(lambda *z: jnp.stack(z), *ys),
                sum(work) if work else None)

        (h, *recurrent), ((ck, cv), work) = jax.lax.scan(
            period, (h, *recurrent), jnp.arange(n // p, dtype=jnp.int32))
        # (periods, full layers a period, ...) -> the full layers, in order
        ck, cv = (a.reshape((-1,) + a.shape[2:]) for a in (ck, cv))
        with jax.named_scope("kv_commit"):
            kpool, vpool = commit(kpool, vpool, ck, cv)
        return (h, kpool, vpool,
                None if work is None else jnp.sum(work, axis=0),
                tuple(recurrent))

    def _run_layers_by_kind(self, layer, h, params, kpools, vpools, tables,
                            commit):
        """``_run_layers`` for a stack of mixed cache kinds: every layer
        reads its kind's pools through its kind's table and its index
        within them, and one commit a kind writes that kind's chunk KV
        after the walk. Which kind a layer is must be static (a pool picked
        by a traced branch is an operand of a conditional, which XLA
        materializes), so the walk is a scan over the PERIODS of the
        kinds' pattern with a period's layers unrolled in its body (Mellum2:
        three windowed layers and a global one; a pattern without a period
        is one period). Layers are walked by index and their weights sliced
        where they are used, as a step that packs does."""
        kinds = self.kinds
        n = self.cfg.num_layers
        kind_of = [ki for _, ki in sorted(
            (l, ki) for ki, kind in enumerate(kinds) for l in kind.layers)]
        p = next(p for p in range(1, n + 1) if n % p == 0 and all(
            kind_of[i] == kind_of[i % p] for i in range(n)))
        # a period's layers of each kind, and where each stands among them
        per = [kind_of[:p].count(ki) for ki in range(len(kinds))]
        rank = [kind_of[:j].count(kind_of[j]) for j in range(p)]
        layers = params["layers"]

        def period(h, t):
            ys = [[] for _ in kinds]
            for j in range(p):
                l, ki = t * p + j, kind_of[j]
                kind = kinds[ki]
                h, y = layer(h, ((layers, l), l, kind.window), cache=(
                    kpools[ki], vpools[ki], tables[ki], kind.ring,
                    t * per[ki] + rank[j]))
                ys[ki].append(y)
            return h, tuple(jax.tree.map(lambda *z: jnp.stack(z), *y)
                            for y in ys)

        h, ys = jax.lax.scan(period, h, jnp.arange(n // p, dtype=jnp.int32))
        # (periods, layers of the kind a period, ...) -> the kind's layers
        ys = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), ys)
        kout, vout, work = [], [], []
        with jax.named_scope("kv_commit"):
            for ki, kind in enumerate(kinds):
                ck, cv, *w = ys[ki]
                k, v = commit(kpools[ki], vpools[ki], ck, cv,
                              block_tables=tables[ki], ring=kind.ring)
                kout.append(k)
                vout.append(v)
                work += [jnp.sum(w[0], axis=0)] if w else []
        return h, tuple(kout), tuple(vout), sum(work) if work else None

    def _head(self, params, h, valid_counts, all_logits=False, tp=None):
        """Last-valid-token logits (B, V) from normed hidden states — or
        per-position logits (B, C, V) when ``all_logits`` (the speculative
        verify needs the target's distribution at every drafted slot).

        Under a vocab-sharded ``tp`` the local product is this shard's
        (…, V/tp) logit columns; bias and softcap are elementwise, so they
        apply shard-local, and ONE all-gather (the per-step logit exchange —
        int8-quantizable, see ``parallel/collectives.py``) assembles the
        full vocab every consumer downstream (argmax, sampling, speculative
        verify) sees replicated."""
        cfg = self.cfg
        dt = cfg.act_dtype
        if all_logits:
            h_last = h                                   # (B, C, E)
            eq_tied, eq_untied = "bce,ve->bcv", "bce,ev->bcv"
        else:
            # last valid token of each chunk
            last_idx = jnp.maximum(valid_counts - 1, 0)
            h_last = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]
            eq_tied, eq_untied = "be,ve->bv", "be,ev->bv"
        if cfg.tie_embeddings:
            logits = jnp.einsum(eq_tied, h_last, params["embed"]["tok"].astype(dt))
        else:
            logits = jnp.einsum(eq_untied, h_last,
                                L.dq(params["embed"]["lm_head"], dt))
        if "lm_head_bias" in params["embed"]:
            logits = logits + L.bcast(
                params["embed"]["lm_head_bias"].astype(logits.dtype),
                logits.ndim)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        if tp is not None and tp.vocab_sharded:
            logits = tp.coll.gather_logits(logits)
        return logits.astype(jnp.float32)

    def _tp_call(self, core, args, carry_specs, out_specs):
        """Run ``core`` under shard_map on the tp mesh (``self.tp``):
        ``carry_specs``/``out_specs`` are flat tuples of PartitionSpecs for
        the array args after the param tree(s); param trees shard per the
        context's spec tree. check_vma is off — replication of the
        unmapped outputs is by construction (every carry input is
        replicated and every shard-varying intermediate passes through a
        psum/all-gather before reaching them); every device keeps its own
        copy of the stats vector, so ``DeviceSlotTable.stats_delta`` can
        ASSERT that construction in debug mode instead of trusting it."""
        tp = self.tp
        return jax.shard_map(core, mesh=tp.mesh, in_specs=carry_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    def _build_frame_loop(self):
        tp = self.tp
        fwd = functools.partial(self._forward, tp=tp)

        @functools.partial(jax.jit,
                           donate_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15,
                                           16, 17, 18, 19),
                           static_argnames=("width", "steps", "greedy",
                                            "repair"))
        def loop(params, prompts, prompt_lens, limits, eos_ids, temps, tables,
                 cached, produced, last_tok, done, poison, nonfinite, stats,
                 rng, kpool, vpool, hidden=None, recurrent=None, block=None,
                 *, width, steps, greedy, repair=False, n_steps=None):
            """One K-step serving FRAME, the one program that generates
            tokens. All per-slot state is carry-IN/carry-OUT, so the
            host only touches the loop at frame boundaries (admit arrivals,
            retire finished rows); between frames the state — last token,
            cached-token counts, per-row limits, EOS/temperature vectors,
            RNG — never leaves the device.

            Slot semantics per step: a row with ``cached < prompt_lens``
            prefills (consumes up to ``width`` prompt tokens); a row past its
            prompt with ``produced < limits`` decodes one token; rows with
            ``done`` set (in-graph EOS) or at their limit freeze — their
            positions go to -1, which the pager routes to the trash block.
            Free slots are rows with ``done=True, limits=0``.

            ``steps`` (static) is the frame's capacity, the rows of its
            emissions; ``n_steps`` (an int32 scalar OPERAND, neither static
            nor donated; ``steps`` where it is left out) is how many of them
            this frame runs. The serve loop plans it at each boundary from
            what its rows have left to do, so one program a (width, steps,
            greedy, repair) runs every frame length.

            Returns (tokens (steps, B), emit (steps, B), new carry...): rows
            ``[:n_steps]`` are the steps that ran, the rest -1 / not emitted.
            All carry arrays + pools are donated: the frame updates them in
            place and the outputs ARE the next frame's inputs. ``stats`` is
            the (N_STATS,) in-graph telemetry accumulator — monotonically
            increasing device counters that surface only at frame
            boundaries (see ``telemetry.py``). ``poison``/``nonfinite``
            (B,) bools are the fault-injection flag and the per-row
            finite-check latch (``faults.py``): both ride the donated
            carry, so arming a fault or detecting a NaN never retraces.

            ``hidden`` (B, E), given: the model drafts for itself with its
            prediction module (``cfg.num_nextn_predict_layers``). It is the
            stack's hidden state at position ``cached - 1`` and rides the
            carry behind ``last_tok`` (returned there too); a width-1 frame
            drafts one token a row with the module and verifies two
            positions with the stack, and emissions are (steps, B, 2)
            (``_self_spec_scan_body``). The module's cache is one more layer
            of the same pool: no argument more.

            ``recurrent``, given: the model has mixers that keep a state a
            slot (``recurrent_shapes``: linear (Gated DeltaNet) layers'
            (state, tail), conv layers' (tail,)). The tuple is the
            carry's LAST field, donated like the pools, and comes back last:
            it rides through the frame's steps and from frame to frame.

            ``block`` = (tokens (B, L) int32, masked (B, L) bool), given: the
            model generates by diffusion over blocks of L positions
            (``cfg.block_length``). The pair is the carry's LAST field as
            ``recurrent`` is another model's, donated and returned last; the
            body is ``_block_scan_body``, a narrow frame is ``width`` = 2 L
            (``narrow_width``), and emissions are (steps, B, L): a row's
            step emits a committed block's tokens or none.

            Tensor-parallel (``self.tp`` set): the same program compiles
            under shard_map on the 1-D tp mesh — params and KV pools
            sharded, every slot-state carry replicated, ``stats`` among
            them (each shard accumulates its own replica-consistent copy;
            the boundary reads one).
            """
            def core(n_steps, params, prompts, prompt_lens, limits, eos_ids,
                     temps, tables, cached, produced, last_tok, done, poison,
                     nonfinite, stats, rng, kpool, vpool, *hidden,
                     recurrent=None, block=None):
                if block is not None:
                    body = _block_scan_body(
                        fwd, params, prompts, prompt_lens, limits, eos_ids,
                        temps, tables, width, greedy, self.cfg,
                        window=self.stat_window, ladder=self.pack_ladder,
                        heads=self.row_heads)
                    carry = (cached, produced, last_tok, done, poison,
                             nonfinite, stats, rng, kpool, vpool,
                             tuple(block))
                    return _run_steps(body, carry, steps, n_steps,
                                      cached.shape + (self.block_length,))
                body = _serving_scan_body(fwd, params, prompts, prompt_lens,
                                          limits, eos_ids, temps, tables,
                                          width, greedy,
                                          draft="self" if hidden else None,
                                          repair=repair,
                                          window=self.stat_window,
                                          ladder=self.pack_ladder,
                                          layers=self.layer_work,
                                          latent=self.latent_layers,
                                          heads=self.row_heads,
                                          mtp=self.has_mtp,
                                          linear=self.linear_layers,
                                          kernel_rule=_rule_kernel_runs(
                                              self.cfg, width),
                                          conv=self.conv_layers)
                carry = (cached, produced, last_tok, *hidden, done, poison,
                         nonfinite, stats, rng, kpool, vpool) \
                    + (() if recurrent is None else (tuple(recurrent),))
                return _run_steps(body, carry, steps, n_steps,
                                  cached.shape + ((2,) if hidden else ()))

            args = (_trip_count(n_steps, steps), params, prompts, prompt_lens,
                    limits, eos_ids, temps, tables, cached, produced,
                    last_tok, done, poison, nonfinite, stats, rng, kpool,
                    vpool)
            if tp is None:
                return core(*args, *(() if hidden is None else (hidden,)),
                            recurrent=recurrent, block=block)
            assert hidden is None and recurrent is None and block is None, \
                "neither a self-draft, a recurrent state nor a half-denoised " \
                "block is served under tp"
            rep, kv = P(), tp.kv_spec
            return self._tp_call(
                core, args,
                (rep, tp.param_specs) + (rep,) * 14 + (kv, kv),
                (rep,) * 10 + (kv, kv))

        return loop

    def frame_loop(self, *args, **kwargs):
        if "frame" not in self._fns:
            self._fns["frame"] = self._build_frame_loop()
        return self._fns["frame"](*args, **kwargs)

    def _build_frame_loop_spec(self, draft_runner):
        tp = self.tp
        fwd = functools.partial(self._forward, tp=tp)
        draft_fwd = functools.partial(draft_runner._forward,
                                      tp=draft_runner.tp)

        @functools.partial(jax.jit,
                           donate_argnums=(8, 9, 10, 11, 12, 13, 14, 15, 16,
                                           17, 18, 19, 20),
                           static_argnames=("width", "steps", "greedy", "gamma",
                                            "repair"))
        def loop(params, draft_params, prompts, prompt_lens, limits, eos_ids,
                 temps, tables, cached, produced, last_tok, penult, done,
                 poison, nonfinite, stats, rng, kpool, vpool, dkpool, dvpool,
                 width, steps, greedy, gamma, repair=False, n_steps=None):
            """Speculative K-step serving frame: ``frame_loop`` with a second
            model riding the carry. Wide (prefill) frames run the target body
            unchanged while the draft ingests the same chunks (its paged KV
            pools ``dkpool``/``dvpool`` share the target's block tables);
            pure-decode frames (width 1) run gamma draft proposals + ONE
            gamma+1-wide target verify per step, with per-row acceptance and
            rollback as in-graph selects (``_serving_scan_body``) — the host
            still touches the loop only at frame boundaries.

            Returns (tokens (steps, B, gamma+1), emit (steps, B, gamma+1),
            new carry...), ``n_steps`` of them run as in ``frame_loop``.
            ``penult`` is the token at position ``cached - 1``
            per row; the first draft step of each speculative step re-feeds
            it so the draft cache self-heals after a fully-accepted step
            without a separate catch-up forward."""
            def core(n_steps, params, draft_params, prompts, prompt_lens,
                     limits, eos_ids, temps, tables, cached, produced,
                     last_tok, penult, done, poison, nonfinite, stats, rng,
                     kpool, vpool, dkpool, dvpool):
                body = _serving_scan_body(
                    fwd, params, prompts, prompt_lens, limits, eos_ids,
                    temps, tables, width, greedy,
                    draft=(draft_fwd, draft_params, gamma), repair=repair,
                    window=self.stat_window,
                    ladder=self.pack_ladder, layers=self.layer_work,
                    latent=self.latent_layers, heads=self.row_heads)
                carry = (cached, produced, last_tok, penult, done, poison,
                         nonfinite, stats, rng, kpool, vpool, dkpool, dvpool)
                return _run_steps(body, carry, steps, n_steps,
                                  cached.shape + (gamma + 1,))

            args = (_trip_count(n_steps, steps), params, draft_params,
                    prompts, prompt_lens, limits, eos_ids, temps, tables,
                    cached, produced, last_tok,
                    penult, done, poison, nonfinite, stats, rng, kpool,
                    vpool, dkpool, dvpool)
            if tp is None:
                return core(*args)
            rep, kv = P(), tp.kv_spec
            return self._tp_call(
                core, args,
                (rep, tp.param_specs, draft_runner.tp.param_specs)
                + (rep,) * 15 + (kv, kv, kv, kv),
                (rep,) * 11 + (kv, kv, kv, kv))

        return loop

    def frame_loop_spec(self, draft_runner, *args, **kwargs):
        if "spec_frame" not in self._fns:
            self._fns["spec_frame"] = self._build_frame_loop_spec(draft_runner)
        return self._fns["spec_frame"](*args, **kwargs)

    def run(self, chunk: int, *args):
        if self.block_length:
            raise NotImplementedError(
                "a model that generates by diffusion over blocks holds a "
                "half-denoised block a slot, which rides the frame programs' "
                "carry: it is served by serve() and generate(), not by "
                "put() / step()")
        if chunk not in self._fns:
            self._fns[chunk] = self._build(chunk)
        return self._fns[chunk](*args)

    def compile_count(self) -> dict:
        """Compiled-executable count PER entry point: each jitted wrapper
        retraces per distinct arg shape/static combo, so these are the real
        program counts (the recompile-budget tests pin the function that
        recompiled instead of asserting one aggregate). Keys: "frame",
        "spec_frame" and "chunk<W>" for the per-chunk ``run`` programs;
        ``sum(compile_count().values())`` is the old aggregate."""
        return {(f"chunk{k}" if isinstance(k, int) else str(k)): f._cache_size()
                for k, f in self._fns.items() if hasattr(f, "_cache_size")}

    def compile_count_total(self) -> int:
        """MONOTONIC total of compiled programs (recompiles are the #1
        silent perf cliff — this is the number to alarm on). Unlike
        ``sum(compile_count().values())`` it never decreases when an entry
        point is evicted (``evict``); ``reset_compile_count`` rebases it to
        zero so a caller can count recompiles per serving window."""
        cur = self._evicted_programs + sum(
            f._cache_size() for f in self._fns.values()
            if hasattr(f, "_cache_size"))
        return cur - self._compile_base

    def reset_compile_count(self) -> None:
        """Rebase ``compile_count_total`` to zero (per-window counting)."""
        self._compile_base = self._evicted_programs + sum(
            f._cache_size() for f in self._fns.values()
            if hasattr(f, "_cache_size"))

    def evict(self, *names) -> None:
        """Drop entry points (a draft re-attach must evict the spec loops
        that closed over the old draft), folding their program counts into
        the monotonic total first."""
        for name in names:
            f = self._fns.pop(name, None)
            if f is not None and hasattr(f, "_cache_size"):
                self._evicted_programs += f._cache_size()


def _trip_count(n_steps, steps):
    """A frame's ``n_steps`` operand as an int32 scalar no larger than its
    capacity ``steps``; the capacity itself where the caller named none."""
    if n_steps is None:
        return jnp.asarray(steps, jnp.int32)
    return jnp.minimum(jnp.asarray(n_steps, jnp.int32), steps)


def _run_steps(body, carry, steps, n_steps, shape):
    """``n_steps`` (a traced int32 scalar, at most the static ``steps``)
    trips of a serving frame's ``body`` (``_serving_scan_body``'s): what
    ``lax.scan(body, carry, None, length=n_steps)`` would return, as
    (tokens, emit, *carry), with the emissions (``shape`` a step) in buffers
    of ``steps`` rows whose rows past ``n_steps`` stay -1 / not emitted. The
    trip count is an operand, so one program runs every frame length the
    serve loop plans; the carry (pools included) is updated in place as a
    scan's is."""
    toks = jnp.full((steps,) + shape, -1, jnp.int32)
    emit = jnp.zeros((steps,) + shape, bool)

    def step(state):
        i, carry, toks, emit = state
        carry, (t, e) = body(carry, None)
        return i + 1, carry, toks.at[i].set(t), emit.at[i].set(e)

    _, carry, toks, emit = jax.lax.while_loop(
        lambda state: state[0] < n_steps, step,
        (jnp.zeros((), jnp.int32), carry, toks, emit))
    return (toks, emit) + carry


def commit_scatter(kpool, vpool, chunk_k, chunk_v, block_tables, positions,
                   ring=None, layer0=0):
    """The chunk's (L, B, C, KVH, D) KV into the (L, KVH, NB, bs, D) pools
    at ``positions`` (B, C) through the rows' block tables, as one XLA
    scatter a pool; a pad (``positions < 0``) goes to trash page 0.
    ``ring``: the (B, ring) tables are rings (``kv_commit``'s); ``layer0``:
    a chunk of fewer layers than the pools lands from that layer on."""
    bs = kpool.shape[3]
    is_pad = positions < 0
    pos_safe = jnp.maximum(positions, 0)
    page = pos_safe // bs
    if ring is not None:
        page = page % ring
    blk = jnp.where(is_pad, 0, jnp.take_along_axis(
        block_tables, page, axis=1))                        # (B, C)
    off = pos_safe % bs
    # the advanced (B, C) indices are contiguous, so the indexed window is
    # (L, KVH, B, C, D); the latent format has one pool
    lyr = slice(None) if chunk_k.shape[0] == kpool.shape[0] \
        else slice(layer0, layer0 + chunk_k.shape[0])
    return (kpool.at[lyr, :, blk, off].set(chunk_k.transpose(0, 3, 1, 2, 4)),
            None if vpool is None else
            vpool.at[lyr, :, blk, off].set(chunk_v.transpose(0, 3, 1, 2, 4)))


def _rung_of(positions, ladder):
    """Index of the smallest rung of ``ladder`` that holds the chunk's live
    positions (``positions >= 0``)."""
    n = jnp.sum((positions >= 0).astype(jnp.int32))
    return sum((n > t).astype(jnp.int32) for t in ladder[:-1])


def _pack_plan(positions, ladder):
    """Where the live positions of a chunk (``positions >= 0``) sit in a
    packed token buffer, and the rung of ``ladder`` that holds them: returns
    (rung index, src (T,) flat chunk position of each packed token, dst
    (B, C) packed index of each chunk position, live (B, C), ladder), or
    None when the shape has one rung only. Live positions keep their order,
    so the indices are a cumulative sum and its inverse: no sort."""
    if len(ladder) == 1:
        return None
    live = positions >= 0
    cum = jnp.cumsum(live.reshape(-1).astype(jnp.int32))
    # packed token t is the first chunk position with t + 1 live ones up to
    # it; past the live count that points beyond the chunk, and is clipped
    src = jnp.minimum(
        jnp.searchsorted(cum, jnp.arange(ladder[-1], dtype=jnp.int32),
                         side="right", method="compare_all"),
        live.size - 1)
    dst = jnp.maximum(cum - 1, 0).reshape(live.shape)
    return _rung_of(positions, ladder), src, dst, live, ladder


def _on_live(pack, fn, *xs, live=None):
    """``fn(*xs)`` for a ``fn`` that treats every position alike (its
    output at a position depends on that position's inputs only), computed
    on the live positions alone: a rung gathers its ``T`` packed tokens
    from the (B, C, ...) inputs as a (1, T, ...) view, runs ``fn`` there and
    gathers the outputs back to (B, C, ...) with zeros at the dead
    positions. The last rung holds the whole chunk and takes the same path,
    so the rungs differ in ``T`` alone and ask one layout of the weights
    they share. The rung was chosen in the graph (``_pack_plan``), so one
    program serves every live count. ``pack`` None is ``fn(*xs)``.

    ``live``, the chunk's (B, C) mask of live positions, is for a ``fn``
    that has to know them (a dead position may reach no routed expert): it
    is then handed the mask as its last argument, in the layout it runs on
    (a rung's padding past the live count is dead), and returns (ys,
    extra); ``extra``, whose shape is no rung's business, comes back as it
    is."""
    if pack is None:
        return fn(*xs) if live is None else fn(*xs, live)
    rung, src, dst, is_live, ladder = pack

    def on_rung(t):
        def run(*xs):
            packed = [x.reshape((1, -1) + x.shape[2:])[:, src[:t]]
                      for x in xs]
            if live is None:
                ys, extra = fn(*packed), None
            else:
                ys, extra = fn(*packed, (jnp.arange(t) < jnp.sum(is_live))[None])
            single = not isinstance(ys, tuple)
            ys = tuple(
                jnp.where(is_live.reshape(is_live.shape
                                          + (1,) * (y.ndim - 2)),
                          y[0][jnp.minimum(dst, t - 1)],
                          jnp.zeros((), y.dtype))
                for y in ((ys,) if single else ys))
            ys = ys[0] if single else ys
            return ys if live is None else (ys, extra)
        return run

    return jax.lax.switch(rung, [on_rung(t) for t in ladder], *xs)


#: rows the chunked delta rule takes a trip of a wide step's loop
#: (``_rule_by_rows``), where the step has as many
RULE_ROWS = 2


def _rule_trips(n_live):
    """(Trips of ``_rule_by_rows``' loop in a step whose rows hold
    ``n_live`` (B,) live positions, rows a trip): the rows that hold more
    than one, ``RULE_ROWS`` a trip."""
    rows = min(RULE_ROWS, n_live.shape[0])
    n = jnp.sum((n_live > 1).astype(jnp.int32))
    return (n + rows - 1) // rows, rows


def _rule_kernel_runs(cfg, width) -> bool:
    """Whether the delta rule of a step ``width`` wide is the repo's kernel
    (``ops/pallas/gated_delta_rule.py``): a wide step's of a model with
    linear layers, on the chip, as the paged kernels are chosen
    (``_use_pallas_paged``), at the shapes the kernel takes. A narrow step
    and every other backend keep ``_rule_by_rows``."""
    return bool(cfg.linear_layers) and width > 1 and _use_pallas_paged() \
        and gated_delta_rule.supported(
            width, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim)


def _rule_positions(n_live, width, kernel=False):
    """Positions ONE linear layer's delta rule computes in a step whose
    rows hold ``n_live`` (B,) live positions of ``width``. By
    ``_rule_by_rows``: a trip's rows x ``width`` a trip of the chunked
    form, and every row through the recurrence. By the chip's ``kernel``
    (``_rule_kernel_runs``): one for a row that holds one, a prefilling
    row's live blocks of ``GDN_CHUNK``, none for a row that sits out."""
    b = n_live.shape[0]
    if width == 1:
        return jnp.asarray(b, jnp.int32)
    if kernel:
        blocks = -(-n_live // L.GDN_CHUNK) * L.GDN_CHUNK
        return jnp.sum(jnp.where(n_live > 1, blocks, n_live)).astype(
            jnp.int32)
    trips, rows = _rule_trips(n_live)
    return trips * rows * width + b


def _row_plan(positions):
    """Which rows of a wide chunk hold what (``positions >= 0`` live). For
    ``_rule_by_rows``, the rows that hold more than one live position,
    packed in slot order: trips of the chunked form's loop, rows a trip,
    src the row of each packed row, B past their count and one entry for
    every row a trip may take, rider (B,) the rows that hold exactly one.
    As ``_pack_plan`` does for tokens: a cumulative sum and its inverse, no
    sort. For the chip's kernel, last, the list of every row that holds
    one or more, with its count (``gated_delta_rule.row_list``). What a
    program does not use, its compiler drops."""
    n_live = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)
    chunked = n_live > 1
    trips, rows = _rule_trips(n_live)
    src = jnp.searchsorted(
        jnp.cumsum(chunked.astype(jnp.int32)),
        jnp.arange(chunked.size + rows - 1, dtype=jnp.int32),
        side="right", method="compare_all")
    return trips, rows, src, n_live == 1, gated_delta_rule.row_list(n_live)


def _rule_by_rows(plan, rule, u, b_in, a_in, pad, state):
    """The gated delta rule of one linear layer's step, ``rule(u, b_in,
    a_in, pad, state) -> (out, new state)`` over rows (B, C, ...) and
    their states (B, H, dk, dv), computed for each row by what it holds
    (``plan``, ``_row_plan``; None: a narrow step, ``rule`` itself, the
    recurrence on every row). The path of every backend but the chip, of
    the chip at the shapes its kernel does not take (``_rule_kernel_runs``),
    and the kernel's oracle (``tests/test_gated_delta_rule_kernel.py``):

    * one live position (a decoding row riding the step, a prompt's last
      token): the recurrence on position 0, run on all B rows at once (what
      a narrow step runs) and kept for these;
    * more (a prefilling row): the chunked form, ``RULE_ROWS`` of those
      rows gathered a trip of ONE loop body whose trip count is computed in
      the graph (as the frame program's step count is an operand): no trip
      where no row prefills, B / ``RULE_ROWS`` where every row does. A last
      trip's row past the count computes a clipped row and is written
      nowhere;
    * none: the state it had.

    Returns out (B, C, H, dv), zeros at the rows that sat the step out and
    behind a rider's one position, and the new states."""
    if plan is None:
        return rule(u, b_in, a_in, pad, state)
    trips, take, src, rider = plan[:4]
    xs = (u, b_in, a_in, pad)
    with jax.named_scope("gdn_scan"):
        out1, state1 = rule(*(x[:, :1] for x in xs), state)
        rider = rider[:, None, None, None]
        rides = rider & (jnp.arange(pad.shape[1]) == 0)[None, :, None, None]

        def trip(i, carry):
            out, new = carry
            # past the count ``src`` names row B: read clipped, dropped
            # by the writes
            to = jax.lax.dynamic_slice_in_dim(src, i * take, take)
            rows = jnp.minimum(to, pad.shape[0] - 1)
            o, s = rule(*(x[rows] for x in xs), state[rows])
            return (out.at[to].set(o, mode="drop"),
                    new.at[to].set(s, mode="drop"))

        return jax.lax.fori_loop(
            0, trips, trip, (jnp.where(rides, out1, 0.0),
                             jnp.where(rider, state1, state)))


def _serving_scan_body(fwd, params, prompts, prompt_lens, limits, eos_ids,
                       temps, tables, width, greedy, draft=None,
                       repair=False, window=None, ladder=pack_ladder,
                       layers=None, latent=None, heads=None, mtp=False,
                       linear=0, kernel_rule=False, conv=0):
    """The scan-step of ``frame_loop`` and ``frame_loop_spec``: the in-graph
    SplitFuse scheduling arithmetic lives in exactly one place.

    Carry: (cached, produced, last_tok, done, poison, nonfinite, stats, rng,
    kpool, vpool) — ``poison`` is the fault-injection flag (NaNs the row's
    logits when set, see ``_inject_poison``) and ``nonfinite`` the per-row
    finite-check latch (``_finite_check``), both read/reset only at frame
    boundaries. Per step, a
    row with ``cached < prompt_lens`` prefills (consumes up to ``width``
    prompt tokens); a row past its prompt with ``produced < limits`` decodes
    one token; ``done`` rows (in-graph EOS) and rows at their limit freeze —
    width 0, positions -1, which the pager routes to the trash block.
    ``eos_ids``/``temps`` are per-row; pass eos_ids = -1 for "no EOS" (token
    ids are never negative) and uniform temps for scalar-temperature callers.
    Emits (token-or--1, emit-mask) per step. The carry's ``stats`` vector
    (``telemetry.N_STATS``) accumulates the in-graph frame counters — a few
    scalar reductions per step, surfaced only at frame boundaries.

    ``draft=(draft_fwd, draft_params, gamma)`` enables speculative decoding:
    the carry grows (penult, dkpool, dvpool) — inserted after ``last_tok``
    and after ``vpool`` respectively — and emissions become (B, gamma+1)
    wide. Wide steps (width > 1) behave exactly as without a draft, except
    the draft ingests the same chunk so its paged KV tracks the committed
    prefix. Width-1 steps become speculative: gamma sequential draft
    proposals, ONE gamma+1-wide target verify, in-graph acceptance
    (greedy token-match / rejection sampling via
    ``speculative_verify_per_row``), and rollback as a ``jnp.where`` on the
    carry — ``cached`` (the per-row committed watermark), ``last_tok``,
    ``penult`` and the emit masks all select back to the accepted prefix,
    while rejected target/draft KV entries simply sit beyond the watermark
    until the next step's writes overwrite them.

    ``draft="self"``: the model's own prediction module drafts: no second
    model and no pools of its own. The carry grows ``hidden`` (B, E) behind
    ``last_tok`` and emissions are (B, 2). A wide step is THIS body with the
    module's rows written behind the forward (``_mtp_calls``) and ``hidden``
    following ``cached``; a width-1 step is ``_self_spec_scan_body``.

    ``repair=True`` (``nonfinite_policy="repair"``): a row whose logits go
    non-finite is not frozen — every carry field selects back to its
    PRE-STEP value (the step simply never happened for that row; the KV it
    wrote sits at/above the unchanged committed watermark, exactly like
    rejected speculation, and the retry overwrites it). The ``nonfinite``
    latch still reports to the host, which counts consecutive latched
    boundaries and escalates a persistent fault to the quarantine path.

    ``layers`` (``PagedModelRunner.layer_work``, a model of mixed cache
    kinds): the step also counts its attention's work summed over the
    layers, each under its own window (``_attn_work_by_layer``).
    ``latent`` (``PagedModelRunner.latent_layers``, a model with latent
    attention): the step counts the latent rows its attention layers read
    and the pairs they score (``LATENT_STAT_NAMES``). ``heads``
    (``PagedModelRunner.row_heads``): a wide step counts its row tiles
    (``_row_tile_work``). ``mtp``: the model has a prediction module, so
    its vector has that module's lanes, which stay 0 while it does not
    draft. ``linear`` (``PagedModelRunner.linear_layers``): the model's
    linear layers; their (state, tail) pair is the carry's last field, the
    forward takes and returns it, and the step counts their work
    (``RECURRENT_STAT_NAMES``): live positions and live rows, each x the
    linear layers, and the positions their rule computes, by
    ``_rule_by_rows`` or by the chip's kernel (``kernel_rule``:
    ``_rule_kernel_runs``). ``conv`` (``PagedModelRunner.conv_layers``):
    the model's conv layers; their tail is the carry's last field (behind
    the linear layers' pair where the stack has both), and the step counts
    the live positions they moved it by, x the conv layers
    (``CONV_STAT_NAMES``). A model with either is served without a draft
    and without ``repair``, which would have to roll the state back
    (``archs.validate_recurrent_serving``)."""
    self_draft = draft == "self"
    keeps = linear or conv      # some mixer keeps a state a slot
    assert not keeps or (draft is None and not repair)
    if self_draft and width == 1:
        return _self_spec_scan_body(fwd, params, prompts, prompt_lens,
                                    limits, eos_ids, temps, tables, greedy,
                                    repair=repair, window=window,
                                    ladder=ladder, latent=latent)
    if draft is not None and not self_draft:
        return _spec_scan_body(fwd, params, prompts, prompt_lens, limits,
                               eos_ids, temps, tables, width, greedy, *draft,
                               repair=repair, window=window, ladder=ladder,
                               layers=layers, latent=latent, heads=heads)
    if self_draft:
        module, commit_rows = _mtp_calls(fwd, params, tables, latent)

    def body(carry, _):
        # what the mixers keep a slot (the linear layers' (state, tail), the
        # conv layers' tail): the last field, where there is one
        carry, recurrent = (carry[:-1], carry[-1:]) if keeps else (carry, ())
        # ``hidden``: one field under a self-draft, none otherwise
        (cached, produced, last_tok, *hidden, done, poison, nonfinite, stats,
         rng, kpool, vpool) = carry
        prev_last, prev_done = last_tok, done
        with jax.named_scope("frame_plan"):
            prefilling, active, w, ids, positions = _wide_plan(
                prompts, prompt_lens, limits, width, cached, produced,
                last_tok, done)
            # the attention's work this step (before a repair zeroes w:
            # the step was computed either way)
            kv_read, attn_pairs = _attn_work(cached, w, window)
            row_tiles = _row_tile_work(w, width, heads)
            layer_work = None if layers is None else \
                _attn_work_by_layer(cached, w, layers)
            if latent:
                layer_work = latent * jnp.stack(
                    [jnp.sum(kv_read), jnp.sum(attn_pairs)]).astype(jnp.int32)
        logits, kpool, vpool, moe_work, *h_all = fwd(
            params, ids, positions, tables, w, kpool, vpool, moe_work=True,
            **({"hidden": True} if self_draft else {}),
            **({"recurrent": recurrent[0]} if keeps else {}))
        if keeps:
            *h_all, state = h_all
            recurrent = (state,)
        if self_draft:
            # the module's cache keeps up: its row at p - 1 is made of
            # (h[p - 1], the token at p), no attention, experts or head;
            # position -1 (a first chunk) is dead
            behind = jnp.concatenate(
                [hidden[0][:, None].astype(h_all[0].dtype),
                 h_all[0][:, :-1]], axis=1)
            pos_m = jnp.where(positions > 0, positions - 1, -1)
            kpool = commit_rows(
                kpool, module(ids, pos_m, behind, w, kpool, True), pos_m)
        with jax.named_scope("sample"):
            logits = _inject_poison(logits, poison)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                rng, sub = jax.random.split(rng)
                nxt = sample_logits_per_row(logits, sub, temps)
        with jax.named_scope("frame_plan"):
            emit, last_tok, done = _wide_emit(active, prefilling, cached, w,
                                              prompt_lens, eos_ids, nxt,
                                              last_tok, done)
            emit, done, nonfinite, bad = _finite_check(logits, active, emit,
                                                       done, nonfinite)
            if repair:
                # the row made no progress this step: restore the pre-step
                # carry (un-freeze, un-advance) — emit is already cleared
                last_tok = jnp.where(bad, prev_last, last_tok)
                done = jnp.where(bad, prev_done, done)
                w = jnp.where(bad, 0, w)
            if self_draft:
                # the stack's hidden state at the last position computed
                hidden = [jnp.where(
                    (w > 0)[:, None],
                    jnp.take_along_axis(
                        h_all[0], jnp.maximum(w - 1, 0)[:, None, None],
                        axis=1)[:, 0].astype(hidden[0].dtype), hidden[0])]
            stats = stats + _stat_delta(
                positions, ladder(*positions.shape),
                emitted=emit, active=active,
                prefill_toks=jnp.where(prefilling, w, 0),
                eos=emit & (nxt == eos_ids),
                # under any draft the verify forwards alone are counted
                # (``_spec_scan_body``): a row decoding in a wide frame of a
                # self-drafting model is plain decode
                target_fwd=None if self_draft else active & ~prefilling,
                kv_read=kv_read, attn_pairs=attn_pairs, row_tiles=row_tiles,
                moe_work=moe_work, layer_work=layer_work,
                mtp_work=jnp.zeros((len(MTP_STAT_NAMES),), jnp.int32)
                if mtp else None,
                recurrent_work=_recurrent_work(w, width, linear, kernel_rule,
                                               conv))
        carry = (cached + w, produced + emit.astype(jnp.int32), last_tok,
                 *hidden, done, poison, nonfinite, stats, rng, kpool, vpool,
                 *recurrent)
        out = (jnp.where(emit, nxt, -1), emit)
        if self_draft:        # a narrow step's two columns; the second empty
            out = (jnp.stack([out[0], jnp.full_like(nxt, -1)], axis=1),
                   jnp.stack([emit, jnp.zeros_like(emit)], axis=1))
        return carry, out

    return body


def _recurrent_work(w, width, linear, kernel_rule, conv):
    """A step's work of the mixers that keep a state a slot, the stat
    vector's last lanes (``PagedModelRunner.recurrent_stat_names``), from
    the rows' live positions ``w`` (B,): ``RECURRENT_STAT_NAMES`` for
    ``linear`` layers, then ``CONV_STAT_NAMES`` for ``conv`` layers; None
    for a model with neither."""
    work = []
    if linear:
        work.append(linear * jnp.stack(
            [jnp.sum(w), jnp.sum(w > 0),
             _rule_positions(w, width, kernel_rule)]).astype(jnp.int32))
    if conv:
        work.append((conv * jnp.sum(w)).astype(jnp.int32)[None])
    return jnp.concatenate(work) if len(work) > 1 else \
        work[0] if work else None


def _inject_poison(logits, poison):
    """Fault-injection hook for the in-graph finite-check: rows whose
    device ``poison`` flag is set get NaN logits, exercising the REAL
    quarantine path (detection, freeze, boundary eviction). The flag is
    normally all-False, so this compiles to one cheap select — always part
    of the frame program, so arming a fault schedule never retraces."""
    pad = (1,) * (logits.ndim - 1)
    return jnp.where(poison.reshape((-1,) + pad),
                     jnp.asarray(jnp.nan, logits.dtype), logits)


def _finite_check(logits, active, emit, done, nonfinite):
    """The in-graph per-row poison detector: an active row whose logits
    contain a non-finite value (NaN/inf — numeric blowup or injected) stops
    emitting THIS step, freezes for the rest of the frame, and latches its
    ``nonfinite`` carry flag, which the host reads at the frame boundary
    (one tiny (B,) read, never inside the frame) to quarantine the row via
    the eviction path. Sibling rows' arithmetic is untouched — the batch
    never dies for one request. Also returns ``bad`` (the per-row detection
    mask) so the repair policy can select the pre-step carry back in."""
    axes = tuple(range(1, logits.ndim))
    bad = active & ~jnp.all(jnp.isfinite(logits), axis=axes)
    emit = emit & ~(bad if emit.ndim == 1 else bad[:, None])
    return emit, done | bad, nonfinite | bad, bad


def _attn_work(cached, w, window):
    """Per row, what the target's attention must do for a step in which
    the row consumes ``w`` positions after ``cached`` committed ones: KV
    positions read, ``min(cached + w, window + w)`` (``cached + w`` without
    a window; 0 for a row that sits the step out), and query x key pairs,
    ``w`` times that. The host mirror in the telemetry tests replays
    exactly this."""
    kv = cached + w
    if window is not None:
        kv = jnp.minimum(kv, window + w)
    kv = jnp.where(w > 0, kv, 0)
    return kv, w * kv


def _row_tile_work(w, width, heads):
    """(row tiles, those that hold a live row) of ONE layer's wide step in
    which each row consumes ``w`` positions of a chunk of ``width``: over
    slots and ``heads[0]`` kv heads, a head's ``width x heads[1]`` query
    rows cut as the paged kernel cuts them
    (``paged_attention.row_tile``), a row's live ones the first
    ``w x heads[1]``. None for a step whose rows are not cut, or where
    ``heads`` is."""
    from ...ops.pallas.paged_attention import row_tile
    tile = None if heads is None else row_tile(width * heads[1])
    if tile is None:
        return None
    kvh, group = heads
    return (jnp.asarray(kvh * w.shape[0] * (width * group // tile)),
            kvh * ((w * group + tile - 1) // tile))


def _attn_work_by_layer(cached, w, layer_windows):
    """``_attn_work`` summed over the rows and over the LAYERS, each layer
    under its own window (0: none), in the order of
    ``telemetry.LAYER_STAT_NAMES``: KV positions read, query x key pairs,
    and the part of the first that the windowed layers (the ring kind)
    read."""
    read = pairs = ring = jnp.zeros((), jnp.int32)
    for win in sorted(set(layer_windows)):
        n = layer_windows.count(win)
        kv, qk = _attn_work(cached, w, win or None)
        read = read + n * jnp.sum(kv)
        pairs = pairs + n * jnp.sum(qk)
        if win:
            ring = ring + n * jnp.sum(kv)
    return jnp.stack([read, pairs, ring]).astype(jnp.int32)


def _stat_delta(positions, ladder, emitted=None, active=None,
                prefill_toks=None, eos=None, target_fwd=None, drafted=None,
                accepted=None, kv_read=None, attn_pairs=None, row_tiles=None,
                moe_work=None, layer_work=None, mtp_work=None,
                recurrent_work=None, block_work=None):
    """One step's (N_STATS,) in-graph counter increment. Each keyword is a
    bool mask / int array to sum, or None for zero — the layout is pinned by
    the STAT_* indices in ``telemetry.py`` and the host-mirror replay tests
    assert the resulting totals exactly. ``positions`` (B, C) is the chunk
    the target forwarded and ``ladder`` its rungs: the lane after the sums
    is the rung the forward chose for it (``_rung_of``, the same
    arithmetic), then one step at that rung; ``row_tiles`` is
    ``_row_tile_work``'s pair. Behind them ``moe_work``, the
    target forward's own count of its routed experts' work
    (``MOE_STAT_NAMES``, and ``SHARE_STAT_NAMES`` behind them where its
    router is wider than the experts held), where the model has any
    (``telemetry.n_stats``), and last ``layer_work``: ``LAYER_STAT_NAMES``
    where the model mixes cache kinds, ``LATENT_STAT_NAMES`` where its
    attention is latent; behind those ``mtp_work``, the prediction
    module's own (``MTP_STAT_NAMES``), where the model drafts for itself, or
    ``recurrent_work`` (``RECURRENT_STAT_NAMES``), where it has linear
    layers, or ``block_work`` (``BLOCK_STAT_NAMES``), where it generates
    by diffusion over blocks."""
    vals = [emitted, active, prefill_toks, eos, target_fwd, drafted, accepted,
            kv_read, attn_pairs, *(row_tiles or (None, None))]
    z = jnp.zeros((), jnp.int32)
    out = [z if v is None else jnp.sum(v.astype(jnp.int32)) for v in vals]
    rung = _rung_of(positions, ladder)
    out.append(jnp.asarray(ladder, jnp.int32)[rung])
    # a shape with one rung counts its steps at none
    steps = (jnp.arange(MAX_RUNGS) == rung).astype(jnp.int32) \
        * int(len(ladder) > 1)
    out = jnp.concatenate([jnp.stack(out), steps]
                          + ([] if moe_work is None else [moe_work])
                          + ([] if layer_work is None else [layer_work])
                          + ([] if mtp_work is None else [mtp_work])
                          + ([] if recurrent_work is None
                             else [recurrent_work])
                          + ([] if block_work is None else [block_work]))
    assert layer_work is None or layer_work.shape[0] in (
        len(LAYER_STAT_NAMES), len(LATENT_STAT_NAMES))
    return out


def _wide_plan(prompts, prompt_lens, limits, width, cached, produced,
               last_tok, done):
    """The per-row SplitFuse scheduling arithmetic of a (wide) serving step:
    who prefills, who decodes, who freezes, and the chunk they consume.
    Returns (prefilling, active, w, ids, positions); frozen rows get w=0 and
    positions -1 (trash-routed). Shared by the plain and speculative scan
    bodies — the host-mirror replay in ``DeviceSlotTable.absorb`` mirrors
    exactly this arithmetic, so it must not fork."""
    offs = jnp.arange(width)
    prefilling = cached < prompt_lens
    active = ~done & (prefilling | (produced < limits))
    w = jnp.where(
        active,
        jnp.where(prefilling,
                  jnp.minimum(width, prompt_lens - cached), 1),
        0)
    idx = jnp.clip(cached[:, None] + offs[None, :], 0,
                   prompts.shape[1] - 1)
    ids = jnp.where(prefilling[:, None],
                    jnp.take_along_axis(prompts, idx, axis=1),
                    jnp.where(offs[None, :] == 0, last_tok[:, None], 0))
    mask = offs[None, :] < w[:, None]
    positions = jnp.where(mask, cached[:, None] + offs[None, :], -1)
    return prefilling, active, w, ids, positions


def _wide_emit(active, prefilling, cached, w, prompt_lens, eos_ids, nxt,
               last_tok, done):
    """Completion/emit bookkeeping of a wide serving step (the other half of
    ``_wide_plan``'s contract): rows completing their prefill and decode
    rows emit ``nxt``; EOS freezes in-graph."""
    completes = active & prefilling & (cached + w == prompt_lens)
    emit = completes | (~prefilling & active)
    last_tok = jnp.where(emit, nxt, last_tok)
    done = done | (emit & (nxt == eos_ids))
    return emit, last_tok, done


def _block_plan(prompts, prompt_lens, limits, eos_ids, width, blk, mask_id,
                cached, produced, done, btok, bmask):
    """``_wide_plan`` for rows that generate by diffusion over blocks of
    ``blk`` positions (``width`` a multiple of it and two at least;
    ``cached`` always is a multiple): a row prefills while ``cached`` is
    under its prompt's whole blocks, ``prompt_lens // blk * blk``, and
    consumes up to ``width`` prompt tokens of them; past them a row with
    budget left holds the block at ``cached .. cached + blk - 1``: the
    prompt's remainder where it reaches into the block, then the carried
    tokens (``btok``) where the carried flags (``bmask``) say a position is
    unmasked and ``mask_id`` where they say it is not. Whether a position is
    masked is that flag, never the token's value: a prompt may hold
    ``mask_id``.

    A held block with no masked position is committed by this step and
    gives out ``emit`` (``_block_emit``). Where that does not end the row
    (no EOS among them, budget left behind them) the step is FUSED: the row
    forwards ``2 blk`` positions, the block's tokens and behind them the
    next block, all masked. Every other holding row forwards ``blk``.

    Returns (prefilling, active, w, ids, positions, tok (B, blk) the held
    block's tokens, masked (B, blk), commit (B,), fused (B,), emit (B,
    blk), is_eos (B, blk)); ``DeviceSlotTable``'s replay mirrors this
    arithmetic on the host, so it must not fork."""
    offs = jnp.arange(width)
    whole = prompt_lens // blk * blk
    prefilling = cached < whole
    active = ~done & (prefilling | (produced < limits))
    at = cached[:, None] + offs[None, :]
    from_prompt = jnp.take_along_axis(
        prompts, jnp.clip(at, 0, prompts.shape[1] - 1), axis=1)
    in_prompt = at[:, :blk] < prompt_lens[:, None]
    masked = ~prefilling[:, None] & ~in_prompt & bmask
    tok = jnp.where(in_prompt, from_prompt[:, :blk], btok)
    commit = active & ~prefilling & ~jnp.any(masked, axis=1)
    emit, is_eos = _block_emit(commit, cached, produced, prompt_lens, limits,
                               eos_ids, tok)
    ends = jnp.any(emit & is_eos, axis=1) | (
        produced + jnp.sum(emit.astype(jnp.int32), axis=1) >= limits)
    fused = commit & ~ends
    w = jnp.where(active,
                  jnp.where(prefilling, jnp.minimum(width, whole - cached),
                            jnp.where(fused, 2 * blk, blk)), 0)
    held = jnp.pad(jnp.concatenate([jnp.where(masked, mask_id, tok),
                                    jnp.full_like(tok, mask_id)], axis=1),
                   ((0, 0), (0, width - 2 * blk)))
    ids = jnp.where(prefilling[:, None], from_prompt, held)
    positions = jnp.where(offs[None, :] < w[:, None], at, -1)
    return (prefilling, active, w, ids, positions, tok, masked, commit, fused,
            emit, is_eos)


def _block_emit(commit, cached, produced, prompt_lens, limits, eos_ids, tok):
    """What a committed block gives out (the other half of ``_block_plan``'s
    contract): its positions at or past the prompt's end, in order, up to
    the row's budget and up to and including its first EOS. Returns (emit
    (B, blk), is_eos (B, blk))."""
    blk = tok.shape[1]
    gen = cached[:, None] + jnp.arange(blk)[None, :] >= prompt_lens[:, None]
    nth = jnp.cumsum(gen.astype(jnp.int32), axis=1) - 1
    is_eos = gen & (tok == eos_ids[:, None])
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos
    emit = (commit[:, None] & gen
            & (produced[:, None] + nth < limits[:, None])
            & (eos_before == 0))
    return emit, is_eos


def _block_scan_body(fwd, params, prompts, prompt_lens, limits, eos_ids,
                     temps, tables, width, greedy, cfg, window=None,
                     ladder=pack_ladder, heads=None):
    """The serving scan step of a model that generates by diffusion over
    blocks of L = ``cfg.block_length`` positions (SDAR), at every width:
    a narrow frame is ``width`` = 2 L, a wide one lets block rows ride its
    chunks with w = L or 2 L. Carry: (cached, produced, last_tok, done,
    poison, nonfinite, stats, rng, kpool, vpool, (block tokens (B, L),
    masked (B, L))); emissions are (B, L).

    Invariants at every step boundary, per row: ``cached`` is a multiple
    of L and the committed watermark, K and V final for [0, cached); a row
    past its prompt's whole blocks holds the block at [cached, cached + L)
    (``_block_plan``). A step forwards it over the pages and over the
    chunk's own keys, under the mask that lets a position see its whole
    block and every block before it, and reads logits AT L positions (no
    shift):

    - some position masked: a DENOISING step of L positions. ``block_unmask``
      fills the ``cfg.unmask_per_step`` masked positions of largest
      confidence (more under "low_confidence_dynamic" where more pass the
      threshold); the row emits nothing and ``cached`` stands: the K, V
      the step wrote lie at and past the watermark, which no step reads
      (the attention masks pool slots from the chunk's first position on)
      and the next step of the row overwrites, as rejected speculation is;
    - none masked, and the row goes on behind the block (``_block_plan``'s
      ``fused``): the FUSED step of 2 L positions, the block's tokens and
      the next block, all masked. The first L positions' K, V are the
      block's final ones: ``cached`` moves by L and the row emits the
      block's positions past its prompt (``_block_emit``). The logits are
      read at the second L, which see the first's keys of this very
      forward as a prefill chunk's later blocks see its earlier ones, and
      ``block_unmask`` runs on them: the next block's first denoising
      step. Its K, V lie past the new watermark like any denoising step's;
    - none masked, and the block is the row's last (an EOS among what it
      emits, or the budget spent): the COMMIT alone, L positions, logits
      unused.

    So a block of m masked positions costs ceil(m / unmask_per_step)
    forwards, and a row one more for its last block: S a block where the
    plain walk takes S + 1, the same tokens. ``last_tok`` rides the carry
    unread. No draft and no repair
    (``archs.validate_block_diffusion_serving``). ``target_forwards``
    counts a block row's every forward, a fused one once;
    ``BLOCK_STAT_NAMES`` split them."""
    blk, per_step = cfg.block_length, cfg.unmask_per_step
    assert width % blk == 0 and width >= 2 * blk, (width, blk)
    threshold = cfg.confidence_threshold \
        if cfg.remasking_strategy == "low_confidence_dynamic" else None

    def body(carry, _):
        (cached, produced, last_tok, done, poison, nonfinite, stats, rng,
         kpool, vpool, (btok, bmask)) = carry
        with jax.named_scope("frame_plan"):
            (prefilling, active, w, ids, positions, tok, masked, commit, fused,
             emit, is_eos) = _block_plan(
                prompts, prompt_lens, limits, eos_ids, width, blk,
                cfg.mask_token_id, cached, produced, done, btok, bmask)
            holds = active & ~prefilling
            denoise = holds & ~commit
            # the positions whose logits the step reads: the held block's
            # masked ones, or every one of the block behind it
            reads = (masked & denoise[:, None]) | fused[:, None]
            kv_read, attn_pairs = _attn_work(cached, w, window)
            row_tiles = _row_tile_work(w, width, heads)
        logits, kpool, vpool, moe_work = fwd(
            params, ids, positions, tables, w, kpool, vpool, moe_work=True,
            head_at=jnp.where(fused, blk, 0))
        with jax.named_scope("sample"), jax.named_scope("bd_unmask"):
            logits = _inject_poison(logits, poison)
            sub = None
            if not greedy:
                rng, sub = jax.random.split(rng)
            x0, unmask = block_unmask(logits, reads, sub, temps,
                                      per_step=per_step, threshold=threshold)
        with jax.named_scope("frame_plan"):
            emit, done, nonfinite, bad = _finite_check(logits, active, emit,
                                                       done, nonfinite)
            done = done | jnp.any(emit & is_eos, axis=1)
            moved = jnp.where(holds, jnp.where(commit, blk, 0), w)
            # the block the row holds next: the same one less what this
            # step unmasked, or the one behind a committed block, all
            # masked but for what a fused step unmasked of it
            denoised = (denoise | fused)[:, None]
            bmask = jnp.where(denoised, reads & ~unmask,
                              jnp.where(commit[:, None], True, bmask))
            btok = jnp.where(denoised, jnp.where(unmask, x0, tok), btok)
            stats = stats + _stat_delta(
                positions, ladder(*positions.shape),
                emitted=emit, active=active,
                prefill_toks=jnp.where(prefilling, w, 0),
                eos=emit & is_eos, target_fwd=holds,
                kv_read=kv_read, attn_pairs=attn_pairs, row_tiles=row_tiles,
                moe_work=moe_work,
                block_work=jnp.stack([
                    jnp.sum(denoise | fused), jnp.sum(commit & ~fused),
                    jnp.sum(unmask), jnp.sum(commit & ~bad), jnp.sum(reads),
                    jnp.sum(fused)]).astype(jnp.int32))
        carry = (cached + moved,
                 produced + jnp.sum(emit.astype(jnp.int32), axis=1),
                 last_tok, done, poison, nonfinite, stats, rng, kpool, vpool,
                 (btok, bmask))
        return carry, (jnp.where(emit, tok, -1), emit)

    return body


def _spec_scan_body(fwd, params, prompts, prompt_lens, limits, eos_ids,
                    temps, tables, width, greedy, draft_fwd, draft_params,
                    gamma, repair=False, window=None, ladder=pack_ladder,
                    layers=None, latent=None, heads=None):
    """Speculative variant of the serving scan step (see
    ``_serving_scan_body``, also for ``layers`` and ``latent``). Carry: (cached, produced, last_tok, penult,
    done, poison, nonfinite, stats, rng, kpool, vpool, dkpool, dvpool);
    emissions are (B, gamma+1). The finite-check watches the TARGET's
    verify logits (a draft gone non-finite only garbles proposals, which
    verification rejects; a non-finite target is unrecoverable for the
    row and quarantines it).

    Invariants at every step boundary, per row: target KV is committed for
    positions [0, cached) (``cached`` IS the committed watermark — pool
    slots at or beyond it may hold rejected speculation and are dead until
    overwritten); ``last_tok`` sits at position ``cached`` and is not yet in
    any cache; ``penult`` is the token at position ``cached - 1``; the draft
    KV is valid for [0, cached - 1] at least (the width-2 first draft step
    re-feeds ``penult`` + ``last_tok``, which restores the one slot a fully
    accepted previous step can leave the draft missing — re-writing an
    already-valid slot reproduces the same KV, since the context below it
    is committed)."""
    k_out = gamma + 1
    koffs = jnp.arange(k_out)

    def tail_work(cached, w, kv_read, attn_pairs):
        """The target's attention work by layer, for the vector's last
        lanes, as the plain step counts it."""
        if latent:
            return _latent_work(latent, kv_read, attn_pairs)
        return None if layers is None else \
            _attn_work_by_layer(cached, w, layers)

    if width > 1:
        def body(carry, _):
            (cached, produced, last_tok, penult, done, poison, nonfinite,
             stats, rng, kpool, vpool, dkpool, dvpool) = carry
            prev_last, prev_done = last_tok, done
            b = cached.shape[0]
            with jax.named_scope("frame_plan"):
                prefilling, active, w, ids, positions = _wide_plan(
                    prompts, prompt_lens, limits, width, cached, produced,
                    last_tok, done)
                kv_read, attn_pairs = _attn_work(cached, w, window)
                row_tiles = _row_tile_work(w, width, heads)
                layer_work = tail_work(cached, w, kv_read, attn_pairs)
            logits, kpool, vpool, moe_work = fwd(
                params, ids, positions, tables, w, kpool, vpool,
                moe_work=True)
            logits = _inject_poison(logits, poison)
            # the draft ingests the identical chunk: prefill rows stream the
            # prompt into the draft pools, decode rows (w=1 inside a wide
            # mixed frame) keep the draft cache on the committed prefix
            _, dkpool, dvpool = draft_fwd(draft_params, ids, positions,
                                          tables, w, dkpool, dvpool)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                rng, sub = jax.random.split(rng)
                nxt = sample_logits_per_row(logits, sub, temps)
            # token at position (cached + w - 1): last prompt token for rows
            # completing prefill, the consumed last_tok for decode rows —
            # snapshot BEFORE _wide_emit overwrites last_tok
            tail = jnp.take_along_axis(
                prompts, jnp.maximum(prompt_lens - 1, 0)[:, None],
                axis=1)[:, 0]
            new_penult = jnp.where(prefilling, tail, last_tok)
            emit, last_tok, done = _wide_emit(active, prefilling, cached, w,
                                              prompt_lens, eos_ids, nxt,
                                              last_tok, done)
            emit, done, nonfinite, bad = _finite_check(logits, active, emit,
                                                       done, nonfinite)
            if repair:
                # pre-step rollback (see _serving_scan_body): the cleared
                # emit already keeps penult/produced untouched for bad rows
                last_tok = jnp.where(bad, prev_last, last_tok)
                done = jnp.where(bad, prev_done, done)
                w = jnp.where(bad, 0, w)
            penult = jnp.where(emit, new_penult, penult)
            toks_k = jnp.full((b, k_out), -1, jnp.int32).at[:, 0].set(
                jnp.where(emit, nxt, -1))
            emit_k = jnp.zeros((b, k_out), bool).at[:, 0].set(emit)
            # TARGET_FWD stays 0 on wide speculative steps: serve_stats'
            # speculative accounting counts VERIFY forwards only (decode
            # rows coasting inside a wide mixed frame are plain decode),
            # and the device counters must replay that arithmetic exactly
            stats = stats + _stat_delta(
                positions, ladder(*positions.shape),
                emitted=emit, active=active,
                prefill_toks=jnp.where(prefilling, w, 0),
                eos=emit & (nxt == eos_ids),
                kv_read=kv_read, attn_pairs=attn_pairs, row_tiles=row_tiles,
                moe_work=moe_work, layer_work=layer_work)
            return ((cached + w, produced + emit.astype(jnp.int32), last_tok,
                     penult, done, poison, nonfinite, stats, rng, kpool,
                     vpool, dkpool, dvpool),
                    (toks_k, emit_k))

        return body

    # ---- width 1: the speculative decode step ----
    def body(carry, _):
        (cached, produced, last_tok, penult, done, poison, nonfinite, stats,
         rng, kpool, vpool, dkpool, dvpool) = carry
        prev_last, prev_penult, prev_done = last_tok, penult, done
        # speculative frames are scheduled only when no slot prefills; a
        # prefilling row here would freeze (serve() never produces one)
        active = ~done & (cached >= prompt_lens) & (produced < limits)
        # positions past the row's KV reservation (prompt + budget + 1
        # lookahead) must route to the trash block — a clipped block-table
        # gather would otherwise scatter rejected speculation into the
        # row's LIVE last page. Their logits are garbage but provably never
        # emitted: index k needs produced + k < limits, which bounds the
        # position below the cap.
        cap = prompt_lens + limits

        def pos_of(p):
            return jnp.where(active[:, None] & (p >= 0) & (p <= cap[:, None]),
                             p, -1)

        if greedy:
            draft_rngs = [None] * gamma
            rng_v = None
        else:
            rng, *subs = jax.random.split(rng, gamma + 2)
            draft_rngs, rng_v = subs[:gamma], subs[gamma]

        def propose(logits, r):
            if greedy:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return sample_logits_per_row(logits, r, temps)

        # ---- draft phase: gamma proposals; step 0 is width 2 (re-feeds
        # penult + last_tok, healing the draft cache — see invariants) ----
        av = active.astype(jnp.int32)
        ids0 = jnp.stack([penult, last_tok], axis=1)
        pos0 = pos_of(jnp.stack([cached - 1, cached], axis=1))
        dlog, dkpool, dvpool = draft_fwd(draft_params, ids0, pos0, tables,
                                         2 * av, dkpool, dvpool)
        q = [propose(dlog, draft_rngs[0])]
        dlogits = [dlog]
        for j in range(1, gamma):
            dlog, dkpool, dvpool = draft_fwd(
                draft_params, q[-1][:, None], pos_of((cached + j)[:, None]),
                tables, av, dkpool, dvpool)
            dlogits.append(dlog)
            q.append(propose(dlog, draft_rngs[j]))
        q = jnp.stack(q, axis=1)                          # (B, G)
        dlogits = jnp.stack(dlogits, axis=1)              # (B, G, V)

        # ---- verify: ONE batched ragged target forward over the committed
        # last token + all gamma drafts ----
        ids_v = jnp.concatenate([last_tok[:, None], q], axis=1)
        pos_v = pos_of(cached[:, None] + koffs[None, :])
        tlogits, kpool, vpool, moe_work = fwd(
            params, ids_v, pos_v, tables, k_out * av, kpool, vpool,
            all_logits=True, moe_work=True)
        tlogits = _inject_poison(tlogits, poison)
        # ---- accept + rollback: pure selects on the carry ----
        e, emit, is_eos, m, seq_toks, done, nonfinite, bad = _accept(
            tlogits, dlogits, q, temps, rng_v, active, produced, limits,
            eos_ids, last_tok, done, nonfinite)
        new_last = jnp.take_along_axis(seq_toks, m[:, None], axis=1)[:, 0]
        new_penult = jnp.take_along_axis(
            seq_toks, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
        last_tok = jnp.where(active, new_last, last_tok)
        penult = jnp.where(active, new_penult, penult)
        done = done | jnp.any(emit & is_eos, axis=1)
        if repair:
            # pre-step rollback (see _serving_scan_body); m is already 0
            # for bad rows (their emit columns were cleared), so cached/
            # produced stand still without an extra select
            last_tok = jnp.where(bad, prev_last, last_tok)
            penult = jnp.where(bad, prev_penult, penult)
            done = jnp.where(bad, prev_done, done)
        # verify forwards == active rows (column 0 of the emit mask); the
        # accepted-draft count is the emit columns past it — the device-side
        # twin of the host arithmetic serve_stats always used
        kv_read, attn_pairs = _attn_work(cached, k_out * av, window)
        stats = stats + _stat_delta(
            pos_v, ladder(*pos_v.shape),
            emitted=emit, active=active, eos=emit & is_eos,
            target_fwd=active, drafted=gamma * active.astype(jnp.int32),
            accepted=emit[:, 1:], kv_read=kv_read, attn_pairs=attn_pairs,
            moe_work=moe_work,
            layer_work=tail_work(cached, k_out * av, kv_read, attn_pairs))
        return ((cached + m, produced + m, last_tok, penult, done, poison,
                 nonfinite, stats, rng, kpool, vpool, dkpool, dvpool),
                (jnp.where(emit, e, -1), emit))

    return body


def _latent_work(latent, kv_read, attn_pairs):
    """``LATENT_STAT_NAMES``' lanes of a step: one layer's rows read and
    pairs scored x the ``latent`` attention layers."""
    return latent * jnp.stack(
        [jnp.sum(kv_read), jnp.sum(attn_pairs)]).astype(jnp.int32)


def _accept(tlogits, dlogits, q, temps, rng_v, active, produced, limits,
            eos_ids, last_tok, done, nonfinite):
    """What a speculative step's verify lets through, per row: the tokens
    ``e`` (B, G + 1) (accepted drafts, then the target's correction or
    bonus token), their ``emit`` mask (a prefix: cut at the row's budget
    and behind its first EOS, cleared where the target's logits are not
    finite), ``is_eos``, the count ``m``, ``[last_tok | e]`` to pick the
    new last tokens from, and ``_finite_check``'s (done, nonfinite,
    bad)."""
    koffs = jnp.arange(q.shape[1] + 1)
    n_acc, repl = speculative_verify_per_row(tlogits, dlogits, q, temps,
                                             rng=rng_v)
    q_pad = jnp.concatenate([q, q[:, -1:]], axis=1)   # (B, G+1)
    e = jnp.where(koffs[None, :] < n_acc[:, None], q_pad, repl[:, None])
    is_eos = e == eos_ids[:, None]
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos
    emit = (active[:, None] & (koffs[None, :] <= n_acc[:, None])
            & (produced[:, None] + koffs[None, :] < limits[:, None])
            & (eos_before == 0))
    emit, done, nonfinite, bad = _finite_check(tlogits, active, emit, done,
                                               nonfinite)
    m = jnp.sum(emit.astype(jnp.int32), axis=1)
    seq_toks = jnp.concatenate([last_tok[:, None], e], axis=1)
    return e, emit, is_eos, m, seq_toks, done, nonfinite, bad


def _page_commit(kpool):
    """What writes a step's rows into float pools: the kernel that writes
    the touched pages in place on the chip, the scatter elsewhere (the
    choice ``_forward`` makes for its own commit)."""
    if _use_pallas_paged() and kpool.dtype != jnp.int8:
        from ...ops.pallas.kv_commit import kv_commit
        return kv_commit
    return commit_scatter


def _mtp_calls(fwd, params, tables, module_layer):
    """What a self-drafting step asks of the prediction module, under the
    scope ``mtp_draft``: ``module(ids, pos, hid, w, kpool, rows_only)``, its
    forward over (the stack's hidden states ``hid``, the tokens one position
    on) against cache layer ``module_layer`` of the pool, and
    ``commit_rows(kpool, rows, pos)``, which writes the module's rows
    there."""
    def module(ids, pos, hid, w, kpool, rows_only=False):
        with jax.named_scope("mtp_draft"):
            return fwd(params, ids, pos, tables, w, kpool, None,
                       mtp=(hid, rows_only))

    def commit_rows(kpool, rows, pos):
        with jax.named_scope("mtp_draft"), jax.named_scope("kv_commit"):
            return _page_commit(kpool)(
                kpool, None, rows, None, block_tables=tables, positions=pos,
                layer0=module_layer)[0]

    return module, commit_rows


def _self_spec_scan_body(fwd, params, prompts, prompt_lens, limits, eos_ids,
                         temps, tables, greedy, repair=False, window=None,
                         ladder=pack_ladder, latent=None):
    """The NARROW serving scan step of a model that drafts for itself with
    its prediction module (DeepSeek-V3's MTP, one module: gamma 1). Carry:
    (cached, produced, last_tok, hidden, done, poison, nonfinite, stats,
    rng, kpool, vpool); emissions are (B, 2).

    The module pairs the stack's hidden state at position p with the token
    at p + 1 and predicts the token at p + 2; its one layer keeps a cache
    of its own rows, layer ``latent`` (the stack's attention layers, so the
    one behind them) of the SAME pool, through the same tables. Invariants
    at every step boundary, per row: the stack's rows are committed for
    [0, cached) and ``last_tok`` sits at ``cached``, as everywhere;
    ``hidden`` is the stack's hidden state (before the final norm) at
    ``cached - 1``, the position whose logits gave ``last_tok``; the
    module's rows are committed for [0, cached - 1). So every step hands
    the module the step's own ids one position BACK, beside the hidden
    states shifted by one with the carry's in front:

    - a wide step is ``_serving_scan_body``'s, which then writes the
      module's rows alone (no attention, experts or head) at
      ``positions - 1``;
    - a narrow step, this one, DRAFTS: the module whole at ``cached - 1``
      over (``hidden``, ``last_tok``) gives its row and the token after
      next; VERIFIES: the stack over [last_tok, draft] at
      [cached, cached + 1]; HEALS: the module's row at ``cached`` from the
      verify's first hidden state and the draft (right if the draft is
      accepted, dead behind the watermark and overwritten by the next draft
      if not), and commits the module's two rows at once. Acceptance and
      rollback are ``_spec_scan_body``'s (``_accept``), ``hidden`` rolls
      back with ``cached``: it is the verify's hidden state at the last
      emitted position.

    ``target_forwards`` counts the verify forwards alone and
    ``drafted_tokens`` one a verify, as under a separate draft."""
    module, commit_rows = _mtp_calls(fwd, params, tables, latent)

    def body(carry, _):
        (cached, produced, last_tok, hidden, done, poison, nonfinite, stats,
         rng, kpool, vpool) = carry
        prev_last, prev_hidden, prev_done = last_tok, hidden, done
        # as in ``_spec_scan_body``: no row prefills in a narrow frame, and
        # a position past the row's reservation goes to the trash page
        active = ~done & (cached >= prompt_lens) & (produced < limits)
        cap = prompt_lens + limits

        def pos_of(p):
            return jnp.where(active[:, None] & (p >= 0) & (p <= cap[:, None]),
                             p, -1)

        rng_d = rng_v = None
        if not greedy:
            rng, rng_d, rng_v = jax.random.split(rng, 3)
        av = active.astype(jnp.int32)
        pos_d = pos_of((cached - 1)[:, None])
        dlog, row_d, draft_moe = module(last_tok[:, None], pos_d,
                                        hidden[:, None], av, kpool)
        with jax.named_scope("sample"):
            if greedy:
                q = jnp.argmax(dlog, axis=-1).astype(jnp.int32)[:, None]
            else:
                q = sample_logits_per_row(dlog, rng_d, temps)[:, None]
        ids_v = jnp.concatenate([last_tok[:, None], q], axis=1)
        pos_v = pos_of(cached[:, None] + jnp.arange(2)[None, :])
        tlogits, kpool, vpool, moe_work, h_v = fwd(
            params, ids_v, pos_v, tables, 2 * av, kpool, vpool,
            all_logits=True, moe_work=True, hidden=True)
        tlogits = _inject_poison(tlogits, poison)
        row_h = module(q, pos_v[:, :1], h_v[:, :1], av, kpool, True)
        kpool = commit_rows(kpool, jnp.concatenate([row_d, row_h], axis=2),
                            jnp.concatenate([pos_d, pos_v[:, :1]], axis=1))
        with jax.named_scope("sample"):
            e, emit, is_eos, m, seq_toks, done, nonfinite, bad = _accept(
                tlogits, dlog[:, None], q, temps, rng_v, active, produced,
                limits, eos_ids, last_tok, done, nonfinite)
        with jax.named_scope("frame_plan"):
            new_last = jnp.take_along_axis(seq_toks, m[:, None], axis=1)[:, 0]
            last_tok = jnp.where(active, new_last, last_tok)
            hidden = jnp.where(
                (m > 0)[:, None],
                jnp.take_along_axis(
                    h_v, jnp.maximum(m - 1, 0)[:, None, None],
                    axis=1)[:, 0].astype(hidden.dtype), hidden)
            done = done | jnp.any(emit & is_eos, axis=1)
            if repair:
                # m is 0 for a bad row: cached, produced and hidden stand
                last_tok = jnp.where(bad, prev_last, last_tok)
                hidden = jnp.where(bad[:, None], prev_hidden, hidden)
                done = jnp.where(bad, prev_done, done)
            kv_read, attn_pairs = _attn_work(cached, 2 * av, window)
            stats = stats + _stat_delta(
                pos_v, ladder(*pos_v.shape),
                emitted=emit, active=active, eos=emit & is_eos,
                target_fwd=active, drafted=active, accepted=emit[:, 1:],
                kv_read=kv_read, attn_pairs=attn_pairs, moe_work=moe_work,
                layer_work=_latent_work(latent, kv_read, attn_pairs),
                # the draft at cached - 1 reads its cached rows: its own too
                mtp_work=jnp.concatenate(
                    [jnp.sum(av * cached)[None].astype(jnp.int32),
                     draft_moe[:2]]))
        return ((cached + m, produced + m, last_tok, hidden, done, poison,
                 nonfinite, stats, rng, kpool, vpool),
                (jnp.where(emit, e, -1), emit))

    return body


def _paged_attention(q, kpages, vpages, positions, cfg, window=None,
                     chunk_k=None, chunk_v=None, chunk_start=None,
                     alibi_slopes=None, scale=None, visible_to=None):
    """q: (B, C, H, D); kpages/vpages: (B, S_pad, KVH, D); positions: (B, C)
    absolute slot of each query (−1 = pad). Query at slot p attends slots ≤ p.
    ``window``: sliding-window width (may be traced; <= 0 = global).
    ``chunk_k/chunk_v``: (B, C, KVH, D) the current chunk's own KV — the
    pool slots >= ``chunk_start`` (B,) are stale and masked; the chunk keys
    attend at key positions = ``positions``. ``alibi_slopes``: per-head
    slopes matching q's head count — the caller slices them under tensor
    parallelism, where q carries only this shard's heads. ``visible_to``
    (B, C), given: the last key position each query sees, in place of its
    own (``paged_ragged_attention``'s: attention causal by block)."""
    h = q.shape[2]
    s_pad = kpages.shape[1]
    k_pos = jnp.arange(s_pad)[None, :] * jnp.ones(
        (q.shape[0], 1), jnp.int32)                     # (B, S_pad)
    if chunk_k is not None:
        kpages = jnp.concatenate([kpages, chunk_k.astype(kpages.dtype)], axis=1)
        vpages = jnp.concatenate([vpages, chunk_v.astype(vpages.dtype)], axis=1)
        k_pos = jnp.concatenate([
            jnp.where(k_pos < chunk_start[:, None], k_pos, -1),
            jnp.where(positions >= 0, positions, -1)], axis=1)
    kvh = kpages.shape[2]
    if kvh != h:
        rep = h // kvh
        kpages = jnp.repeat(kpages, rep, axis=2)
        vpages = jnp.repeat(vpages, rep, axis=2)
    d = q.shape[-1]
    if scale is None:
        scale = cfg.attn_scale if cfg.attn_scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kpages,
                        preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        # key position (gathered slot / chunk position) relative to query
        logits = logits + (alibi_slopes[None, :, None, None]
                           * (k_pos[:, None, None, :].astype(jnp.float32)
                              - jnp.maximum(positions, 0)[:, None, :, None]))
    # softcap AFTER the bias — the order the Pallas kernel and
    # reference_attention use (ALiBi and softcapping never co-occur in the
    # supported families, but the two paths must stay bit-comparable)
    if cfg.attn_softcap:
        logits = cfg.attn_softcap * jnp.tanh(logits / cfg.attn_softcap)
    kp = k_pos[:, None, :]                               # (B, 1, S_total)
    see = positions if visible_to is None else visible_to
    mask = (kp >= 0) & (kp <= see[:, :, None])           # pad keys/rows dead
    if window is not None:
        from ...ops.attention import window_mask
        mask = mask & window_mask(positions[:, :, None], kp, window)
    logits = jnp.where(mask[:, None], logits, jnp.finfo(jnp.float32).min)
    # pad queries have no visible keys: softmax over -inf row → uniform; their
    # outputs are discarded by the caller, and max-subtraction keeps it finite.
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vpages)
