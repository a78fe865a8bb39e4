"""Plain reference of SDAR-30B-A3B-Chat (``sdar_moe``): Qwen3-MoE's decoder
layer under a mask that is causal BY BLOCK, generated by diffusion over
blocks. Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no paging, no batching, no grouped product. It is
independent of the program's model code and takes only the program's
WEIGHTS (the pytree ``models.CausalLM.init`` makes: ``embed.tok``,
``embed.lm_head``, ``layers.{attn,mlp,norm1,norm2}`` stacked over layers,
``final_norm``; ``attn.{wq,wk,wv,wo}`` by head, ``attn.{q_norm,k_norm}.scale``
of ``head_dim`` lanes, ``mlp.{router,wi_gate,wi_up,wo}`` with the three
expert matrices stacked over experts) and the sizes from the configuration
file, whose group ``diffusion`` holds what ``config.json`` does not name:
``block_length`` L, ``denoising_steps`` S, ``remasking_strategy``,
``confidence_threshold``, ``mask_token_id``.

The model, as ISSUE 47 states it (SDAR, arXiv:2510.06303; the family's
``modeling_sdar_moe.py`` and ``generate.py``). One layer, eps 1e-6:

    x = rms(h);  q = x Wq (H heads x D), k = x Wk, v = x Wv (KVH x D), no bias
    q, k = rms_D(q), rms_D(k)     one weight of D lanes shared by the heads,
                                   BEFORE RoPE (theta 1e6, split halves)
    key j is visible to query i  iff  j <= (i // L) * L + L - 1
    h += softmax_f32(q k^T / sqrt(D)) v Wo
    x = rms(h);  p = softmax_f32(x Wg) over all experts; the top 8, their
    weights renormalised to sum 1;  h += sum_e w_e Wdown_e(silu(Wgate_e x)
    * Wup_e x)    (no shared expert; every layer sparse)

Final RMSNorm, untied head. The logits at position i score the token AT
position i: no shift.

Generation of one sequence (``generate``): prefill the prompt's whole blocks
``x[0 : P // L * L]`` under the mask and keep their K, V; then block by
block: the prompt's remainder where it reaches into the block, a mask token
elsewhere; while a position is masked, one forward of the L positions over
the kept K, V and the block's own keys, nothing kept, which unmasks the
``L / S`` masked positions of largest confidence ``softmax(l)[argmax l]``
(ties: the lowest position; under ``low_confidence_dynamic`` every masked
position whose confidence passes the threshold, where those are at least
``L / S``); then ONE MORE forward of the mask-free block, whose K, V are
kept and whose logits go unused. Output: the positions past the prompt, up
to ``max_new`` and up to and including the first EOS.

Departures from the family's published code, same mathematics unless said:
whether a position is masked is STATE here, not ``token == mask_token_id``
(the family's code compares, so a prompt that holds that id would be
denoised over; here it is kept); the family's generate pads the prompt's
remainder by re-running the partial block from the prompt's tokens, which
is what holding them unmasked does; experts are computed dense and
weighted by 0 where not chosen (a block of experts at a time), attention
a block of queries at a time. K and V of committed
blocks are kept between steps: under the block mask nothing later changes
them (mathematics, not a cache of the system's); ``tests/
test_sdar_serving.py`` shows the walk equal to the one-shot forward of the
final sequence.

``logits_rows`` serves the benchmark's unchanged check
(``perfbench/reference_check.py``), which was written for a left-to-right
model: it hands over ``prompt + generated[:-1]`` and, as ``rows``, the
SHIFTED positions ``len(prompt) - 1 + i``, and takes ``max - picked`` of
row i at ``generated[i]``. This module returns ONE ROW A GENERATED TOKEN,
IN ORDER, and ignores the shift: row i is the logits AT generated token
i's own position, from the denoising step that unmasked it (``replay``).
"""

import functools
import itertools
import sys

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512           # queries a block of the one-shot attention
EXPERT_BLOCK = 8        # experts a block of the one-shot routed layer
KV_BUCKET = 512         # the kept K, V are padded to a multiple of this
F32 = jnp.float32

#: the comparison's own tolerance (``perfbench/reference_check.LOGIT_TOL``,
#: restated: that module is not imported by a configuration's reference):
#: bf16 keeps 8 bits of mantissa, and the served path rounds differently
#: through every layer, so the served choice of WHAT a position becomes and
#: of WHERE to unmask are both held to it
LOGIT_TOL = 0.25


def _diffusion(config):
    d = config["diffusion"]
    blk, steps = int(d["block_length"]), int(d["denoising_steps"])
    assert blk % steps == 0, (blk, steps)
    threshold = float(d["confidence_threshold"]) \
        if d["remasking_strategy"] == "low_confidence_dynamic" else None
    return blk, blk // steps, threshold, int(d["mask_token_id"])


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, positions, theta):
    """x: (T, heads, D) at absolute ``positions`` (T,), split halves."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(attn, norm1, h, positions, theta, eps):
    """(q (T, H, D), k, v (T, KVH, D)) of ``h`` (T, E): the projections,
    the per-head norms, RoPE."""
    w = jax.tree.map(lambda a: a.astype(F32), attn)
    x = _rms_norm(h, norm1["scale"], eps)
    q = jnp.einsum("te,ehd->thd", x, w["wq"])
    k = jnp.einsum("te,ehd->thd", x, w["wk"])
    v = jnp.einsum("te,ehd->thd", x, w["wv"])
    q = _rms_norm(q, w["q_norm"]["scale"], eps)
    k = _rms_norm(k, w["k_norm"]["scale"], eps)
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _attend(q, k, v, q_pos, k_pos, blk):
    """Softmax attention of q (T, H, D) at ``q_pos`` over k, v (S, KVH, D)
    at ``k_pos`` (-1: no key): key j is visible to query i iff
    j <= (i // blk) * blk + blk - 1."""
    groups = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    last = q_pos // blk * blk + blk - 1
    mask = (k_pos[None, :] >= 0) & (k_pos[None, :] <= last[:, None])
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _route(h, norm2, router, top_k, eps):
    """(x, the chosen experts (T, k), their weights renormalised to 1)."""
    x = _rms_norm(h, norm2["scale"], eps)
    p = jax.nn.softmax(jnp.einsum("te,ex->tx", x, router.astype(F32)),
                       axis=-1)
    top_p, chosen = jax.lax.top_k(p, top_k)
    return x, chosen, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


# ---- the one-shot forward: a whole sequence under the block mask ----

@functools.partial(jax.jit, static_argnames=("theta", "eps", "blk"))
def _attention_layer(attn, norm1, h, *, theta, eps, blk):
    """h + attention(rms(h)) over the whole of ``h`` (T, E), a block of
    queries at a time; also the layer's k, v."""
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        pos = jnp.arange(t)
        q, k, v = _qkv(attn, norm1, h, pos, theta, eps)
        out = [_attend(q[a:a + Q_BLOCK], k, v, pos[a:a + Q_BLOCK], pos, blk)
               for a in range(0, t, Q_BLOCK)]
        a = jnp.concatenate(out, axis=0)
        return h + jnp.einsum("thd,hde->te", a, attn["wo"].astype(F32)), k, v


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _dense_weights(h, norm2, router, *, top_k, eps):
    with jax.default_matmul_precision("highest"):
        x, chosen, top_p = _route(h, norm2, router, top_k, eps)
        weights = jnp.sum(jax.nn.one_hot(chosen, router.shape[-1], dtype=F32)
                          * top_p[..., None], axis=-2)
        return x, weights


@jax.jit
def _expert_block(x, weights, wi_gate, wi_up, wo):
    """Every token through every expert of the block, weighted (0 where
    the expert is not among the token's chosen)."""
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(jnp.einsum("te,xef->txf", x, wi_gate.astype(F32)))
        up = jnp.einsum("te,xef->txf", x, wi_up.astype(F32))
        out = jnp.einsum("txf,xfe->txe", gate * up, wo.astype(F32))
        return jnp.einsum("txe,tx->te", out, weights)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, scale, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("te,ev->tv", _rms_norm(h, scale, eps),
                          lm_head.astype(F32))


def _routed(h, norm2, mlp, layer, config):
    """h + routed experts(rms(h)) of one layer, h (T, E); ``mlp`` holds
    every layer's (a block of experts is sliced from the stack)."""
    x, weights = _dense_weights(
        h, norm2, mlp["router"][layer],
        top_k=int(config["num_experts_per_tok"]),
        eps=float(config["rms_norm_eps"]))
    for a in range(0, int(config["num_experts"]), EXPERT_BLOCK):
        z = a + EXPERT_BLOCK
        h = h + _expert_block(x, weights[:, a:z], mlp["wi_gate"][layer, a:z],
                              mlp["wi_up"][layer, a:z], mlp["wo"][layer, a:z])
    return h


def forward(params, ids, config, rows=None):
    """The whole of ``ids`` (T,) in ONE forward under the block mask:
    (logits (T, V) float32, or those of ``rows`` alone, k, v (layers, T,
    KVH, D)). A zero tail changes nothing before it so long as it starts on
    a block's edge; callers pad so."""
    blk = _diffusion(config)[0]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    layers = params["layers"]
    h = params["embed"]["tok"][jnp.asarray(ids)].astype(F32)
    ks, vs = [], []
    for i in range(jax.tree.leaves(layers)[0].shape[0]):
        attn, norm1, norm2 = jax.tree.map(
            lambda w, i=i: w[i],
            (layers["attn"], layers["norm1"], layers["norm2"]))
        h, k, v = _attention_layer(attn, norm1, h, theta=theta, eps=eps,
                                   blk=blk)
        ks.append(k)
        vs.append(v)
        h = _routed(h, norm2, layers["mlp"], i, config)
    if rows is not None:
        h = h[np.asarray(rows)]
    return (_head(h, params["final_norm"]["scale"],
                  params["embed"]["lm_head"], eps=eps),
            jnp.stack(ks), jnp.stack(vs))


# ---- the block step: L positions a block over kept K, V ----

@functools.partial(jax.jit, static_argnames=("theta", "eps", "blk"))
def _block_attention(attn, norm1, h, kbuf, vbuf, starts, *, theta, eps, blk):
    """h + attention(rms(h)) for N blocks at once, h (N, L, E): block n's L
    positions ``starts[n]`` .. over the kept K, V of [0, starts[n])
    (``kbuf`` / ``vbuf`` (S, KVH, D): one sequence's, shared by the blocks;
    rows from a block's start on unread by it) and over the block's own
    keys, every one of which it sees. Also the blocks' k, v (N, L, KVH, D)."""
    with jax.default_matmul_precision("highest"):
        n = h.shape[0]
        pos = starts[:, None] + jnp.arange(blk)[None, :]
        q, k, v = _qkv(attn, norm1, h.reshape(n * blk, -1), pos.reshape(-1),
                       theta, eps)
        q, k, v = (x.reshape((n, blk) + x.shape[1:]) for x in (q, k, v))
        groups = q.shape[2] // k.shape[2]
        rep = lambda x, axis: jnp.repeat(x, groups, axis=axis)  # noqa: E731
        scale = 1.0 / np.sqrt(q.shape[-1])
        kept = jnp.einsum("nqhd,khd->nhqk", q, rep(kbuf, 1)) * scale
        own = jnp.einsum("nqhd,nkhd->nhqk", q, rep(k, 2)) * scale
        seen = jnp.arange(kbuf.shape[0])[None, :] < starts[:, None]
        kept = jnp.where(seen[:, None, None, :], kept, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([kept, own], axis=-1), axis=-1)
        a = jnp.einsum("nhqk,khd->nqhd", p[..., :kbuf.shape[0]],
                       rep(vbuf, 1)) \
            + jnp.einsum("nhqk,nkhd->nqhd", p[..., kbuf.shape[0]:], rep(v, 2))
        return h + jnp.einsum("nqhd,hde->nqe", a, attn["wo"].astype(F32)), \
            k, v


def block_forward(params, kbuf, vbuf, starts, ids, config):
    """ONE forward of N blocks ``ids`` (N, L), block n at positions
    ``starts[n]`` .., each over the kept K, V before its start (``kbuf`` /
    ``vbuf`` (layers, S, KVH, D)) and over its own keys; nothing is kept.
    The layer's routed block is the one-shot forward's (every expert for
    every token, a block of experts at a time). Returns (logits (N, L, V)
    float32, the blocks' k, v (layers, N, L, KVH, D))."""
    blk = _diffusion(config)[0]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    layers = params["layers"]
    ids, starts = jnp.asarray(ids, jnp.int32), jnp.asarray(starts, jnp.int32)
    n = ids.shape[0]
    h = params["embed"]["tok"][ids].astype(F32)
    ks, vs = [], []
    for i in range(kbuf.shape[0]):
        attn, norm1, norm2 = jax.tree.map(
            lambda w, i=i: w[i],
            (layers["attn"], layers["norm1"], layers["norm2"]))
        h, k, v = _block_attention(attn, norm1, h, kbuf[i], vbuf[i], starts,
                                   theta=theta, eps=eps, blk=blk)
        ks.append(k)
        vs.append(v)
        h = _routed(h.reshape(n * blk, -1), norm2, layers["mlp"], i,
                    config).reshape(h.shape)
    logits = _head(h.reshape(n * blk, -1), params["final_norm"]["scale"],
                   params["embed"]["lm_head"], eps=eps)
    return logits.reshape(n, blk, -1), jnp.stack(ks), jnp.stack(vs)


@jax.jit
def _keep(kbuf, vbuf, start, k, v):
    return (jax.lax.dynamic_update_slice_in_dim(kbuf, k, start, axis=1),
            jax.lax.dynamic_update_slice_in_dim(vbuf, v, start, axis=1))


def _prefill(params, tokens, config):
    """K, V (layers, T padded to a bucket's edge, KVH, D) of ``tokens``,
    whole blocks, by the one-shot forward (few distinct shapes: a zero
    tail changes nothing before it)."""
    padded = np.zeros((max(1, -(-len(tokens) // Q_BLOCK)) * Q_BLOCK,),
                      np.int32)
    padded[:len(tokens)] = tokens
    _, k, v = forward(params, padded, config, rows=[0])
    return k, v


class _Walk:
    """One sequence's kept K, V and the block step over them."""

    def __init__(self, params, prompt, total, config):
        """Prefill the whole blocks of ``prompt`` under the mask; ``total``:
        the most positions the walk will reach."""
        self.params, self.config = params, config
        self.blk = _diffusion(config)[0]
        size = -(-(total + self.blk) // KV_BUCKET) * KV_BUCKET
        n = jax.tree.leaves(params["layers"])[0].shape[0]
        heads = params["layers"]["attn"]["wk"].shape[2:]
        self.kbuf = jnp.zeros((n, size) + heads, F32)
        self.vbuf = jnp.zeros_like(self.kbuf)
        self.start = len(prompt) // self.blk * self.blk
        if self.start:
            k, v = _prefill(params, prompt[:self.start], config)
            self.kbuf, self.vbuf = _keep(self.kbuf, self.vbuf, 0,
                                         k[:, :self.start], v[:, :self.start])

    def step(self, ids):
        """Logits (L, V) of the block ``ids`` at the walk's position, and
        its k, v for ``commit``."""
        logits, k, v = block_forward(self.params, self.kbuf, self.vbuf,
                                     [self.start], [ids], self.config)
        return logits[0], k[:, 0], v[:, 0]

    def commit(self, ids):
        """The forward of the mask-free block: its K, V are kept, its
        logits go unused."""
        _, k, v = self.step(ids)
        self.kbuf, self.vbuf = _keep(self.kbuf, self.vbuf,
                                     jnp.int32(self.start), k, v)
        self.start += self.blk


def _choose(logits, masked, per_step, threshold):
    """(x0 (L,), the positions a denoising step unmasks) by the text's
    rule, greedy: argmax, confidence softmax(l)[x0], the ``per_step``
    largest among ``masked`` (ties: lowest position), or every one past
    ``threshold`` where those are at least ``per_step``."""
    logits = np.asarray(logits, np.float64)
    x0 = logits.argmax(-1)
    z = logits - logits.max(-1, keepdims=True)
    conf = np.exp(z[np.arange(len(x0)), x0]) / np.exp(z).sum(-1)
    order = sorted(masked, key=lambda j: (-conf[j], j))
    chosen = order[:min(per_step, len(order))]
    if threshold is not None:
        passing = [j for j in masked if conf[j] > threshold]
        if len(passing) >= per_step:
            chosen = passing
    return x0, sorted(chosen)


def generate(params, prompt, max_new, config, eos=None, trace=None):
    """The model's output for ``prompt``, walking the text above literally,
    greedy. ``trace``, a list, receives one (start, tokens, masked
    positions, logits, unmasked positions) a denoising step."""
    blk, per_step, threshold, mask_id = _diffusion(config)
    prompt = [int(t) for t in prompt]
    walk = _Walk(params, prompt, len(prompt) + max_new, config)
    x, out = list(prompt), []
    while len(out) < max_new:
        s = walk.start
        tok = [x[s + j] if s + j < len(prompt) else mask_id
               for j in range(blk)]
        masked = [j for j in range(blk) if s + j >= len(prompt)]
        while masked:
            logits = walk.step(tok)[0]
            x0, unmask = _choose(logits, masked, per_step, threshold)
            if trace is not None:
                trace.append((s, list(tok), list(masked), np.asarray(logits),
                              list(unmask)))
            for j in unmask:
                tok[j] = int(x0[j])
            masked = [j for j in masked if j not in unmask]
        walk.commit(tok)
        x[s:] = x[s:len(prompt)] + tok[max(0, len(prompt) - s):]
        for t in tok[max(0, len(prompt) - s):]:
            if len(out) < max_new:
                out.append(t)
                if eos is not None and t == eos:
                    return out
    return out


# ---- the benchmark's check: which order did the served path take? ----

NODE_BUCKET = 128       # block states a replay's forward takes, padded to it


@jax.jit
def _row_stats(logits, finals):
    """Per position of logits (N, L, V): (the maximum, the logsumexp, the
    logit of the final token ``finals`` (N, L))."""
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[..., None]), axis=-1))
    at = jnp.take_along_axis(logits, finals[..., None], axis=-1)[..., 0]
    return top, lse, at


def _options(gap, logc, lead, masked, per_step, threshold):
    """The ways a denoising step may have unmasked, by the reference's own
    reading of it: every choice of ``per_step`` CANDIDATES, the masked
    positions whose final token lies within ``LOGIT_TOL`` of that position's
    maximum logit (``gap``) AND whose log-confidence of the final token
    (``logc``) lies within ``LOGIT_TOL`` of the best masked position's own
    (``lead``); under a threshold also every masked position past it (by the
    same tolerance), where those are enough and all are candidates by their
    token."""
    cands = [j for j in masked if gap[j] <= LOGIT_TOL
             and logc[j] >= lead - LOGIT_TOL]
    take = min(per_step, len(masked))
    options = [tuple(c) for c in itertools.combinations(cands, take)]
    if threshold is not None:
        passing = tuple(j for j in masked
                        if logc[j] > np.log(threshold) - LOGIT_TOL)
        if len(passing) >= per_step and passing not in options \
                and all(gap[j] <= LOGIT_TOL for j in passing):
            options.append(passing)
    return options


def _forward_states(step, blocks, states, mask_id):
    """``step`` over block states [(block, masked positions)], a bucket of
    ``NODE_BUCKET`` at a time (the logits of L positions a state over a
    vocabulary of 151,936 are what bounds a forward): yields (the states of
    a bucket, logits (n, L, V) on the device, finals (n, L))."""
    for a in range(0, len(states), NODE_BUCKET):
        part = states[a:a + NODE_BUCKET]
        nodes = part + [part[-1]] * (NODE_BUCKET - len(part))
        finals = np.asarray([blocks[b][1] for b, _ in nodes], np.int32)
        toks = finals.copy()
        for i, (_, masked) in enumerate(nodes):
            toks[i, list(masked)] = mask_id
        yield part, step([blocks[b][0] for b, _ in nodes], toks), \
            jnp.asarray(finals)


def replay(step, blocks, per_step, threshold, mask_id):
    """Replay whole blocks from their final tokens: ``blocks`` = [(start,
    final (L,), first masked position)], whose positions from the third on
    started masked; ``step(starts, ids)`` is one forward of those blocks
    over the K, V kept before each (``block_forward``). Finds for each block
    an order of unmasking that the reference itself explains: the served
    choice of WHERE to unmask is held to the same bf16 rounding as the
    choice of WHAT (``_options``).

    What a denoising step sees depends on WHICH positions are still masked,
    not on the order they were unmasked in (an unmasked position holds its
    final token), so the search walks the SETS of masked positions a block
    can be in, at most 2^L a block (on seeded weights confidences lie close
    and many orders pass; on trained ones nearly always one), largest sets
    first, and keeps for each set the way into it of smallest worst gap. The
    K, V a block reads are its predecessors' FINAL ones, so all blocks'
    sets of one size go through ``step`` together. A second pass forwards the
    sets on each block's best way once more and takes the rows.

    Returns one ({position: the logits row (V,) of the step that unmasked
    it}, worst gap) a block, under the order of SMALLEST worst gap, or
    (None, inf) where no order explains the block."""
    blk = len(blocks[0][1]) if blocks else 0
    # (block, masked set) -> (worst gap so far, the set before, unmasked)
    reached = {(b, frozenset(range(first, blk))): (0.0, None, ())
               for b, (_, _, first) in enumerate(blocks)}
    for size in range(blk, 0, -1):
        states = sorted((b, tuple(sorted(m))) for b, m in reached
                        if len(m) == size)
        for part, logits, finals in _forward_states(step, blocks, states,
                                                    mask_id):
            top, lse, at = (np.asarray(x, np.float64)
                            for x in _row_stats(logits, finals))
            for i, (b, masked) in enumerate(part):
                worst = reached[b, frozenset(masked)][0]
                gap = {j: top[i, j] - at[i, j] for j in masked}
                logc = {j: at[i, j] - lse[i, j] for j in masked}
                lead = max(top[i, j] - lse[i, j] for j in masked)
                for unmask in _options(gap, logc, lead, masked, per_step,
                                       threshold):
                    to = (b, frozenset(masked) - set(unmask))
                    here = max([worst] + [gap[j] for j in unmask])
                    if to not in reached or here < reached[to][0]:
                        reached[to] = (here, frozenset(masked), unmask)
    # each block's best way, backwards from the mask-free set
    ways, on_way = [], []
    for b in range(len(blocks)):
        way, at_set = [], frozenset()
        while (b, at_set) in reached and reached[b, at_set][1] is not None:
            _, before, unmask = reached[b, at_set]
            way.append((before, unmask))
            at_set = before
        whole = (b, frozenset()) in reached
        ways.append(way if whole else None)
        on_way += [(b, tuple(sorted(m))) for m, _ in way] if whole else []
    rows = {}
    for part, logits, _ in _forward_states(step, blocks, on_way, mask_id):
        want = [(i, j) for i, (b, masked) in enumerate(part)
                for j in dict(ways[b])[frozenset(masked)]]
        got = np.asarray(logits[jnp.asarray([w[0] for w in want]),
                                jnp.asarray([w[1] for w in want])])
        for (i, j), row in zip(want, got):
            rows[part[i][0], j] = row
    return [(None, np.inf) if way is None else
            ({j: rows[b, j] for _, unmask in way for j in unmask},
             reached[b, frozenset()][0])
            for b, way in enumerate(ways)]


def logits_rows(params, ids, rows, config):
    """For the benchmark's unchanged check (module docstring): ``ids`` =
    prompt + generated[:-1] and ``rows`` a left-to-right model's shifted
    positions, so the prompt is ``ids[:rows[0] + 1]``. Returns float32
    (len(rows), V), ONE ROW A GENERATED TOKEN, IN ORDER: the logits AT the
    token's own position from the denoising step that unmasked it, under
    the order ``replay`` found; a block no order explains gives ``-inf`` at
    its picked ids, which the check reads as an infinite gap and fails.

    The K, V every block reads are those of the sample's final tokens
    before it, from ONE forward of the sample's whole blocks under the
    block mask (what the commits' forwards keep, all at once).

    The sample's LAST block is not compared (its final token is not in
    ``ids``, and where ``max_new_tokens`` cut it its dropped positions
    never are): its rows are zeros, a gap of 0. The log line says how many
    tokens were and were not compared."""
    blk, per_step, threshold, mask_id = _diffusion(config)
    ids = [int(t) for t in ids]
    n_prompt, n_gen = int(rows[0]) + 1, len(rows)
    vocab = params["embed"]["lm_head"].shape[-1]
    out = np.zeros((n_gen, vocab), np.float32)
    whole = len(ids) // blk * blk
    blocks = [(s, ids[s:s + blk], max(0, n_prompt - s))
              for s in range(n_prompt // blk * blk, whole, blk)]
    kbuf, vbuf = _prefill(params, ids[:whole], config)

    def step(starts, toks):
        return block_forward(params, kbuf, vbuf, starts, toks, config)[0]

    compared = 0
    for (s, final, first), (got, _) in zip(
            blocks, replay(step, blocks, per_step, threshold, mask_id)):
        for j in range(first, blk):
            i = s + j - n_prompt
            if got is None:
                out[i, final[j]] = -np.inf
            else:
                out[i] = got[j]
            compared += 1
    print(f"[sdar_moe_reference] {compared} of {n_gen} generated tokens "
          f"compared, block by block; {n_gen - compared} in the sample's "
          "last block not compared", file=sys.stderr, flush=True)
    return out
