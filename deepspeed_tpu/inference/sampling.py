"""Token sampling strategies for generation (greedy, temperature, top-k,
top-p). All pure functions usable inside jit/scan."""

import jax
import jax.numpy as jnp


def sample_logits_per_row(logits, rng, temps):
    """Row-wise sampling for the device-resident serving frame: ``temps``
    (B,) float32 rides in the frame carry, so rows with different sampling
    settings share one batch. Rows with temp <= 0 take argmax (bit-identical
    to the greedy host path); the rest sample at their own temperature.
    logits: (B, V) → token ids (B,) int32."""
    greedy_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy_toks, sampled)


def block_unmask(logits, masked, rng, temps, *, per_step: int,
                 threshold=None):
    """One denoising step's choice for rows that generate by diffusion over
    blocks: WHAT each masked position would become and WHICH of them are
    unmasked now, entirely in-graph.

    logits: (B, L, V) float32, the model's scores of the token AT each of
    the block's L positions; masked: (B, L) bool; temps: (B,). Returns
    (x0 (B, L) int32, unmask (B, L) bool).

    ``x0`` is the argmax of a position's logits (a row with temp > 0: a
    draw from softmax(l / temp), ``rng`` None when every row is greedy);
    its confidence is softmax(l)[x0], the untempered probability.
    ``unmask`` holds the ``min(per_step, masked positions)`` masked
    positions of largest confidence (ties: the lowest position;
    "low_confidence_static"). ``threshold`` (a float;
    "low_confidence_dynamic"): every masked position whose confidence
    passes it, where those are at least ``per_step``. Confidences are
    compared as log-probabilities, x0's logit minus the logsumexp: the
    same order, no underflow over a wide vocabulary."""
    b, l, v = logits.shape
    flat = logits.reshape(b * l, v)
    if rng is None:
        x0 = jnp.argmax(flat, axis=-1).astype(jnp.int32)
    else:
        x0 = sample_logits_per_row(flat, rng, jnp.repeat(temps, l))
    logc = (jnp.take_along_axis(flat, x0[:, None], axis=1)[:, 0]
            - jax.nn.logsumexp(flat, axis=-1)).reshape(b, l)
    x0 = x0.reshape(b, l)
    logc = jnp.where(masked, logc, -jnp.inf)
    # rank of a position among its block's by confidence, ties to the left
    # (L is small: the L x L comparison is cheaper than a sort)
    at = jnp.arange(l)
    ahead = (logc[:, None, :] > logc[:, :, None]) | (
        (logc[:, None, :] == logc[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    unmask = masked & (jnp.sum(ahead, axis=-1) < per_step)
    if threshold is not None:
        passing = masked & (logc > jnp.log(jnp.float32(threshold)))
        unmask = jnp.where(
            (jnp.sum(passing, axis=1) >= per_step)[:, None], passing, unmask)
    return x0, unmask


def speculative_verify_per_row(target_logits, draft_logits, draft_toks, temps,
                               rng=None):
    """Per-row draft verification for the speculative serving frame: decides
    how many drafted tokens survive and what the replacement/bonus token is,
    entirely in-graph (acceptance never syncs the host).

    target_logits: (B, G+1, V) the target model's logits at the G+1 verified
    positions (position 0 is the committed last token; positions 1..G are the
    drafted tokens). draft_logits: (B, G, V) the draft's proposal logits.
    draft_toks: (B, G) the proposed tokens. temps: (B,) per-row temperatures.

    Returns (n_accept (B,) int32 in [0, G], replacement (B,) int32): the
    count of leading accepted drafts and the token to emit right after them —
    the target's continuation on full acceptance, its correction at the first
    rejected position otherwise.

    Rows with temp <= 0 use exact greedy token-match (accept while the draft
    token equals the target argmax), which makes the speculative output
    bit-identical to non-speculative greedy decoding. Rows with temp > 0 use
    Leviathan-style rejection sampling: accept q_j with probability
    min(1, p_t(q_j) / p_d(q_j)); on the first rejection the replacement is
    drawn from the normalized residual max(p_t - p_d, 0), which preserves the
    target distribution exactly. ``rng=None`` means all rows are greedy and
    no randomness is consumed."""
    g = draft_toks.shape[1]
    tgt_greedy = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # (B, G+1)
    match = (draft_toks == tgt_greedy[:, :g]).astype(jnp.int32)
    # leading-ones count: cumprod zeroes everything after the first mismatch
    greedy_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1).astype(jnp.int32)
    greedy_repl = jnp.take_along_axis(tgt_greedy, greedy_acc[:, None],
                                      axis=1)[:, 0]
    if rng is None:
        return greedy_acc, greedy_repl
    r_u, r_res = jax.random.split(rng)
    t = jnp.maximum(temps, 1e-6)[:, None, None]
    p_t = jax.nn.softmax(target_logits.astype(jnp.float32) / t, axis=-1)
    p_d = jax.nn.softmax(draft_logits.astype(jnp.float32) / t, axis=-1)
    pt_q = jnp.take_along_axis(p_t[:, :g], draft_toks[..., None], -1)[..., 0]
    pd_q = jnp.take_along_axis(p_d, draft_toks[..., None], -1)[..., 0]
    u = jax.random.uniform(r_u, draft_toks.shape)
    accept = (u * pd_q <= pt_q).astype(jnp.int32)   # accept w.p. min(1, pt/pd)
    samp_acc = jnp.sum(jnp.cumprod(accept, axis=1), axis=1).astype(jnp.int32)
    n_acc = jnp.where(temps <= 0.0, greedy_acc, samp_acc)
    # residual at the first rejected position; the bonus position (n_acc == G)
    # has no draft distribution, so pad p_d with zeros there and the residual
    # degenerates to p_t itself
    pd_pad = jnp.concatenate([p_d, jnp.zeros_like(p_d[:, :1])], axis=1)
    idx = n_acc[:, None, None]
    p_t_at = jnp.take_along_axis(p_t, idx, axis=1)[:, 0]        # (B, V)
    p_d_at = jnp.take_along_axis(pd_pad, idx, axis=1)[:, 0]
    res = jnp.maximum(p_t_at - p_d_at, 0.0)
    # p_d == p_t exactly (self-draft) leaves a zero residual: fall back to p_t
    res = jnp.where(jnp.sum(res, axis=-1, keepdims=True) > 0.0, res, p_t_at)
    sampled_repl = jax.random.categorical(
        r_res, jnp.log(res + 1e-30), axis=-1).astype(jnp.int32)
    repl = jnp.where(temps <= 0.0, greedy_repl, sampled_repl)
    return n_acc, repl


def sample_logits(logits, rng, *, temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 1.0, greedy: bool = False):
    """logits: (B, V) → token ids (B,) int32."""
    if greedy or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
