"""Operations and bytes the algorithm needs, from shapes alone. The
yardstick's own arithmetic: nothing here reads the program.

The 6*N*D rule for training and the peak table's use follow the repo's
``bench.py``; the attention terms, the bytes and the serving side are new.
A configuration is the dict of a ``perfbench/configs/*.json`` file, with
the keys of the model's public ``config.json``.
"""


def dims(config):
    e = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"E": e, "L": config["num_hidden_layers"], "H": h,
            "KVH": config.get("num_key_value_heads") or h,
            "D": config.get("head_dim") or e // h,
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "W": config.get("sliding_window") or 0}


def layer_matmul_params(d):
    """Weights of one layer that a token is multiplied by: q, k, v, o and
    the gated MLP's three matrices."""
    attn = d["E"] * d["H"] * d["D"] * 2 + d["E"] * d["KVH"] * d["D"] * 2
    return attn + 3 * d["E"] * d["F"]


def matmul_params(d):
    """N of the 6*N*D rule: every layer's matrices and the LM head. The
    embedding is a lookup, not a product."""
    return d["L"] * layer_matmul_params(d) + d["E"] * d["V"]


def param_count(d):
    """All parameters: N, the embedding, two norms a layer and the last."""
    return (matmul_params(d) + d["V"] * d["E"] + 2 * d["L"] * d["E"]
            + d["E"])


def visible(d, ctx):
    """Keys a query at context length ``ctx`` (itself included) attends:
    all of them, or the sliding window's worth."""
    return min(ctx, d["W"]) if d["W"] else ctx


def visible_sum(d, start, stop):
    """Sum of ``visible`` over the queries at positions start..stop-1."""
    return sum(visible(d, i + 1) for i in range(start, stop))


def causal_visible_sum(d, seq_len):
    """``visible_sum(d, 0, seq_len)`` in closed form."""
    w = d["W"] if d["W"] and d["W"] < seq_len else seq_len
    return w * (w + 1) // 2 + (seq_len - w) * w


def attn_flops(d, n_visible):
    """QK^T and PV over ``n_visible`` query-key pairs, all layers: 2 FLOPs
    a multiply-add, two products."""
    return 4 * d["H"] * d["D"] * d["L"] * n_visible


def kv_bytes(d, n_visible, bytes_per_value=2):
    """Bytes of keys and values read for ``n_visible`` cached positions,
    all layers."""
    return 2 * d["KVH"] * d["D"] * d["L"] * bytes_per_value * n_visible


def weight_bytes(d, bytes_per_param=2):
    """What one step reads of the weights: every matrix once (the
    embedding only by the row)."""
    return bytes_per_param * (matmul_params(d) + 2 * d["L"] * d["E"]
                              + d["E"])


def train_step_flops(d, rows, seq_len):
    """Forward and backward of one step: 6*N per token, plus attention
    (forward 4*H*D per visible pair per layer, backward twice that).
    Recomputation is not counted."""
    tokens = rows * seq_len
    return (6 * matmul_params(d) * tokens
            + 3 * attn_flops(d, rows * causal_visible_sum(d, seq_len)))


def request_work(d, prompt_len, n_out, chunk=128, bytes_per_value=2):
    """Useful work of one served request, split by phase: FLOPs of its
    prompt's and its generated tokens' attention, and the KV bytes an
    ideal server reads for them (once per prompt chunk, once per generated
    token)."""
    prefill_visible = causal_visible_sum(d, prompt_len)
    decode_visible = visible_sum(d, prompt_len, prompt_len + max(n_out - 1, 0))
    chunk_ends = list(range(chunk, prompt_len, chunk)) + [prompt_len]
    prefill_kv = sum(visible(d, c) for c in chunk_ends)
    return {"prefill_attn_flops": attn_flops(d, prefill_visible),
            "decode_attn_flops": attn_flops(d, decode_visible),
            "prefill_kv_bytes": kv_bytes(d, prefill_kv, bytes_per_value),
            "decode_kv_bytes": kv_bytes(d, decode_visible, bytes_per_value)}


def serve_span_floor(d, peaks, *, prefill_tokens, decode_tokens, steps,
                     requests, bytes_per_param=2):
    """The least time the chip could take for a span of serving: the larger
    of useful FLOPs over peak FLOP/s and needed bytes over peak bytes/s.

    ``prefill_tokens``/``decode_tokens``/``steps`` are the span's counters
    (prompt tokens consumed, tokens emitted, model steps run: every step
    reads the weights once). ``requests`` is a list of (prompt_len, n_out)
    that stands for the span's traffic: attention work per token is its
    mean. Returns (seconds, "compute" | "memory", flops, bytes)."""
    work = [request_work(d, p, n) for p, n in requests]
    p_tok = max(1, sum(p for p, _ in requests))
    d_tok = max(1, sum(max(n - 1, 0) for _, n in requests))
    per_prefill_flops = sum(w["prefill_attn_flops"] for w in work) / p_tok
    per_decode_flops = sum(w["decode_attn_flops"] for w in work) / d_tok
    per_prefill_kv = sum(w["prefill_kv_bytes"] for w in work) / p_tok
    per_decode_kv = sum(w["decode_kv_bytes"] for w in work) / d_tok
    layer_flops = 2 * d["L"] * layer_matmul_params(d)
    head_flops = 2 * d["E"] * d["V"]
    flops = (layer_flops * (prefill_tokens + decode_tokens)
             + head_flops * decode_tokens
             + per_prefill_flops * prefill_tokens
             + per_decode_flops * decode_tokens)
    nbytes = (steps * weight_bytes(d, bytes_per_param)
              + per_prefill_kv * prefill_tokens
              + per_decode_kv * decode_tokens)
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return max(t_flops, t_bytes), bound, flops, nbytes
