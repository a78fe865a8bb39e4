"""Training "traffic": a fresh seeded batch for every step, made on the
host and handed to ``train_batch`` there, so input staging is in the loop."""

import numpy as np

from perfbench import draws


def plan(params, schedule_seed, horizon_s):
    del schedule_seed, horizon_s
    return {"mode": "train", "seq_len": int(params["seq_len"]),
            "micro_batch_per_chip": int(params["micro_batch_per_chip"]),
            "gradient_accumulation_steps":
                int(params.get("gradient_accumulation_steps", 1)),
            "trace_steps": int(params.get("trace_steps", 3))}


def batch_for(seed, step, rows, seq_len, vocab):
    """Step ``step``'s batch: uniform token ids, labels the next token."""
    ids = draws.stream(seed, 3, step).integers(
        0, vocab, (rows, seq_len + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
