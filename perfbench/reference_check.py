"""The comparison that decides ``correct`` for served tokens: copied from
the repo's ``chip_smoke.py`` ``reference_gaps`` (teacher-forced logit gap),
with the configuration's own plain reference in place of the program's
``model.apply``.

With random weights the largest logit changes on rounding, so sampled
tokens are not compared. Instead, for each generated token: how far its
REFERENCE logit sits below the reference maximum at that position (0 = the
reference's own greedy choice). Covers the first token (prefill frames)
and every later one (decode frames, through the paged cache).
"""

import numpy as np

# bf16 keeps 8 bits of mantissa: logits of magnitude ~4-8 resolve to ~0.03,
# and the served (bf16, paged) and reference (float32) paths round
# differently through every layer (PR 21's stated reason and value). Far
# tighter than a wrong mask, position or page would pass: those move logits
# by whole units.
LOGIT_TOL = 0.25


def reference_gaps(reference, params, config, prompt, generated):
    """Gap per generated token; only the generated positions' rows are
    taken from the reference, against the whole context."""
    ids = list(prompt) + list(generated[:-1])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(generated))
    logits = reference.logits_rows(params, ids, rows, config)
    return logits.max(-1) - logits[np.arange(len(generated)),
                                   np.asarray(generated)]


def check(reference, params, config, samples):
    """``samples``: [(name, prompt, generated)]. Returns (ok, {name: max
    gap})."""
    worst = {}
    for name, prompt, generated in samples:
        gaps = reference_gaps(reference, params, config, prompt, generated)
        worst[name] = float(gaps.max()) if np.isfinite(gaps).all() \
            else float("inf")
    return all(g <= LOGIT_TOL for g in worst.values()), worst
