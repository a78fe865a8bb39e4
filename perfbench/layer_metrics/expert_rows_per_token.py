"""routed experts. Rows sent through experts per live token and layer,
from the program's counters over the window: ``expert_rows`` over
(``prefill_tokens`` + ``target_forwards``) x layers, the live tokens as
``useful_position_share`` counts them. Exactly ``num_experts_per_tok``
when no dead position reaches an expert."""


def read(ctx):
    c = ctx.get("counters") or {}
    live = c.get("prefill_tokens", 0) + c.get("target_forwards", 0)
    if not c.get("expert_rows") or not live:
        return None
    return c["expert_rows"] / (live * ctx["config"]["num_hidden_layers"])
