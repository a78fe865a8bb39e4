"""frame program. Device busy time over the whole frames of the traced
span, per thousand tokens those frames processed (prompt tokens consumed
plus tokens emitted, counted in-graph)."""


def read(ctx):
    span = ctx.get("span")
    if not span:
        return None
    tokens = span["prefill_tokens"] + span["emitted_tokens"]
    return span["busy_s"] * 1e3 / (tokens / 1000.0) if tokens else None
