"""KV manager. Bytes of KV pages the live sequences hold per token of
context that admission reserved for them, over the window's frames: the
program's ``kv_bytes_in_use`` (pages in use x bytes a page, over the cache
kinds) summed over frames, over its ``context_tokens_reserved`` (the tokens
the table kind's pages hold) summed likewise. One pool under one table for
all 8 layers of the Mellum2 cut is 16,384 B a token whatever the context; a
ring behind the window holds the windowed layers' share constant a slot."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("context_tokens_reserved_sum"):
        return None
    return c["kv_bytes_in_use_sum"] / c["context_tokens_reserved_sum"]
