"""frame program. Of the KV positions the window's steps had to read,
summed over layers with each layer's own window, the share the windowed
layers read (``kv_positions_read_window`` over ``kv_positions_read_layers``,
narrow and wide frames together, counted in-graph). Six of eight layers read
at most window + chunk positions a row: the longer the contexts, the
smaller their share."""


def read(ctx):
    c = ctx.get("counters") or {}
    total = sum(c.get(f"kv_positions_read_layers_{s}", 0)
                for s in ("narrow", "wide"))
    if not total:
        return None
    ring = sum(c.get(f"kv_positions_read_window_{s}", 0)
               for s in ("narrow", "wide"))
    return 100.0 * ring / total
