"""KV manager. Most pages in use at a frame boundary over the pool's
pages. The program's gauge is a running maximum since the server started,
so it covers warm-up and the seconds before the window as well."""


def read(ctx):
    g = ctx.get("gauges") or {}
    if not ctx.get("kv_blocks") or "kv_blocks_in_use_peak" not in g:
        return None
    return 100.0 * g["kv_blocks_in_use_peak"] / ctx["kv_blocks"]
