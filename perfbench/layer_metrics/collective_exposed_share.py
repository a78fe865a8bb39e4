"""comm. Time a collective (all-gather, reduce-scatter, all-reduce: sync,
or the start/done of an async one) holds a chip's op stream, so that no
compute op runs, over the traced window; mean over chips."""


def read(ctx):
    red = ctx.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
