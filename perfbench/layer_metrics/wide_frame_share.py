"""scheduler / admission. Share of the window's frames that ran at the
prefill width (a frame is wide when any slot prefills: it consumed prompt
tokens). Every decoding row emits one token per wide step."""


def read(ctx):
    frames = ctx.get("frames")
    if not frames:
        return None
    return 100.0 * sum(1 for f in frames if f[2] > 0) / len(frames)
