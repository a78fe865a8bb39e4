"""frame program. Share of the window's steps that ran in frames at the
prefill width (frames that consumed prompt tokens), from the frames the
monitor saw: (t, emitted, prefill, steps), ``steps`` the count the host
planned for the frame (``frame_steps_last``). Where every frame runs the
same steps this is ``wide_frame_share``; a wide frame that ends with its
last prefilling row runs fewer, and the two part."""


def read(ctx):
    frames = ctx.get("frames")
    if not frames:
        return None
    steps = sum(f[3] for f in frames)
    if not steps:
        return None
    return 100.0 * sum(f[3] for f in frames if f[2] > 0) / steps
