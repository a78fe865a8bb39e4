"""frame program. Programs XLA compiled inside the window: every one, by
JAX's monitoring event (the frame programs, which
``runner.compile_count_total()`` counts and which make a run incorrect, and
the small programs host code dispatches between frames). Should be 0."""


def read(ctx):
    return ctx.get("window_compiles")
