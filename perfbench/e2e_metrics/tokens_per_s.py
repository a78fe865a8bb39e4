"""Tokens the client saw completed inside the window, over the window: a
generated token when it arrives, a prompt spread evenly between the
request's send and its first token (``clientlog.completed_tokens``)."""

from perfbench import clientlog


def read(ctx):
    done = clientlog.completed_tokens(ctx["records"], ctx["t0"], ctx["t1"])
    return done / ctx["window_s"] if done else None
