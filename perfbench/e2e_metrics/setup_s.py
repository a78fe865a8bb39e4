"""Process start to the first measured second: loading, warming up and, in
a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
