"""device. Of the traced frames' idle seconds with a request live, the
share whose gap lies under one of the program's own spans
(``serve/<phase>``, ``serve_frame/...``): every gap, not the longest ten.
Gaps of an empty server (``serve/idle``) count on neither side: the
traced run's idle table reports them."""

from perfbench import scope_reduce


def read(ctx):
    red = scope_reduce.for_ctx(ctx)
    if not red:
        return None
    idle = red["idle_s"] - red["empty_s"]
    if idle <= 0:
        return None
    named = idle - red["idle_by_span"].get(scope_reduce.UNATTRIBUTED, 0.0)
    return scope_reduce.share(named, idle)
