"""scheduler / admission. Host milliseconds between two frames, per
frame: the program's ``host_<phase>_ns`` counters over the window, over
``frames``. Every phase but the two that wait: ``fetch`` (for the chip) and
``idle`` (on an empty server, for a request)."""

from perfbench import scope_reduce


def read(ctx):
    phases = scope_reduce.phase_ms_per_frame((ctx or {}).get("counters")
                                             or {})
    if not phases:
        return None
    return sum(v for k, v in phases.items() if k not in scope_reduce.WAITS)
