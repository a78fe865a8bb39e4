"""device. Peak bytes in use on the fullest chip, as the backend reports
them (``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
