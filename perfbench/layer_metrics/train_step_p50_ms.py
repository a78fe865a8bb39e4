"""train engine / ZeRO. Median host-clock time of a ``train_batch`` call,
batch made on the host, to ``block_until_ready``."""

from perfbench import clientlog


def read(ctx):
    return clientlog.percentile(
        [s * 1e3 for s in ctx.get("step_seconds") or []], 50)
