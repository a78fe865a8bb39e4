"""Rank-aware logging utilities.

TPU-native analog of the reference's ``deepspeed/utils/logging.py`` (log_dist,
logger setup). Rank filtering uses the JAX process index instead of
torch.distributed ranks.
"""

import functools
import logging
import os
import sys

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


@functools.lru_cache(None)
def _create_logger(name="DeepSpeedTPU", level=logging.INFO):
    logger_ = logging.getLogger(name)
    logger_.setLevel(level)
    logger_.propagate = False
    if not logger_.handlers:
        handler = logging.StreamHandler(stream=sys.stdout)
        handler.setFormatter(
            logging.Formatter(
                "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s",
                datefmt="%Y-%m-%d %H:%M:%S",
            ))
        logger_.addHandler(handler)
    return logger_


level = LOG_LEVELS.get(os.environ.get("DS_TPU_LOG_LEVEL", "info").lower(), logging.INFO)
logger = _create_logger(level=level)


def _process_index():
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


def should_log_on_rank(ranks=None):
    """True if this process should log for the given rank filter (None = rank 0 only
    by convention of the reference's log_dist; [-1] = all ranks)."""
    if ranks is None:
        ranks = [0]
    if -1 in ranks:
        return True
    return _process_index() in ranks


def log_dist(message, ranks=None, level=logging.INFO):
    """Log ``message`` only on the processes listed in ``ranks``.

    Mirrors the reference API: ranks=None → rank 0; ranks=[-1] → all ranks.
    """
    if should_log_on_rank(ranks):
        logger.log(level, f"[Rank {_process_index()}] {message}")


def logs_to_stderr():
    """Point the package logger at stderr. Its default handler streams to
    stdout (reference behavior); entry points whose stdout is a JSON
    contract (``bin/dstpu_serve``, the benches, ``chip_smoke.py``) call
    this before anything logs."""
    for handler in logger.handlers:
        if hasattr(handler, "stream"):
            handler.stream = sys.stderr


def print_rank_0(message):
    if _process_index() == 0:
        print(message, flush=True)


def warning_once(message, _seen=set()):
    if message not in _seen:
        _seen.add(message)
        logger.warning(message)
