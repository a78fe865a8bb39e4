"""Per-node process spawner.

Analog of ``deepspeed/launcher/launch.py`` (``main:133``): spawns ``nproc``
worker processes with RANK/LOCAL_RANK/WORLD_SIZE set from the env the runner
exported; workers call ``deepspeed_tpu.init_distributed`` which feeds those
into ``jax.distributed.initialize``.

Failure semantics match the reference spawner: any worker exiting non-zero
kills the remaining workers (SIGTERM, then SIGKILL after a grace period),
signals received by the spawner propagate to the whole group, and per-rank
logs can be redirected with ``--log-dir`` (reference ``launch.py:133``
signal handling + per-rank output files).

A chip belongs to one process at a time. This spawner never imports JAX,
so it holds no chip itself; with ``--nproc`` > 1 it hands local chip
``LOCAL_RANK`` to each worker (``TPU_VISIBLE_CHIPS`` and one-chip process
bounds) instead of letting every worker inherit — and fight over — all of
them. Workers pinned to the CPU (``JAX_PLATFORMS=cpu``) and a caller that
set ``TPU_VISIBLE_CHIPS`` itself are left alone.
"""

import argparse
import os
import signal
import subprocess
import sys
import time


def _terminate(procs, grace_s: float = 5.0):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace_s
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def _chip_env(local_rank: int, nproc: int) -> dict:
    """Env that shows worker ``local_rank`` one chip of its own."""
    if (nproc == 1 or os.environ.get("JAX_PLATFORMS") == "cpu"
            or "TPU_VISIBLE_CHIPS" in os.environ):
        return {}
    return {"TPU_VISIBLE_CHIPS": str(local_rank),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--nproc", type=int, default=1)
    parser.add_argument("--log-dir", type=str, default=None,
                        help="write each rank's stdout/stderr to <dir>/rank<N>.log")
    parser.add_argument("script", type=str)
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    rank_offset = int(os.environ.get("RANK_OFFSET", 0))
    procs = []
    logs = []
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(args.nproc):
        env = dict(os.environ, **_chip_env(local_rank, args.nproc))
        env["LOCAL_RANK"] = str(local_rank)
        env["RANK"] = str(rank_offset + local_rank)
        out = None
        if args.log_dir:
            out = open(os.path.join(args.log_dir, f"rank{env['RANK']}.log"), "w")
            logs.append(out)
        procs.append(subprocess.Popen([sys.executable, args.script] + args.script_args,
                                      env=env, stdout=out, stderr=out))

    def handle(signum, _frame):
        _terminate(procs)
        sys.exit(128 + signum)

    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, handle)

    # monitor: first non-zero exit tears down the group (reference behavior
    # — a dead rank would otherwise hang the collective world)
    rc = 0
    live = list(procs)
    try:
        while live:
            for p in list(live):
                ret = p.poll()
                if ret is None:
                    continue
                live.remove(p)
                if ret != 0:
                    sys.stderr.write(
                        f"[launch] rank process pid={p.pid} exited with {ret}; "
                        f"terminating remaining {len(live)} worker(s)\n")
                    _terminate(live)
                    return ret
                rc |= ret
            time.sleep(0.2)
    finally:
        for f in logs:
            f.close()
    return rc


if __name__ == "__main__":
    sys.exit(main())
