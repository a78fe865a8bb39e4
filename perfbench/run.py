#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the workload up in ``BENCHMARK.json`` and everything it names in
files of their own (see ``harness.py``); holds no cell's name. Warms up
every shape the cell's traffic uses (set-up), measures for ``--seconds``,
checks the outputs, and prints one JSON object as the last line of stdout:
``correct, attempted, failed, metrics, device`` and, traced, ``breakdown``.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.

Exits nonzero, with no result line, when JAX finds no accelerator or fewer
chips than the cell asks for, or when the program is not in the checkout.
``--rehearse`` walks the same code on whatever JAX has (a CPU rehearsal
with a tiny configuration): its result line names the platform and carries
only counts, never a time or a rate.
"""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend; counts only in the result line")
    ap.add_argument("--benchmark", default=None,
                    help="another BENCHMARK.json (tests and rehearsals)")
    args = ap.parse_args(argv)

    harness.require_program()
    cell = harness.find_cell(args.workload, args.benchmark)
    devices = harness.require_devices(cell.chips, args.rehearse)
    harness.cache_every_program()
    os.makedirs(harness.RUN_DIR, exist_ok=True)
    run = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, t_start=T_START,
        devices=devices, compiles=harness.CompileCounter(),
        run_dir=os.path.join(harness.RUN_DIR, cell.name))
    os.makedirs(run.run_dir, exist_ok=True)
    driver = cell.module("drivers", cell.config["kind"])
    result = driver.run(run)

    section, kind_dir = (("per_layer", "layer_metrics") if run.trace
                         else ("end_to_end", "e2e_metrics"))
    metrics = harness.read_metrics(cell, section, kind_dir, result["ctx"],
                                   args.rehearse)
    trace_red = result["ctx"].get("trace")
    breakdown = None
    if run.trace and trace_red:
        breakdown = {"device_ops": trace_red["device_ops"],
                     "idle_gaps": trace_red["idle_gaps"]}
    harness.print_result(
        result["correct"], result["attempted"], result["failed"], metrics,
        harness.device_block(devices, trace_red if run.trace else None),
        breakdown)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
