#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip.

    python3 perfbench/sweep.py --workload <cell> --rates 0.5 0.64 0.8 1.0 ... \
        [--seconds 51] [--seed 0] [--out chiprun_out/sweep.json]

One server, warmed once; then the cell's own traffic at each rate in turn
(the traffic file's rate replaced, everything else as it is), each with
the traffic before the window, a window and a full drain. A rate is
sustained by ISSUE 22's rule: requests completed in the window over
requests due in it >= 0.97, and no more requests await their first token
at the window's end than at its middle. Both are read from the client's
log (``clientlog.steadiness``). The knee is the highest sustained rate;
the traffic file then holds 0.8 x the knee as a number, and the benchmark
itself never searches. Stops after the first rate that fails unless told
to keep going.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import clientlog, harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark", default=None)
    ap.add_argument("--keep-going", action="store_true",
                    help="do not stop at the first rate that fails")
    args = ap.parse_args()

    harness.require_program()
    cell = harness.find_cell(args.workload, args.benchmark)
    harness.require_devices(cell.chips, args.rehearse)
    harness.cache_every_program()
    serve = cell.module("drivers", "serve")
    config, traffic = cell.config, cell.traffic
    run_dir = os.path.join(harness.RUN_DIR, f"sweep-{cell.name}")
    os.makedirs(run_dir, exist_ok=True)
    dstpu = serve.load_dstpu_serve()
    svc = dstpu.build_service(dstpu.parse_args(
        serve.serve_argv(config, args.seed, run_dir)))
    rows = []
    try:
        port, vocab = svc.edge.edge_port, config["vocab_size"]
        generator = cell.module("generators", traffic["generator"])
        t0 = float(traffic["pre_window_s"])
        t1 = t0 + args.seconds

        def plan_at(rate):
            return generator.plan(
                dict(traffic, arrivals=dict(traffic["arrivals"], rate=rate)),
                traffic["schedule_seed"], t1)

        serve.warm_up(svc, config, args.seed, plan_at(max(args.rates)))
        for rate in args.rates:
            plan = plan_at(rate)
            plan.update(stop_t=t1, deadline_t=t1 + 240.0)
            child, _, log_path = serve.start_client(
                run_dir, plan, port, args.seed, vocab)
            tail = serve.finish_client(child, t1 + 300.0)
            records = clientlog.read_log(log_path)
            ttft = clientlog.ttfts_ms(records, t0, t1)
            tpot = clientlog.tpots_ms(records, t0, t1)
            row = dict(
                clientlog.steadiness(records, t0, t1), rate=rate,
                failed=sum(r["status"] != "ok" for r in records),
                unfinished=tail["unfinished"],
                ttft_mean_ms=sum(ttft) / max(1, len(ttft)),
                ttft_p90_ms=clientlog.tail_quantile(ttft, 90),
                tpot_mean_ms=clientlog.mean_gap_ms(records, t0, t1),
                tpot_p90_ms=clientlog.tail_quantile(tpot, 90),
                tokens_per_s=clientlog.completed_tokens(records, t0, t1)
                / args.seconds,
                drained_s=max([r["last_t"] for r in clientlog.ok(records)],
                              default=t1) - t1)
            row["sustained"] = (
                row["completed"] >= 0.97 * row["due"]
                and row["waiting"][2] <= row["waiting"][1]
                and not row["failed"] and not row["unfinished"])
            rows.append(row)
            print(json.dumps(row), flush=True)
            if not row["sustained"] and not args.keep_going:
                break
    finally:
        svc.edge.shutdown()
        svc.driver.stop()
    good = [r["rate"] for r in rows if r["sustained"]]
    result = {"workload": cell.name, "seconds": args.seconds,
              "knee": max(good) if good else None, "rows": rows}
    print(json.dumps({"knee": result["knee"]}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
