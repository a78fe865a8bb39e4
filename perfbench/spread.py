#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over repeated runs, as the driver
reads it: for each metric the distance between the quartiles over the
median, per set of runs, and the wider of the sets.

    python3 perfbench/spread.py SET1.jsonl SET2.jsonl ...

Each file holds one result line (the benchmark's last line of stdout) per
run of one cell. Prints per metric the medians, the spreads, and five
times the widest spread (the contract's rule for a bound; never under 1%).

    python3 perfbench/spread.py --admit RUNS.jsonl ...

pools the files' runs (one cell, one commit) and reads every split of them
into two equal sets as the driver would: for each metric and each bound
from 1% to 10%, the share of splits in which the wider spread is under
half the bound (``tight``), the bound is at most eight times the wider
spread or 1% (``loose``; over several cells only the widest has to pass),
and the second median is within the bound of the first (``shift``). A
metric whose runs are mostly alike and now and then far off passes no
bound in most splits, whatever five times its usual spread says.
"""

import itertools
import json
import sys

from clientlog import percentile


def read(path):
    runs = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line.startswith("{") and '"metrics"' in line:
                runs.append(json.loads(line))
    return runs


def spread(values):
    med = percentile(values, 50)
    return (percentile(values, 75) - percentile(values, 25)) / med


def main(paths):
    sets = [read(p) for p in paths]
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for name in names:
        meds, spreads = [], []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if values:
                meds.append(percentile(values, 50))
                spreads.append(spread(values))
        widest = max(spreads)
        print(f"{name}: medians {[round(m, 3) for m in meds]} spreads "
              f"{[round(100 * s, 2) for s in spreads]} % -> 5x widest "
              f"{max(1.0, 500 * widest):.1f} %")
    bad = [r for runs in sets for r in runs
           if not r["correct"] or r["failed"]]
    print(f"runs {[len(runs) for runs in sets]}, not correct or with "
          f"failures: {len(bad)}")


def admit(paths):
    runs = [r for p in paths for r in read(p)]
    half = len(runs) // 2
    splits = [(c, [i for i in range(2 * half) if i not in c])
              for c in itertools.combinations(range(2 * half), half)
              if 0 in c]
    print(f"{len(runs)} runs, {len(splits)} splits into two sets of {half}; "
          "share of splits passing tight/loose/shift")
    for name in sorted({m for r in runs for m in r["metrics"]} - {"setup_s"}):
        values = [r["metrics"][name]["value"] for r in runs]
        read_as = []
        for a, b in splits:
            a, b = [values[i] for i in a], [values[i] for i in b]
            read_as.append((max(spread(a), spread(b)),
                            abs(percentile(b, 50) / percentile(a, 50) - 1)))
        cols = []
        for bound in (0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.08, 0.1):
            n = len(read_as)
            tight = sum(w < bound / 2 for w, _ in read_as) / n
            loose = sum(bound <= max(0.01, 8 * w) for w, _ in read_as) / n
            shift = sum(d <= bound for _, d in read_as) / n
            cols.append(f"{bound:.2f}: {tight:.2f}/{loose:.2f}/{shift:.2f}")
        print(f"{name}: " + "  ".join(cols))


if __name__ == "__main__":
    if sys.argv[1:2] == ["--admit"]:
        admit(sys.argv[2:])
    else:
        main(sys.argv[1:])
