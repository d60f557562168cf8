#!/usr/bin/env python3
"""Spreads of a cell's runs, by the rule the bounds are set with.

    python benchmark/rehearsal/spread.py <set1.jsonl> <set2.jsonl> [...]

Each file holds the result lines (the last line of ``run.py``) of one set of
runs of one cell, one per line. For every metric: each set's median and its
spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` over the median), the wider spread,
and five times it, which is what a bound is set to (never under 1%).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import stats  # noqa: E402


def main(paths):
    sets = []
    for path in paths:
        with open(path) as f:
            lines = [json.loads(line) for line in f if line.startswith("{")]
        bad = [r for r in lines if not r["correct"] or r["failed"]]
        print(f"{path}: {len(lines)} runs, {len(bad)} incorrect or with failures")
        sets.append(lines)
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    for name in names:
        spreads, cells = [], []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            if len(values) < 2:
                continue
            med = statistics.median(values)
            spreads.append(stats.spread(values) if med else float("nan"))
            cells.append(f"median {med:.6g} spread {spreads[-1]:.4f} "
                         f"[{min(values):.6g} .. {max(values):.6g}]")
        if spreads:
            print(f"{name}: " + " | ".join(cells)
                  + f" | widest {max(spreads):.4f}, x5 = {5 * max(spreads):.4f}")
    peaks = [r["device"]["memory_peak_bytes"] for runs in sets for r in runs]
    if peaks:
        print(f"memory_peak_bytes: max {max(peaks)}")


if __name__ == "__main__":
    main(sys.argv[1:])
