#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one process, one set-up,
one window per rate. Run on the chip, by hand, when the cell is defined
(and again when a change has moved the knee); the table goes into PERF.md
and 0.8 x the knee into the traffic file as ``rate_per_s``.

    python benchmark/rehearsal/knee_sweep.py <cell> <seconds> <rate> [<rate> ...]

The knee is the highest rate at which no request failed and the backlog
(requests due and not yet answered) was no larger over the last third of
the window than over its middle third.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark.lib import compile_meter, harness, stats  # noqa: E402


def backlog(records, t0, lo, hi):
    grid = np.arange(t0 + lo, t0 + hi, 0.1)
    return float(np.mean([sum(1 for r in records if r["due"] <= t < r["recv"])
                          for t in grid]))


def main(argv):
    cell_name, seconds = argv[0], float(argv[1])
    rates = [float(a) for a in argv[2:]]
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    registry = harness.Registry()
    cell = registry.cell(cell_name)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if rehearsal:
        config, traffic = harness.toy(config), harness.toy(traffic)

    import jax

    harness.configure_jax()
    run = harness.Run(
        cell=cell, config=config, traffic=traffic, seed=0, seconds=seconds,
        trace=False, rehearsal=rehearsal, t_process=T_PROCESS,
        meter=compile_meter.CompileMeter(), trace_dir="",
        devices=jax.devices()[:1])
    driver = harness.load_module("drivers", traffic["driver"])
    session = driver.Session(run)
    rows = []
    try:
        for i, rate in enumerate(rates):
            reqs = session.requests(1000 + i, seconds, rate)
            before = len(session.callers.records)
            mark = run.meter.mark()
            t0 = session.open_window(reqs)
            records = session.callers.records[before:]
            for rec in records:
                driver._latencies(rec)
            failed = sum(1 for r in records if not r["ok"])
            ttft = [r["ttft"] for r in records if "ttft" in r]
            tpot = [r["tpot"] for r in records if "tpot" in r]
            mid = backlog(records, t0, seconds / 3, 2 * seconds / 3)
            last = backlog(records, t0, 2 * seconds / 3, seconds)
            drain = max(r["recv"] for r in records) - (t0 + seconds)
            rows.append((rate, len(records), failed, stats.median(ttft),
                         stats.percentile(ttft, 95), stats.median(tpot),
                         stats.percentile(tpot, 95), mid, last, drain,
                         run.meter.since(mark)["programs"]))
            print("rate %.2f/s: n %d failed %d ttft p50 %.0f p95 %.0f ms, tpot "
                  "p50 %.1f p95 %.1f ms, backlog middle third %.1f last third "
                  "%.1f, drained %.1fs after the window, programs %d" % rows[-1],
                  flush=True)
    finally:
        session.close()
    held = [r[0] for r in rows if r[2] == 0 and r[8] <= r[7]]
    print("admit shapes seen:", sorted(set(
        line.split(" took")[0] for line in session.log[session.n_warm_log:]
        if line.startswith("admit["))))
    print(f"knee by the rule: {max(held) if held else None} requests/s")


if __name__ == "__main__":
    main(sys.argv[1:])
