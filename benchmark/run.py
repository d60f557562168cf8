#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: it builds the cell's configuration from the seed,
checks the system's outputs against the plain reference, warms every shape
the traffic uses (all of that is ``setup_s``), measures for ``--seconds``,
and prints one JSON object as the last line of its standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics (profiler and
the program's tracing off); with ``--trace 1`` they are its per-layer
metrics, taken from spans, counters and a profiler trace of a few seconds.

Which cells exist, what each is made of and which metrics it reports is
read from ``BENCHMARK.json``; the files it names are found under this
directory (``configs/``, ``traffic/``, ``drivers/``, ``layer_metrics/``).
Nothing in this file knows a cell.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--rehearsal`` runs the same code on whatever backend is
there (the CPU, Pallas interpreted) with a toy configuration; its last line
carries ``"rehearsal": true`` and no device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, "benchmark", ".trace")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "distriflow_tpu")):
        print("the system under test (distriflow_tpu/) is not in this "
              "directory: nothing to measure", file=sys.stderr)
        return 2

    from benchmark.lib import compile_meter, harness, peaks, xplane
    from benchmark.lib.harness import say

    registry = harness.Registry()
    cell = registry.cell(args.workload)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if args.rehearsal:
        config, traffic = harness.toy(config), harness.toy(traffic)
    seconds = (args.seconds if args.seconds is not None
               else float(registry.table["run_seconds"]))

    import jax

    say(f"imports: {time.monotonic() - T_PROCESS:.1f}s")
    devices = jax.devices()
    kind = devices[0].device_kind
    on_tpu = devices[0].platform == "tpu"
    say(f"jax {jax.__version__}, platform {devices[0].platform}, device kind "
        f"{kind!r}, {len(devices)} device(s), {os.cpu_count()} host cores")
    if not args.rehearsal and not on_tpu:
        print(f"no TPU: jax reports platform {devices[0].platform!r}; "
              "nothing was built or run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} chip(s), jax found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    say(f"device memory limit: {limit} bytes")
    if args.rehearsal:
        say("REHEARSAL: not a measurement; no device metric will be printed")

    say(f"compile cache: {harness.configure_jax()}")

    run = harness.Run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearsal=args.rehearsal,
        t_process=T_PROCESS, meter=compile_meter.CompileMeter(),
        trace_dir=TRACE_DIR, devices=devices[:cell["chips"]],
        peaks=peaks.peaks_for(kind) if on_tpu else None)
    driver = harness.load_module("drivers", traffic["driver"])
    driver.run(run)
    c = run.compile_setup
    say(f"set-up {run.end_to_end.get('setup_s', float('nan')):.1f}s from process "
        f"start ({time.monotonic() - T_PROCESS:.1f}s the whole run so far); "
        f"programs {c['programs']:.0f} = cache hits {c['cache_hits']:.0f} + "
        f"compiles {c['backend_compiles']:.0f}, {c['backend_s']:.1f}s in the "
        "backend compiler or loading from the cache")

    if run.trace and on_tpu:
        path = xplane.find_xplane(run.trace_dir)
        if path is None:
            print("the profiler wrote no trace", file=sys.stderr)
            return 1
        run.profile = xplane.reduce(xplane.load(path))

    group = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for metric in registry.metrics(group, cell["name"]):
        if run.trace:
            reader = harness.load_module("layer_metrics", metric["name"])
            value = reader.read(run)
        else:
            value = run.end_to_end.get(metric["name"])
        if args.rehearsal:
            # every name, so the rehearsal can check the cell's list; only a
            # count is a number when the run was not on the chip
            if metric["source"] != "program_counter":
                value = None
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        elif value is not None:
            # a reader that found nothing to read leaves its metric out
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics, "device": device}
    if run.profile is not None:
        device["busy_s"] = run.profile.busy_s
        device["window_s"] = run.profile.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.profile.op_seconds],
            "idle_gaps": [[n, s] for n, s in run.profile.idle_gaps]}
    if args.rehearsal:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
