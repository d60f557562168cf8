#!/usr/bin/env python3
"""Show that a cell's comparison with its reference tells the precision the
configuration states from the nearest below it: the driver's own check on
one set of weights, the program as stated and lowered. Run on the chip, by
hand, when a limit of the comparison is set or moved; both sets of readings
go into PERF.md beside the limits.

    python benchmark/rehearsal/precision_control.py <cell> [<seed>]

Exits 0 when the stated program is ``correct`` and the lowered one is not.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import compile_meter, harness  # noqa: E402


def main(argv):
    rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    registry = harness.Registry()
    cell = registry.cell(argv[0])
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if rehearsal:
        config, traffic = harness.toy(config), harness.toy(traffic)

    import jax

    harness.configure_jax()
    run = harness.Run(
        cell=cell, config=config, traffic=traffic,
        seed=int(argv[1]) if len(argv) > 1 else 0, seconds=0.0, trace=False,
        rehearsal=rehearsal, t_process=T_PROCESS,
        meter=compile_meter.CompileMeter(), trace_dir="",
        devices=jax.devices()[:1])
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.precision_control(run)
    print(f"stated correct {got['stated']}, lowered correct {got['lowered']}")
    return 0 if got["stated"] and not got["lowered"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
