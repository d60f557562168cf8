"""``decode_overlap_share.serve`` read in a traced CPU rehearsal of each cell
that lists it: a rehearsal's line carries no share (it is not a count), so
the reader's own earlier line is what says it found the ``overlapped``
field on the window's ``decode_iter`` spans.

    JAX_PLATFORMS=cpu python -m pytest benchmark/rehearsal/test_overlap_share.py -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import harness  # noqa: E402

METRIC = "decode_overlap_share.serve"
CELLS = next(m["workloads"] for m in harness.Registry().table["per_layer"]
             if m["name"] == METRIC)
SAID = re.compile(r"decode dispatches in the window: (\d+), enqueued while "
                  r"the one before was in flight: (\d+)")


@pytest.mark.parametrize("cell", CELLS)
def test_overlap_share_reads_in_a_rehearsal(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2900000033", "--seconds", "3",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    found = SAID.search(proc.stdout)
    assert found, proc.stdout[-3000:]
    dispatches, ahead = map(int, found.groups())
    assert dispatches > 0 and 0 <= ahead <= dispatches
    assert METRIC in proc.stdout.strip().splitlines()[-1]
