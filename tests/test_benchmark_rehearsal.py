"""The benchmark's own static rehearsal cases, collected into tier-1.

The cases live in ``benchmark/rehearsal/`` (the benchmark's directories hold
the benchmark and nothing else); importing them here lets the one tier-1
command guard the harness that decides every PR: added files are picked up,
a CPU run reports no time, the roofline counts equal the kernels' own tally.
The six ``test_cell_rehearsal`` cases start a subprocess each and sit in
``test_benchmark_cells.py``, so xdist's ``--dist loadfile`` gives them a
worker of their own.
"""

import pytest

pytest.register_assert_rewrite("benchmark.rehearsal.test_benchmark",
                               "benchmark.rehearsal.test_annotations")

from benchmark.lib.peaks import PEAKS  # noqa: E402
from benchmark.rehearsal.test_annotations import *  # noqa: E402,F401,F403
from benchmark.rehearsal.test_benchmark import *  # noqa: E402,F401,F403
from distriflow_tpu.train.sync import SyncTrainer  # noqa: E402

del test_cell_rehearsal  # noqa: F821 -- collected by test_benchmark_cells.py


def test_the_two_peak_tables_agree_on_the_v5e():
    """The package may not import ``benchmark/``, so its MFU table
    (``SyncTrainer.mfu``) and the benchmark's are two; the chip's entry is
    held equal here."""
    v5e = PEAKS["TPU v5 lite"]["bf16_flops_per_s"]  # jax's device_kind
    assert SyncTrainer.PEAK_BF16_FLOPS["v5 lite"] == v5e
    assert SyncTrainer.PEAK_BF16_FLOPS["v5e"] == v5e
