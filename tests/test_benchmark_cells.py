"""Each cell of ``BENCHMARK.json`` rehearsed end to end on the CPU, traced
and untraced: ``benchmark/rehearsal/test_benchmark.py::test_cell_rehearsal``
collected into tier-1 (see ``test_benchmark_rehearsal.py``). One subprocess a
case, 12-21 s each; a file of their own so ``--dist loadfile`` runs them
beside the other files, not in front of the static cases."""

import pytest

pytest.register_assert_rewrite("benchmark.rehearsal.test_benchmark")

from benchmark.rehearsal.test_benchmark import test_cell_rehearsal  # noqa: E402,F401
