"""``benchmark/rehearsal/test_overlap_share.py`` collected into tier-1: the
serving cells' traced CPU rehearsals, one subprocess a case (12-20 s each),
in a file of their own so that ``--dist loadfile`` runs them beside the
other cell rehearsals and not behind them."""

import pytest

pytest.register_assert_rewrite("benchmark.rehearsal.test_overlap_share")

from benchmark.rehearsal.test_overlap_share import (  # noqa: E402,F401
    test_overlap_share_reads_in_a_rehearsal,
)
