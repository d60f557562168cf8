"""Device time per optimizer step of the ops whose scope holds ``forward``
and not ``transpose(``: the loss as it was traced. Scopes come from the
trace's own HLO and steps from the ``train_step`` markers
(``lib/annotations.py``)."""
from benchmark.lib import annotations


def read(run):
    ms = annotations.scoped_device_ms(run)
    return ms["forward"] if ms else None
