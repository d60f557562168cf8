"""Device time per optimizer step of the ops whose scope holds ``transpose(``
of ``forward``: the transpose of the loss, which is its backward. Scopes come from the
trace's own HLO and steps from the ``train_step`` markers
(``lib/annotations.py``)."""
from benchmark.lib import annotations


def read(run):
    ms = annotations.scoped_device_ms(run)
    return ms["backward"] if ms else None
