"""Device time per optimizer step of the ops whose scope holds ``optimizer``:
the update, its application and the EMA. A weight-gradient fusion that
XLA merged the update into counts where its root does. Scopes come from the
trace's own HLO and steps from the ``train_step`` markers
(``lib/annotations.py``)."""
from benchmark.lib import annotations


def read(run):
    ms = annotations.scoped_device_ms(run)
    return ms["optimizer"] if ms else None
