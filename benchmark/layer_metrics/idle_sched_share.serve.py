"""Share of the traced window in which device 0 ran no op while a
``df/engine/*`` phase other than ``gather`` was open: the scheduler holding
the chip back. ``device_idle_share.serve`` less this is the chip waiting
for traffic (or a gap shorter than ``lib/xplane.MIN_GAP_NS``)."""
from benchmark.lib import annotations


def read(run):
    return annotations.idle_sched_share(run)
