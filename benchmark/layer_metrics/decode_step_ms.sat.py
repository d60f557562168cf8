"""Median over decode dispatches of the ``decode_iter`` span's wall time per
decode step (a dispatch advances every slot ``decode_chunk`` steps)."""
from benchmark.lib import spans


def read(run):
    return spans.decode_step_ms(run)
