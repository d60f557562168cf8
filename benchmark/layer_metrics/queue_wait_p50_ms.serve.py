"""Median of the server's ``queue_wait`` request spans (enqueue to the
admission pass that took the request)."""
from benchmark.lib import spans


def read(run):
    return spans.median_ms(run, "queue_wait")
