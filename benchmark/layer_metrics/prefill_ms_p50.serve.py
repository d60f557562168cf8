"""Median of the server's ``prefill`` request spans (prefill, page scatter
and first-token pick of the request's group, tokens on the host)."""
from benchmark.lib import spans


def read(run):
    return spans.median_ms(run, "prefill")
