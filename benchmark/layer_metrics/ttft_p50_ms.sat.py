"""Median time to first token of the requests answered in the window
(client clock plus the reply's stamp; recorded, decides nothing)."""
from benchmark.lib import stats


def read(run):
    values = [r["ttft"] for r in run.requests if "ttft" in r]
    return stats.median(values) if values else None
