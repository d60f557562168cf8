"""Median gap between output tokens of the requests answered in the window
(client clock over the whole reply; recorded, decides nothing)."""
from benchmark.lib import stats


def read(run):
    values = [r["tpot"] for r in run.requests if "tpot" in r]
    return stats.median(values) if values else None
