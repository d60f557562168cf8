"""Median host-clock time between the loss fetches of successive steps
(``run_chunked``'s ``log`` callback, every step)."""
from benchmark.lib import stats


def read(run):
    times = [t for t, _ in run.steps]
    gaps = [(b - a) * 1e3 for a, b in zip(times, times[1:])]
    return stats.median(gaps) if gaps else None
