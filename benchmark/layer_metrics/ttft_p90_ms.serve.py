"""p90 of the time to first token over the requests due in the window:
(sent - due) on the client's clock plus the reply's own ``ttft_ms`` (the
server does not stream). Recorded, decides nothing: over 138 requests its
spread between runs (6-9%, PR 23) is wider than any admissible bound."""
from benchmark.lib import stats


def read(run):
    values = [r["ttft"] for r in run.requests if "ttft" in r]
    return stats.percentile(values, 90.0) if values and not run.failed else None
