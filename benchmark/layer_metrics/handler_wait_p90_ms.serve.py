"""p90 of the transport's ``handler_wait`` spans of ``generate`` frames in
the window: frame handed to the executor until its handler runs on a pool
thread. The wait comes before the server's enqueue stamp, so no other
server-side number holds it; the client sees it in the gap between tokens.
An earlier line gives the median and the spread."""
from benchmark.lib import spans, stats
from benchmark.lib.harness import say


def read(run):
    waits = [r["dur_ms"]
             for r in spans.in_window(run.spans, "handler_wait", run.window)
             if r.get("event") == "generate"]
    if not waits:
        return None
    say("  " + stats.describe("handler wait (generate frames)", waits))
    return stats.percentile(waits, 90.0)
