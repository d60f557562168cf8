"""Median over decode dispatches of the ``dispatch_ms`` the ``decode_iter``
spans carry: the host's time inside the jitted decode call until it
returns (argument transfer, output allocation, launch), before the token
fetch blocks. An earlier line gives the spread, and the median of the
dispatches the profiler saw beside that of all the window's."""
from benchmark.lib import spans, stats
from benchmark.lib.harness import say


def read(run):
    rows = [r for r in spans.decode_iterations(run) if "dispatch_ms" in r]
    if not rows:
        return None
    values = [r["dispatch_ms"] for r in rows]
    traced = [r["dispatch_ms"] for r in rows
              if run.trace_window[0] <= r["mono"] <= run.trace_window[1]]
    say("  " + stats.describe("decode dispatch (host, in the jitted call)",
                              values)
        + (f"; median of the {len(traced)} under the profiler "
           f"{stats.median(traced):.3f}" if traced else ""))
    return stats.median(values)
