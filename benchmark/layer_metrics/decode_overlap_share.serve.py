"""Percent of the window's decode dispatches that the engine enqueued while
the dispatch before was still in flight, its tokens not yet fetched
(``overlapped`` of the ``decode_iter`` spans): the rest waited for that
fetch, because a request was waiting, the first of a busy stretch had
nothing before it, or the server fetches each chunk before the next. An
earlier line gives the two counts. None where the spans carry no such
field."""
from benchmark.lib import spans
from benchmark.lib.harness import say


def read(run):
    rows = [r for r in spans.decode_iterations(run) if "overlapped" in r]
    if not rows:
        return None
    ahead = sum(int(r["overlapped"]) for r in rows)
    say(f"  decode dispatches in the window: {len(rows)}, enqueued while the "
        f"one before was in flight: {ahead}")
    return 100.0 * ahead / len(rows)
