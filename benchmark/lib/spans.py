"""Reading the program's request spans (``obs/tracing.py`` rows emitted by
``InferenceServer._req_span``: ``queue_wait``, ``admission``, ``prefill``,
``decode_iter``, ``retire``). They exist only for requests that carry a
trace id, which the callers of a traced run do. Times are the server's
host clock (monotonic), taken after the tokens are on the host."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.lib import stats


def in_window(spans: Sequence[Dict[str, Any]], name: str,
              window: Tuple[float, float]) -> List[Dict[str, Any]]:
    return [s for s in spans
            if s["name"] == name and window[0] <= s["mono"] <= window[1]]


def median_ms(run: Any, name: str) -> Optional[float]:
    rows = in_window(run.spans, name, run.window)
    return stats.median([r["dur_ms"] for r in rows]) if rows else None


def decode_iterations(run: Any, window: Optional[Tuple[float, float]] = None
                      ) -> List[Dict[str, Any]]:
    """One row per decode dispatch (every live request emits a span for
    it, all with the dispatch's start and duration)."""
    seen: Dict[float, Dict[str, Any]] = {}
    for row in in_window(run.spans, "decode_iter", window or run.window):
        seen.setdefault(row["mono"], row)
    return [seen[k] for k in sorted(seen)]


def decode_step_ms(run: Any) -> Optional[float]:
    """Median over dispatches of its wall time per decode step."""
    its = decode_iterations(run)
    chunk = run.shapes["decode_chunk"]
    return stats.median([r["dur_ms"] / chunk for r in its]) if its else None


def context_token_steps(run: Any, window: Tuple[float, float]) -> int:
    """Sum over the decode steps dispatched inside ``window`` and over the
    requests live in them of the request's cached context at that step:
    what single-query attention had to read, in tokens. A request's context
    is its prompt (from its ``prefill`` span) plus what it has emitted."""
    plen = {s["trace_id"]: s["plen"] for s in run.spans if s["name"] == "prefill"}
    emitted: Dict[str, int] = {}
    total = 0
    rows = sorted((s for s in run.spans if s["name"] == "decode_iter"),
                  key=lambda s: s["mono"])
    for row in rows:
        tid = row["trace_id"]
        have = emitted.get(tid, 1)  # the first token came with the prefill
        take = int(row["take"])
        if window[0] <= row["mono"] and row["mono"] + row["dur_ms"] / 1e3 <= window[1]:
            base = plen.get(tid, 0) + have
            total += take * base + take * (take - 1) // 2
        emitted[tid] = have + take
    return total
