"""Traffic of sessions: further turns on long contexts that are resident.

A traffic file names ``sessions`` ({context length: how many}); each is a
slice of the corpus that set-up makes resident in the server's prefix map.
A request is one further turn of one session: the session's whole context
plus ``turn_tokens`` fresh corpus tokens as the prompt (every full page of
the context is a prefix hit; the turn goes through ``extend``), and an
output drawn as ``lib/loadgen.py`` draws it.

As there, every seed gets the *same sequence* of (arrival gap, session,
output length), drawn once from ``shape_seed``: the sessions are taken in a
fixed permutation cycle, ``--seed`` rotates where the run starts in the
sequence and picks the corpus slices (the sessions' and the turns'), so two
seeds do the same work in another order.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, NamedTuple

import numpy as np

from benchmark.lib.loadgen import _outputs


class Session(NamedTuple):
    index: int
    context_len: int
    offset: int          # start of the context's slice in the corpus


class Request(NamedTuple):
    index: int
    due_s: float         # offset from the window's start
    prompt_len: int      # context + turn
    out_tokens: int
    offset: int          # start of the turn's slice in the corpus
    session: int


def session_lengths(traffic: Mapping[str, Any]) -> List[int]:
    """Context length of session 0, 1, ...: shortest first."""
    out: List[int] = []
    for length in sorted(int(k) for k in traffic["sessions"]):
        out += [length] * int(traffic["sessions"][str(length)])
    return out


def sessions(traffic: Mapping[str, Any], seed: int,
             offsets_from: int) -> List[Session]:
    """The sessions' contexts: consecutive slices of the corpus from a
    start that ``seed`` picks at or after ``offsets_from``."""
    slack = int(traffic["session_slack_tokens"])
    at = offsets_from + int(np.random.default_rng(seed).integers(slack))
    out = []
    for i, length in enumerate(session_lengths(traffic)):
        out.append(Session(i, length, at))
        at += length
    return out


def sessions_end(traffic: Mapping[str, Any], offsets_from: int) -> int:
    """First corpus token no session of any seed can hold."""
    return (offsets_from + int(traffic["session_slack_tokens"])
            + sum(session_lengths(traffic)))


def requests(traffic: Mapping[str, Any], seconds: float, seed: int,
             offsets_from: int, corpus_len: int) -> List[Request]:
    """The window's requests in sending order, all due inside ``[0,
    seconds)``; turns are slices at distinct offsets at or after
    ``offsets_from`` (past the sessions and the warm-up's turns)."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    lengths = session_lengths(traffic)
    turn = int(traffic["turn_tokens"])
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    outs = _outputs(traffic["output_tokens"], n, shape)
    gaps = shape.exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()
    cycle = shape.permutation(len(lengths))
    which = np.resize(cycle, n)

    order = np.random.default_rng(seed)
    start = int(order.integers(n))
    which, outs, gaps = (np.roll(v, -start) for v in (which, outs, gaps))
    due = np.cumsum(gaps) - gaps  # the first request is due at 0
    offsets = offsets_from + order.choice(
        corpus_len - turn - offsets_from, size=n, replace=False)
    return [Request(i, float(due[i]), lengths[int(which[i])] + turn,
                    int(outs[i]), int(offsets[i]), int(which[i]))
            for i in range(n)]


def describe(reqs: List[Request]) -> Dict[str, Any]:
    plens = [r.prompt_len for r in reqs]
    outs = [r.out_tokens for r in reqs]
    return {"n": len(reqs),
            "out_tokens_mean": float(np.mean(outs)),
            "out_tokens_max": int(max(outs)),
            "sessions_hit": len({r.session for r in reqs}),
            "prompt_lengths": {int(k): plens.count(k) for k in sorted(set(plens))}}
