"""The one traffic generator: a traffic file's parameters -> requests.

Every seed gets the *same sequence* of (arrival gap, prompt length, output
length), drawn once from the file's ``shape_seed``; ``--seed`` picks where
in that cycle the run starts (a rotation) and which corpus slices the
prompts are. So two seeds do the same work in another order, with the same
bursts and the same neighbours in the queue, and a difference between runs
is the system's, not the draw's. (A full shuffle would keep the multiset
but redraw every burst: the tails of an open loop then vary by the draw,
tens of percent, and no bound could see a regression through that.)

Traffic file keys (serving):

- ``loop``: ``"open"`` (arrivals on a schedule, ``rate_per_s``) or
  ``"closed"`` (``clients`` callers, each sending when its last returned);
- ``prompt_lengths``: {length: share}; a closed, small set, because the
  paged engine compiles one prefill program per (group size, length);
- ``output_tokens``: {"median", "sigma", "min", "max"} of a clipped
  lognormal;
- ``pool_requests`` (closed loop): how many requests the callers cycle over.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, NamedTuple

import numpy as np


class Request(NamedTuple):
    index: int
    due_s: float        # offset from the window's start (open loop)
    prompt_len: int
    out_tokens: int
    offset: int         # start of the prompt's slice in the held-out corpus


def _quota(shares: Mapping[str, float], n: int) -> List[int]:
    """``n`` prompt lengths in the stated shares, by largest remainder."""
    lengths = [int(k) for k in shares]
    exact = [float(shares[str(length)]) * n for length in lengths]
    counts = [math.floor(x) for x in exact]
    order = sorted(range(len(lengths)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    out: List[int] = []
    for length, count in zip(lengths, counts):
        out += [length] * count
    return out


def _outputs(spec: Mapping[str, float], n: int,
             rng: np.random.Generator) -> np.ndarray:
    draws = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(draws), spec["min"], spec["max"]).astype(int)


def n_requests(traffic: Mapping[str, Any], seconds: float) -> int:
    if traffic["loop"] == "open":
        return max(1, round(traffic["rate_per_s"] * seconds))
    return int(traffic["pool_requests"])


def requests(traffic: Mapping[str, Any], seconds: float, seed: int,
             offsets_from: int, corpus_len: int) -> List[Request]:
    """The run's requests in sending order. Open loop: all are due inside
    ``[0, seconds)``, the gaps an exponential draw scaled to fill it.
    Prompts are slices of the corpus starting at distinct offsets at or
    after ``offsets_from``: no two share a first page, so none rides the
    prefix cache (a cell that wants sharing says so in its traffic file,
    and the generator grows that parameter then)."""
    n = n_requests(traffic, seconds)
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    prompt_lens = np.array(_quota(traffic["prompt_lengths"], n))
    outs = _outputs(traffic["output_tokens"], n, shape)
    gaps = shape.exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()

    prompt_lens = shape.permutation(prompt_lens)
    order = np.random.default_rng(seed)
    start = int(order.integers(n))
    prompt_lens, outs, gaps = (np.roll(v, -start)
                               for v in (prompt_lens, outs, gaps))
    due = np.cumsum(gaps) - gaps  # the first request is due at 0
    longest = max(int(k) for k in traffic["prompt_lengths"])
    offsets = offsets_from + order.choice(
        corpus_len - longest - offsets_from, size=n, replace=False)
    return [Request(i, float(due[i]) if traffic["loop"] == "open" else 0.0,
                    int(prompt_lens[i]), int(outs[i]), int(offsets[i]))
            for i in range(n)]


def describe(reqs: List[Request]) -> Dict[str, Any]:
    plens = [r.prompt_len for r in reqs]
    outs = [r.out_tokens for r in reqs]
    return {"n": len(reqs),
            "prompt_tokens_mean": float(np.mean(plens)),
            "out_tokens_mean": float(np.mean(outs)),
            "out_tokens_max": int(max(outs)),
            "prompt_lengths": {int(k): plens.count(k) for k in sorted(set(plens))}}
