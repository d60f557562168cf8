"""Chunked host training loop: one device dispatch per K optimizer steps.

For small models the per-step host dispatch, not the device step, sets the
wall clock (its size on the current machine: not measured); the reference has
the same problem in sharper form (a full serialize -> websocket -> aggregate ->
broadcast round per step, SURVEY.md §3.3). The TPU-idiomatic fix is to run K
steps as a device-side ``lax.scan`` (:meth:`SyncTrainer.step_many`) so one
dispatch covers K real parameter updates.

:func:`run_chunked` packages the loop the experiment CLIs share: chunk a host
batch stream, stack each chunk to ``[K, B, ...]``, dispatch, and keep honest
steady-state timing (the first, compiling dispatch is excluded; partial tail
chunks are not run — a different scan length would force a second XLA compile
mid-run).
"""

from __future__ import annotations

import functools
import itertools
import time
from typing import Any, Callable, Iterable, NamedTuple, Optional

import jax
import numpy as np


class ChunkedRunResult(NamedTuple):
    steps_run: int       # optimizer steps actually executed
    timed_steps: int     # steps inside the steady-state timing window
    elapsed_s: float     # wall time of the timed window (value-fetch barrier)
    last_loss: Optional[float]  # loss of the final executed step
    ran_dry: bool = False  # the batch stream ended before `steps` batches

    @property
    def steps_per_sec(self) -> float:
        """Steady-state steps/sec; nan if everything fit in one dispatch."""
        if not self.timed_steps:
            return float("nan")
        return self.timed_steps / self.elapsed_s

    def tail_note(self, requested_steps: int) -> Optional[str]:
        """Human-readable note when fewer than ``requested_steps`` ran
        (shared by the experiment CLIs), or None if all ran."""
        if self.steps_run >= requested_steps:
            return None
        if self.ran_dry:
            return (f"note: ran {self.steps_run} of {requested_steps} steps "
                    "— the batch stream ended early")
        return (f"note: ran {self.steps_run} of {requested_steps} steps — "
                "the tail is not a full --steps-per-dispatch chunk; pick a "
                "step count divisible by it to run them all")


def run_chunked(
    trainer: Any,
    stream: Iterable[Any],
    steps: int,
    steps_per_dispatch: int = 1,
    log: Optional[Callable[[int, float], None]] = None,
    log_every: int = 20,
) -> ChunkedRunResult:
    """Drive ``trainer`` over ``stream`` with one dispatch per K steps.

    ``stream`` yields host batch pytrees (``(x, y)`` / ``(x, y, w)``); each
    chunk of K is stacked to a leading step axis and run through
    ``trainer.step_many`` (K > 1) or ``trainer.step`` (K == 1) — identical
    optimizer trajectories either way. ``steps`` bounds how many batches are
    consumed; only full chunks run (``steps % K`` tail steps are skipped —
    the caller logs this, knowing its CLI flags). ``log(step, loss)`` fires
    roughly every ``log_every`` steps and after the final chunk.
    """
    k = max(1, min(steps_per_dispatch, steps)) if steps else 1
    run_steps = (steps // k) * k
    stream = iter(stream)
    start = time.perf_counter()
    timed_steps = 0
    step = 0
    last: Optional[float] = None
    ran_dry = False
    while step < run_steps:
        chunk = list(itertools.islice(stream, k))
        if len(chunk) < k:
            ran_dry = True  # stream ended before `steps` batches
            break
        if k > 1:
            stacked = jax.tree.map(lambda *xs: np.stack(xs), *chunk)
            # [-1] value fetch doubles as the device barrier
            last = float(trainer.step_many(stacked)[-1])
        else:
            last = float(trainer.step(chunk[0]))
        first_dispatch = step == 0
        step += k
        if first_dispatch:
            # steady-state timing: the first dispatch carries XLA
            # compilation (~20-40s) and would swamp short runs
            start = time.perf_counter()
        else:
            timed_steps += k
        if log is not None and (
            step >= run_steps or (step // k) % max(1, log_every // k) == 0
        ):
            log(step, last)
    elapsed = time.perf_counter() - start
    return ChunkedRunResult(step, timed_steps, elapsed, last, ran_dry)


def evaluate_dataset(
    evaluate: Callable[..., list],
    x: Any,
    y: Any,
    batch_size: int = 512,
    metrics: tuple = ("loss", "accuracy"),
    divisor: Optional[int] = None,
    **eval_kwargs: Any,
) -> list:
    """Exact whole-array metrics, evaluated in fixed-size chunks.

    ``evaluate`` is any trainer's ``evaluate(x, y, metrics=..., weight=...)``
    (all three training engines share the signature). Per-chunk
    example-mean metrics recombine weighted by real-row count, so the
    result equals one giant batch without ever materializing it on device
    — the CLIs' truncate-to-512 shortcut, replaced.

    ``divisor`` is the sharding constraint on chunk row counts (the mesh's
    data-axis size for SyncTrainer); auto-detected from the bound
    trainer's mesh when possible. A trailing chunk that does not divide is
    zero-padded with weight-0 rows — weighted-mean metrics stay exact. The
    tail's distinct shape compiles one extra program.
    """
    n = len(x)
    if n == 0:
        raise ValueError("evaluate_dataset needs at least one example")
    if len(y) != n:
        raise ValueError(f"x and y lengths differ: {n} vs {len(y)}")
    if divisor is None:
        fn = evaluate
        while isinstance(fn, functools.partial):  # unwrap partial chains
            fn = fn.func
        owner = getattr(fn, "__self__", None)
        mesh = getattr(owner, "mesh", None)
        divisor = int(mesh.shape.get("data", 1)) if mesh is not None else 1
    if batch_size % divisor:
        batch_size += divisor - batch_size % divisor  # keep full chunks legal
    from distriflow_tpu.parallel.mesh import pad_partial_batch

    totals = [0.0] * len(metrics)
    for lo in range(0, n, batch_size):
        hi = min(lo + batch_size, n)
        real = hi - lo
        cx, cy, weight = pad_partial_batch(divisor, x[lo:hi], y[lo:hi])
        if weight is not None:
            vals = evaluate(cx, cy, metrics=tuple(metrics), weight=weight,
                            **eval_kwargs)
        else:
            vals = evaluate(cx, cy, metrics=tuple(metrics), **eval_kwargs)
        for i, v in enumerate(vals):
            totals[i] += float(v) * real
    return [t / n for t in totals]
