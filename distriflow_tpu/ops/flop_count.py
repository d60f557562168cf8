"""Trace-time tally of Pallas kernel model-FLOPs.

XLA's compiled-program ``cost_analysis()`` reports **zero** FLOPs for custom
calls, so any program using the Pallas kernels (flash attention, fused CE)
under-counts its numerator and ``SyncTrainer.mfu()`` could only report a
lower bound. Each kernel wrapper calls :func:`record_pallas_cost` with its
analytic cost at *trace* time; ``SyncTrainer.cost_analysis()`` re-traces the
step abstractly inside :func:`tally_pallas_cost` (``jax.eval_shape`` — no
compile, no execution) and adds the tally to XLA's numbers, making MFU exact.

Convention: recorded FLOPs are **model FLOPs** (the algorithmic forward +
backward work), not hardware FLOPs — the flash backward's score recompute is
rematerialization overhead and is excluded, per the standard MFU definition
(PaLM appendix B): MFU compares achieved *useful* FLOP/s against peak, so a
kernel that recomputes does not get credit for the recompute.

Round 18 adds the **hardware** side of the ledger: ``hw_flops`` is the
FLOPs the kernel actually executes — model FLOPs PLUS recompute — and it is
what a roofline share divides by peak (model FLOPs would flatter a kernel
that recomputes). The
two columns make the cost of rematerialization a first-class, queryable
number: the fused attention backward's whole win is that its ``hw_flops``
drops from 14 to 10 matmul-units while its model FLOPs (the MFU numerator)
stay fixed at 8. ``hw_flops`` defaults to ``flops`` for kernels that do not
recompute.
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from typing import Dict, Iterator, Optional

_TALLY: ContextVar[Optional[Dict[str, float]]] = ContextVar(
    "pallas_cost_tally", default=None
)

_FIELDS = ("flops", "bytes_accessed", "transcendentals", "hw_flops")


def record_pallas_cost(
    flops: float = 0.0,
    bytes_accessed: float = 0.0,
    transcendentals: float = 0.0,
    category: Optional[str] = None,
    hw_flops: Optional[float] = None,
) -> None:
    """Add one kernel invocation's analytic cost to the active tally.

    No-op when no tally is active (the common case: normal jit tracing).
    Call sites run at trace time, once per ``pallas_call`` wiring, so a
    kernel invoked per-block (ring attention) records once per block with
    that block's true shapes.

    ``category`` additionally files the cost under ``tally["by_category"]``
    so consumers can re-scale one kernel family's share — the fused CE
    records GLOBAL row counts (before its own per-data-shard split) while
    the attention kernels record inside their shard_map, per shard; ``SyncTrainer.cost_analysis`` divides the CE
    share by the row-shard degree to keep the per-device convention exact.
    The benchmark's own counts (``benchmark/lib/flops.py``) are held equal
    to these categories by ``tests/test_device_clock.py``.

    ``hw_flops``: FLOPs the kernel body actually executes (model FLOPs +
    recompute); defaults to ``flops``. Never folded into MFU.
    """
    tally = _TALLY.get()
    if tally is not None:
        hw = float(flops if hw_flops is None else hw_flops)
        tally["flops"] += float(flops)
        tally["bytes_accessed"] += float(bytes_accessed)
        tally["transcendentals"] += float(transcendentals)
        tally["hw_flops"] += hw
        if category is not None:
            cat = tally["by_category"].setdefault(
                category, {f: 0.0 for f in _FIELDS},
            )
            cat["flops"] += float(flops)
            cat["bytes_accessed"] += float(bytes_accessed)
            cat["transcendentals"] += float(transcendentals)
            cat["hw_flops"] += hw


@contextmanager
def tally_pallas_cost() -> Iterator[Dict[str, float]]:
    """Collect Pallas kernel costs recorded while tracing inside the block."""
    tally: Dict[str, float] = {f: 0.0 for f in _FIELDS}
    tally["by_category"] = {}  # type: ignore[assignment]
    token = _TALLY.set(tally)
    try:
        yield tally
    finally:
        _TALLY.reset(token)


def pallas_cost_of(fn, *args, **kwargs) -> Dict[str, float]:
    """Tally of one abstract trace of ``fn(*args, **kwargs)``.

    ``jax.eval_shape`` under a fresh tally — no compile, no execution, no
    data movement. The convenience entry for tests, which need "what would
    this function's kernels record?" without standing up a trainer. Caveat (the PR 1 warm-cache lesson, pinned by
    tests/test_depthwise_gn.py): a warm trace cache replays memoized
    jaxprs and skips the Python kernel wrappers, so a zero tally from a
    function KNOWN to contain Pallas calls means the cache ate the trace —
    clear with ``jax.clear_caches()`` and retrace, exactly as
    ``SyncTrainer.cost_analysis`` does.
    """
    import jax

    with tally_pallas_cost() as tally:
        jax.eval_shape(lambda *a: fn(*a, **kwargs), *args)
    return tally
