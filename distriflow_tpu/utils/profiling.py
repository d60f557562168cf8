"""TPU profiling hooks.

The reference's only tracing is wall-clock ``time()`` logging
(``src/server/abstract_server.ts:98-103``). On TPU we add real tracing:
``jax.profiler`` trace capture around training sections, plus a per-step
timing helper that blocks on device completion so timings are honest
(dispatch is async in JAX).
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace to ``log_dir`` (no-op if None)."""
    if log_dir is None:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


@contextlib.contextmanager
def device_timer() -> Iterator[dict]:
    """Times a block including device completion. Yields a dict; read
    ``result['ms']`` after the block. Caller must block on its outputs
    (``jax.block_until_ready`` or a value fetch) for the timing to include
    device work."""
    result = {"ms": 0.0}
    start = time.perf_counter()
    try:
        yield result
    finally:
        result["ms"] = (time.perf_counter() - start) * 1e3
