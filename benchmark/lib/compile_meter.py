"""Counts what JAX compiles, from ``jax.monitoring`` events.

Copied from ``chip_smoke.py::CompileMeter`` and ``mosaic_kernels`` (PR 21).
``programs`` counts backend compilations *and* persistent-cache loads (both
fire the backend-compile duration event); ``cache_hits`` counts the loads.
A window in which ``programs`` moved compiled or loaded something.
"""

from __future__ import annotations

import re
import time
from typing import Any, Dict, List, NamedTuple


class Mark(NamedTuple):
    programs: int
    cache_hits: int
    backend_s: float
    t: float


class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.programs = 0
        self.cache_hits = 0
        self.backend_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == self._BACKEND:
            self.programs += 1
            self.backend_s += secs

    def mark(self) -> Mark:
        return Mark(self.programs, self.cache_hits, self.backend_s,
                    time.monotonic())

    def since(self, mark: Mark) -> Dict[str, float]:
        programs = self.programs - mark.programs
        hits = self.cache_hits - mark.cache_hits
        return {"programs": programs, "cache_hits": hits,
                "backend_compiles": programs - hits,
                "backend_s": self.backend_s - mark.backend_s,
                "wall_s": time.monotonic() - mark.t}


def mosaic_kernels(compiled_text: str) -> Dict[str, List[str]]:
    """Pallas kernel name -> the ``tpu_custom_call`` lines of a compiled
    program that run it (each pallas_call of the program carries a
    ``name=`` that XLA keeps in the custom call's ``op_name``)."""
    found: Dict[str, List[str]] = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        for name in re.findall(r"[A-Za-z_0-9]+", op.group(1) if op else ""):
            if name.startswith(("flash_", "fused_ce_")):
                found.setdefault(name, []).append(line)
    return found
