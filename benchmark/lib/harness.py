"""What run.py, the drivers and the metric readers share: the registry in
``BENCHMARK.json``, loading a file by the name found there, and the record
of one run that the readers take their numbers from."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def say(*args: Any) -> None:
    """Earlier lines of the run: everything but the result."""
    print(*args, flush=True)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR) -> Any:
    """``benchmark/<kind>/<name>.py`` as a module; names may hold dots and
    dashes, so the file is loaded by path."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Registry:
    """``BENCHMARK.json``: the one table of cells and of which metric each
    cell reports. A later PR adds entries there and files beside the ones
    they name; nothing here knows a cell, a configuration or a metric."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.table = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Dict[str, Any]:
        for cell in self.table["workloads"]:
            if cell["name"] == name:
                return cell
        known = ", ".join(c["name"] for c in self.table["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> Dict[str, Any]:
        for entry in self.table["configs"]:
            if entry["name"] == name:
                return load_json(os.path.join(self.root, entry["file"]))
        raise SystemExit(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        for base in self.table["paths"]:
            path = os.path.join(self.root, base, "traffic", name + ".json")
            if os.path.exists(path):
                return load_json(path)
        raise SystemExit(f"no traffic file traffic/{name}.json under "
                         f"{self.table['paths']}")

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        return [m for m in self.table[group]
                if "workloads" not in m or cell in m["workloads"]]


@dataclasses.dataclass
class Run:
    """One run, as the drivers leave it and the readers find it."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_process: float                 # monotonic, at process start
    meter: Any                       # lib.compile_meter.CompileMeter
    trace_dir: str
    devices: List[Any] = dataclasses.field(default_factory=list)
    peaks: Optional[Dict[str, Any]] = None  # None in a rehearsal
    # filled by the driver
    correct: bool = False
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    compile_setup: Dict[str, float] = dataclasses.field(default_factory=dict)
    compile_window: Dict[str, float] = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    steps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    requests: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)        # monotonic
    spans: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    trace_window: Tuple[float, float] = (0.0, 0.0)  # monotonic, profiler on
    profile: Any = None              # lib.xplane.Reduction, chip runs only
    shapes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Times one part of set-up and says so on an earlier line, with
        what JAX compiled or loaded meanwhile."""
        mark = self.meter.mark()
        try:
            yield
        finally:
            d = self.meter.since(mark)
            say(f"  [phase] {name}: {d['wall_s']:.1f}s (programs "
                f"{d['programs']:.0f} = cache hits {d['cache_hits']:.0f} + "
                f"compiles {d['backend_compiles']:.0f}, "
                f"{d['backend_s']:.1f}s in the backend)")

    @property
    def model(self) -> Dict[str, Any]:
        return model_view(self.config)


def model_view(c: Dict[str, Any]) -> Dict[str, Any]:
    """A configuration file's sizes under the names ``lib/flops.py`` and the
    drivers use."""
    return {"vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_layers": c["num_hidden_layers"],
            "d_ff": c["intermediate_size"],
            "max_seq": c["max_position_embeddings"],
            "dtype": c["compute_dtype"],
            "rope_base": float(c["rotary_emb_base"])}


def transformer_config(model: Dict[str, Any], name_kernels: bool,
                       **over: Any) -> Any:
    """The program's ``TransformerConfig`` at ``model``'s sizes. On the chip
    the kernel choices stay on auto (``None``), which is what a user gets;
    off it auto picks the XLA paths, so a rehearsal or a compile for a
    described chip names the kernels (``name_kernels``)."""
    import jax.numpy as jnp

    from distriflow_tpu import TransformerConfig

    force = True if name_kernels else None
    m = {**model, **over}
    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_layers=m["n_layers"], d_ff=m["d_ff"], max_seq=m["max_seq"],
        dtype=getattr(jnp, m["dtype"]), rope_base=m["rope_base"],
        use_flash_attention=force, use_flash_decode=force,
        loss="fused_sparse_softmax_cross_entropy" if name_kernels else None)


def prng_key(seed: int) -> Any:
    """A key from any whole number: ``--seed`` runs past 32 signed bits."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def configure_jax() -> str:
    """The benchmark's process configuration, before the first compile: the
    program's persistent compile cache (in the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program. jax keeps
    programs that compiled in under a second out of the cache by default;
    most serving programs are such, and a warm start recompiled them all
    (PERF.md, PR 21)."""
    import jax

    from distriflow_tpu import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def toy(group: Dict[str, Any]) -> Dict[str, Any]:
    """A file's own ``rehearsal`` overrides laid over it: the toy size the
    CPU rehearsal runs, kept beside the real one it stands in for."""
    return {**group, **group.get("rehearsal", {})}
