"""What the program's own annotations say on the profiler's clock.

``Run.profile`` (``lib/xplane.py``) keeps the device's ops and drops the
host plane, so the readers that need both load the traced run's
``.xplane.pb`` again here, once per run. Three things are read:

- *phases*: host events named ``df/<role>/<phase>``, which the program's
  ``PhaseProfiler`` opens around each phase of the engine's loop
  (``distriflow_tpu/obs/profiler.py``). With the device's ops on the same
  clock they split the chip's idle time into the part spent while the
  scheduler was at work (any ``df/engine/*`` phase but ``gather``) and the
  part spent waiting for traffic (``gather``, or no phase at all);
- *step markers*: host events named ``train_step``
  (``StepTraceAnnotation`` in ``SyncTrainer.step`` / ``step_many``), which
  count the optimizer steps the profiler saw;
- *scopes*: the ``op_name`` of an op's HLO instruction, which holds the
  ``jax.named_scope`` it was traced under. On this stack (jax 0.9.0,
  libtpu 0.0.34) an ``XLA Ops`` event carries no such stat and
  ``ProfileData`` does not expose event metadata; the trace does hold
  every program's ``HloProto`` in its ``/host:metadata`` plane, so the
  instruction names are joined to it, read with the few lines of protobuf
  wire format below (no schema package is imported). A fusion has the
  ``op_name`` XLA gave it, its root's.

A program without these annotations (the parent of the PR that added
them) gives empty lists, and every reader built on them returns None.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

from benchmark.lib import xplane
from benchmark.lib.harness import say

ENGINE_PREFIX = "df/engine/"
WAITING = "df/engine/gather"   # the engine has no work: not the scheduler
STEP_MARKER = "train_step"
METADATA_PLANE = "/host:metadata"
SCOPES = ("forward", "backward", "optimizer")


class Note(NamedTuple):
    start: int
    end: int
    name: str
    stats: Dict[str, Any]


# -- the trace, once per run ----------------------------------------------------


def _once(run: Any, key: str, make: Any) -> Any:
    """``make()`` the first time, kept on the run: six readers share one
    load of the trace and one reduction of it."""
    kept = vars(run).setdefault("_annotations", {})
    if key not in kept:
        kept[key] = make()
    return kept[key]


def trace_path(run: Any) -> Optional[str]:
    if not run.trace or run.profile is None:
        return None
    return xplane.find_xplane(run.trace_dir)


def profile_of(run: Any) -> Any:
    path = trace_path(run)
    if path is None:
        return None
    return _once(run, "profile", lambda: xplane.load(path))


def notes(profile: Any, prefix: str, exact: bool = False) -> List[Note]:
    """Host events whose name starts with (or, ``exact``, is) ``prefix``,
    sorted by start."""
    out: List[Note] = []
    for plane in profile.planes:
        if not plane.name.startswith(xplane.HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == prefix or (
                        not exact and ev.name.startswith(prefix)):
                    start = int(ev.start_ns)
                    out.append(Note(start, start + int(ev.duration_ns),
                                    ev.name, dict(ev.stats)))
    return sorted(out)


def overlap(a: Sequence[xplane.Interval], b: Sequence[xplane.Interval]) -> int:
    """Length of the part of union ``a`` that union ``b`` covers."""
    return xplane.total(a) - xplane.total(xplane.subtract(a, b))


# -- idle time under the scheduler's phases ----------------------------------------


def idle_by_phase(profile: Any, ops: Dict[str, List[xplane.Op]]
                  ) -> Optional[Dict[str, float]]:
    """Seconds in which device 0 ran no op, split by what the engine was
    doing: ``window``, ``idle``, ``scheduler`` (a ``df/engine/*`` phase
    other than ``gather`` open), ``gather``, ``unannotated``, and one entry
    per phase name (nested phases each count their own cover). Gaps shorter
    than ``xplane.MIN_GAP_NS`` are launch latency between the ops of a
    running program and are no one's wait. None without ``df/engine``
    events."""
    phases = notes(profile, ENGINE_PREFIX)
    if not phases or not ops:
        return None
    start = min(o[0].start for o in ops.values())
    end = max(max(op.end for op in o) for o in ops.values())
    first = next(iter(ops.values()))
    gaps = [g for g in xplane.subtract(
        [(start, end)], xplane.union((op.start, op.end) for op in first))
        if g[1] - g[0] >= xplane.MIN_GAP_NS]
    by_name: Dict[str, List[xplane.Interval]] = {}
    for note in phases:
        by_name.setdefault(note.name, []).append((note.start, note.end))
    working = xplane.union(iv for name, ivs in by_name.items()
                           if name != WAITING for iv in ivs)
    waiting = xplane.union(by_name.get(WAITING, []))
    out = {"window": (end - start) / 1e9, "idle": xplane.total(gaps) / 1e9,
           "scheduler": overlap(gaps, working) / 1e9}
    out["gather"] = overlap(xplane.subtract(gaps, working), waiting) / 1e9
    out["unannotated"] = out["idle"] - out["scheduler"] - out["gather"]
    for name, ivs in sorted(by_name.items()):
        out[name] = overlap(gaps, xplane.union(ivs)) / 1e9
    return out


def idle_sched_share(run: Any) -> Optional[float]:
    """Share of the traced window in which device 0 ran no op while the
    scheduler was at work. Prints the whole split on an earlier line, and
    beside it the context token-steps of the window as the ``decode_iter``
    annotations carry them and as ``lib/spans.py`` derives them from the
    request spans across two clocks."""
    profile = profile_of(run)
    if profile is None:
        return None
    split = idle_by_phase(profile, run.profile.ops)
    if split is None:
        return None
    share = lambda k: 100.0 * split[k] / split["window"]  # noqa: E731
    say(f"  idle by phase: window {split['window']:.3f} s, idle gaps "
        f"{split['idle']:.4f} s ({share('idle'):.2f}%) = scheduler at work "
        f"{split['scheduler']:.4f} + waiting in gather {split['gather']:.4f} "
        f"+ under no phase {split['unannotated']:.4f}")
    say("  idle under each phase (nested phases count their own cover): "
        + ", ".join(f"{k[len(ENGINE_PREFIX):]} {v:.4f}"
                    for k, v in split.items() if k.startswith(ENGINE_PREFIX)))
    report_context(run, profile)
    return share("scheduler")


def context_token_steps(profile: Any, chunk: int) -> Tuple[int, int]:
    """(token-steps, dispatches) from the ``decode_iter`` annotations: a
    dispatch advances ``n_active`` rows ``chunk`` steps from ``ctx_tokens``
    cached positions in all, so it reads
    ``chunk * ctx_tokens + n_active * chunk * (chunk - 1) / 2``."""
    total = count = 0
    for note in notes(profile, ENGINE_PREFIX + "decode_iter", exact=True):
        if "ctx_tokens" not in note.stats:
            continue
        n = int(note.stats["n_active"])
        total += chunk * int(note.stats["ctx_tokens"]) + n * chunk * (chunk - 1) // 2
        count += 1
    return total, count


def report_context(run: Any, profile: Any) -> None:
    from benchmark.lib import spans

    chunk = run.shapes.get("decode_chunk")
    if not chunk:
        return
    mine, dispatches = context_token_steps(profile, chunk)
    theirs = spans.context_token_steps(run, run.trace_window)
    if mine and theirs:
        say(f"  context read in the traced window: {mine} token-steps by the "
            f"decode_iter annotations ({dispatches} dispatches, one clock; "
            f"every live row counted for the whole chunk), {theirs} by the "
            f"request spans matched to the profiler's window "
            f"({100.0 * (mine - theirs) / theirs:+.2f}%)")


# -- step markers -------------------------------------------------------------------


def steps_traced(run: Any) -> int:
    """Optimizer steps whose ``train_step`` marker lies in the trace."""
    profile = profile_of(run)
    if profile is None:
        return 0
    markers = notes(profile, STEP_MARKER, exact=True)
    return len(markers) * int(run.traffic.get("steps_per_dispatch", 1))


# -- scopes: protobuf wire format, just enough for the HLO's op_name ---------------


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message's top level: a
    varint's number, or the bytes of a length-delimited field."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = buf[pos:pos + size]
            pos += size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at {pos}")
        yield number, wire, value


def _sub(buf: bytes, number: int) -> Iterator[bytes]:
    for n, wire, value in fields(buf):
        if n == number and wire == 2:
            yield value


def _text(buf: bytes, number: int) -> str:
    for value in _sub(buf, number):
        return bytes(value).decode("utf-8", "replace")
    return ""


def module_scopes(hlo_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction name -> op_name) of one ``HloProto``:
    ``hlo_module`` = 1 -> ``name`` = 1, ``computations`` = 3 ->
    ``instructions`` = 2 -> ``name`` = 1, ``metadata`` = 7 -> ``op_name``
    = 2 (xla/service/hlo.proto, xla/xla_data.proto)."""
    names: Dict[str, str] = {}
    module_name = ""
    for module in _sub(hlo_proto, 1):
        module_name = _text(module, 1)
        for computation in _sub(module, 3):
            for instr in _sub(computation, 2):
                op_name = ""
                for meta in _sub(instr, 7):
                    op_name = _text(meta, 2)
                names[_text(instr, 1)] = op_name
    return module_name, names


def trace_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Module name -> (instruction name -> op_name) for every program whose
    ``HloProto`` the trace's ``/host:metadata`` plane holds: ``XSpace``
    ``planes`` = 1 -> ``name`` = 2, ``event_metadata`` = 4 (a map: value =
    2) -> ``stats`` = 5 -> ``bytes_value`` = 6 (tsl xplane.proto)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):
            for event_metadata in _sub(entry, 2):
                for stat in _sub(event_metadata, 5):
                    for blob in _sub(stat, 6):
                        try:
                            name, names = module_scopes(bytes(blob))
                        except (ValueError, IndexError):
                            continue  # a bytes stat that is no HloProto
                        if names:
                            out.setdefault(name, {}).update(names)
    return out


def scope_of(op_name: str) -> Optional[str]:
    """Which part of the step an op belongs to, by the scopes
    ``SyncTrainer`` traces under: the optimizer's; else the transpose of
    the loss (its backward); else the loss itself."""
    if "optimizer" in op_name:
        return "optimizer"
    if "forward" in op_name:
        return "backward" if "transpose(" in op_name else "forward"
    return None


def scoped_device_ms(run: Any) -> Optional[Dict[str, float]]:
    """Device milliseconds per optimizer step by scope (``forward``,
    ``backward``, ``optimizer``, ``unscoped``; device mean), the ops that
    enclose others left out as in ``lib/xplane.py``. Prints the split on an
    earlier line. None when the trace holds no step marker or no op of the
    step carries a scope."""
    path = trace_path(run)
    if path is None:
        return None
    return _once(run, "scoped_ms", lambda: _scoped_device_ms(run, path))


def _scoped_device_ms(run: Any, path: str) -> Optional[Dict[str, float]]:
    steps = steps_traced(run)
    if not steps:
        return None
    by_instruction: Dict[str, str] = {}
    for names in trace_scopes(path).values():
        for instr, op_name in names.items():
            by_instruction.setdefault(instr, op_name)
    spans: Dict[str, List[xplane.Interval]] = {k: [] for k in SCOPES}
    spans["unscoped"] = []
    for ops in run.profile.ops.values():
        for op in ops:
            if op.kind in xplane.ENCLOSING:
                continue
            scope = scope_of(by_instruction.get(xplane.instruction(op.name), ""))
            spans[scope or "unscoped"].append((op.start, op.end))
    n = max(len(run.profile.ops), 1)
    ms = {k: xplane.total(v) / n / steps / 1e6 for k, v in spans.items()}
    if not any(ms[k] for k in SCOPES):
        return None
    busy = run.profile.busy_s * 1e3 / steps
    say(f"  step by scope over {steps} traced steps: forward "
        f"{ms['forward']:.2f} ms, backward {ms['backward']:.2f}, optimizer "
        f"{ms['optimizer']:.2f}, under no scope {ms['unscoped']:.2f} "
        f"({100.0 * ms['unscoped'] / busy:.2f}% of the {busy:.2f} ms the "
        "device is busy per step)")
    return ms
