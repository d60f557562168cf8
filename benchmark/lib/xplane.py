"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. Every
function takes the duck-typed shape ProfileData has (``planes`` ->
``lines`` -> ``events`` with ``name``, ``start_ns``, ``duration_ns``,
``stats``), so the unit checks run on a synthetic fixture.

Conventions, fixed here so that every PR computes the same number:

- a *device plane* is one whose name starts with ``/device:TPU:``; its op
  events are those of the line named ``XLA Ops`` (one serial stream per
  core). Lines such as ``Steps`` and ``XLA Modules`` span whole programs
  and would hide every gap, so they are never counted as busy time;
- *busy* is the union of the op events' intervals; the *window* runs from
  the first op's start to the last op's end over all device planes; idle
  share is ``1 - busy / window``, averaged over the device planes;
- an op event's name is its HLO instruction's text (``%fusion.9 = f32[...]
  fusion(...)``); its *instruction* is the part before `` = `` without the
  ``%``, its *kind* the instruction without the trailing ``.N``. A Pallas
  kernel's custom call is named after the ``name=`` of its ``pallas_call``,
  so a *kernel*'s time is the sum of the ops whose kind is the kernel's name;
- ``while``, ``conditional`` and ``call`` ops enclose the ops of their
  bodies: they count as busy time (the device is running the program) and
  are left out of the ranking of ops, which would count their bodies twice;
- *collective* events are ops whose kind starts with one of
  :data:`COLLECTIVES`; the *exposed* part is the part of their union during
  which no other op runs on that device;
- an *idle gap* is an interval of the window with no op on the device. It
  is attributed to the host event (any thread of a ``/host:`` plane) that
  covers most of it, the shortest such event winning among those covering
  at least half: the innermost thing the host was doing meanwhile.
"""

from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OP_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
MIN_GAP_NS = 20_000  # shorter holes are launch latency between ops
MAX_ATTRIBUTED_GAPS = 300  # the longest ones; attribution scans the host plane

Interval = Tuple[int, int]


ENCLOSING = ("while", "conditional", "call")


class Op(NamedTuple):
    start: int
    end: int
    name: str   # the event's name as the profiler gives it
    kind: str   # see the module doc; a host event's line name


def instruction(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def kind_of(name: str) -> str:
    return re.sub(r"\.\d+$", "", instruction(name))


def label_of(name: str) -> str:
    """Kind and result shape: short enough to read, the same for every
    instance of an op in a loop or a layer stack."""
    _, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].strip()
    return f"{kind_of(name)} {shape}".strip()


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Any:
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(profile: Any) -> Dict[str, List[Op]]:
    """Device plane name -> its op events, sorted by start."""
    out: Dict[str, List[Op]] = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops: List[Op] = []
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                ops.append(Op(start, start + int(ev.duration_ns), ev.name,
                              kind_of(ev.name)))
        if ops:
            out[plane.name] = sorted(ops)
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals: Iterable[Interval]) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union ``a`` not covered by union ``b`` (both merged)."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def is_collective(kind: str) -> bool:
    return kind.startswith(COLLECTIVES)


def host_events(profile: Any) -> List[Op]:
    events: List[Op] = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                dur = int(ev.duration_ns)
                if dur > 0:
                    events.append(Op(start, start + dur, ev.name, line.name))
    return sorted(events)


def attribute_gap(gap: Interval, events: Sequence[Op],
                  ends: Sequence[int]) -> str:
    """Name of the host event that best explains ``gap`` (module doc).
    ``events`` are sorted by start and ``ends[i]`` is the latest end among
    ``events[:i + 1]``, so everything before the first ``ends`` past the
    gap's start is over before the gap and is skipped."""
    length = gap[1] - gap[0]
    best_cover, best = 0, None
    inner = None
    for ev in events[bisect.bisect_right(ends, gap[0]):]:
        if ev.start >= gap[1]:
            break
        cover = min(ev.end, gap[1]) - max(ev.start, gap[0])
        if cover <= 0:
            continue
        if cover > best_cover:
            best_cover, best = cover, ev
        if 2 * cover >= length and (
                inner is None or ev.end - ev.start < inner.end - inner.start):
            inner = ev
    chosen = inner or best
    return chosen.name if chosen is not None else "host: nothing traced"


class Reduction(NamedTuple):
    devices: int
    window_s: float          # first op start to last op end
    busy_s: float            # mean over devices of the busy union
    idle_share: float
    collective_s: float      # mean over devices
    collective_exposed_s: float
    op_seconds: List[Tuple[str, float]]   # top ops by time, device-mean
    idle_gaps: List[Tuple[str, float]]    # top host attributions, device 0
    ops: Dict[str, List[Op]]

    def kernel_seconds(self, kernel: str) -> Tuple[float, int]:
        """(seconds, calls) of the ops of kind ``kernel``, device mean."""
        secs, calls = 0.0, 0
        for ops in self.ops.values():
            hits = [op for op in ops if op.kind == kernel]
            secs += sum(op.end - op.start for op in hits) / 1e9
            calls += len(hits)
        n = max(len(self.ops), 1)
        return secs / n, calls // n


def reduce(profile: Any, top: int = 10) -> Optional[Reduction]:
    """None when the trace holds no device plane (a CPU rehearsal)."""
    per_device = device_ops(profile)
    if not per_device:
        return None
    n = len(per_device)
    start = min(ops[0].start for ops in per_device.values())
    end = max(max(op.end for op in ops) for ops in per_device.values())
    window = end - start
    busy = coll = exposed = 0
    by_name: Dict[str, int] = {}
    for ops in per_device.values():
        busy += total(union((op.start, op.end) for op in ops))
        coll_u = union((op.start, op.end) for op in ops
                       if is_collective(op.kind))
        other_u = union((op.start, op.end) for op in ops
                        if not is_collective(op.kind)
                        and op.kind not in ENCLOSING)
        coll += total(coll_u)
        exposed += total(subtract(coll_u, other_u))
        for op in ops:
            if op.kind not in ENCLOSING:
                label = label_of(op.name)
                by_name[label] = by_name.get(label, 0) + op.end - op.start
    first = next(iter(per_device.values()))
    gaps = [g for g in subtract([(start, end)],
                                union((op.start, op.end) for op in first))
            if g[1] - g[0] >= MIN_GAP_NS]
    gaps.sort(key=lambda g: g[0] - g[1])
    hosts = [ev for ev in host_events(profile)
             if ev.end - ev.start >= MIN_GAP_NS // 2]
    ends = list(itertools.accumulate((ev.end for ev in hosts), max))
    by_host: Dict[str, int] = {}
    for gap in gaps[:MAX_ATTRIBUTED_GAPS]:
        label = attribute_gap(gap, hosts, ends)
        by_host[label] = by_host.get(label, 0) + gap[1] - gap[0]
    rest = total(gaps[MAX_ATTRIBUTED_GAPS:])
    if rest:
        by_host["shorter gaps, not attributed"] = rest
    rank = lambda d, scale: [  # noqa: E731
        (k, v / scale) for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Reduction(
        devices=n, window_s=window / 1e9, busy_s=busy / n / 1e9,
        idle_share=1.0 - busy / n / window if window else 0.0,
        collective_s=coll / n / 1e9, collective_exposed_s=exposed / n / 1e9,
        op_seconds=rank(by_name, 1e9 * n), idle_gaps=rank(by_host, 1e9),
        ops=per_device)
