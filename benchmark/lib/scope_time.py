"""Device time of a traced run by ``jax.named_scope``, for programs that
name their parts (``dsa_indexer``, ``dsa_attend``, ``moe_experts``).

An ``XLA Ops`` event carries no ``op_name`` on this stack; the trace holds
each program's ``HloProto`` (``lib/annotations.py::trace_scopes``), and
instruction names repeat across programs, so an op is looked up in the
program that ran it: the ``XLA Modules`` line of the device plane gives each
program's intervals. Where a trace has no such line the ops are looked up
in every program in turn. Everything here returns None rather than raise
when the trace, the plane or the scopes are not there (a CPU rehearsal, a
program that names no such scope).
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.lib import annotations, xplane
from benchmark.lib.harness import say

MODULE_LINE = "XLA Modules"
#: the scopes ``models/latent_sparse.py`` names its parts with
SCOPES = ("dsa_indexer", "dsa_attend", "moe_experts")


def _module_intervals(profile: Any) -> List[Tuple[int, int, str]]:
    """(start, end, program name) on the first device, sorted."""
    for plane in profile.planes:
        if not plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == MODULE_LINE:
                return sorted(
                    (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                     ev.name.split("(", 1)[0]) for ev in line.events)
        return []
    return []


def scope_seconds(run: Any) -> Optional[Dict[str, Dict[str, float]]]:
    """``{program: {scope: seconds, "busy": seconds}}`` on the first device,
    over the ops that enclose no others; a program the trace cannot tell
    apart is filed under ``"?"``."""
    path = annotations.trace_path(run)
    if path is None or run.profile is None or not run.profile.ops:
        return None
    return annotations._once(run, "scope_seconds",
                             lambda: _scope_seconds(run, path))


def _scope_seconds(run, path):
    names = annotations.trace_scopes(path)
    if not names:
        return None
    modules = _module_intervals(annotations.profile_of(run))
    starts = [m[0] for m in modules]
    out: Dict[str, Dict[str, float]] = {}
    for op in next(iter(run.profile.ops.values())):
        if op.kind in xplane.ENCLOSING:
            continue
        instr = xplane.instruction(op.name)
        program, op_name = "?", ""
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i][1] and modules[i][2] in names:
            program = modules[i][2]
            op_name = names[program].get(instr, "")
        else:
            op_name = next((n[instr] for n in names.values() if instr in n), "")
        row = out.setdefault(program, {"busy": 0.0})
        secs = (op.end - op.start) / 1e9
        row["busy"] += secs
        for scope in SCOPES:
            if scope in op_name:
                row[scope] = row.get(scope, 0.0) + secs
                break
    if not any(s in row for row in out.values() for s in SCOPES):
        return None
    say("  device seconds by program and scope: " + "; ".join(
        f"{p} busy {row['busy']:.4f}" + "".join(
            f" {s} {row[s]:.4f}" for s in SCOPES if s in row)
        for p, row in sorted(out.items(), key=lambda kv: -kv[1]["busy"])))
    return out


def share_of_busy(run: Any, of: Sequence[str]) -> Optional[float]:
    """Percent of the device's busy time spent under the scopes ``of``."""
    table = scope_seconds(run)
    if not table:
        return None
    busy = sum(row["busy"] for row in table.values())
    under = sum(row.get(s, 0.0) for row in table.values() for s in of)
    return 100.0 * under / busy if busy else None


def decode_roofline(run: Any, scope: str,
                    least_bytes: int) -> Optional[float]:
    """Percent: the time ``least_bytes`` take at the chip's HBM peak over
    the device time of ``scope`` in the decode program."""
    table = scope_seconds(run)
    if not table or not least_bytes or run.peaks is None:
        return None
    secs = table.get("jit_decode", table.get("?", {})).get(scope)
    if not secs:
        return None
    least = least_bytes / run.peaks["hbm_bytes_per_s"]
    say(f"  {scope}: {secs:.4f} s in the decode program against "
        f"{least_bytes / 1e9:.3f} GB it had to read ({least:.4f} s at the "
        "HBM peak)")
    return 100.0 * least / secs
