"""Device time of a traced run by the scopes ``models/hybrid_ssm.py`` names
its parts with, by program: ``lib/scope_time.py``'s method (an op is looked
up in the ``HloProto`` of the program that ran it) over this family's
scopes. Everything here returns None rather than raise when the trace, the
plane or the scopes are not there (a CPU rehearsal, a program that names no
such scope, as the parent of the PR that added them).
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, Optional, Sequence

from benchmark.lib import annotations, scope_time, xplane
from benchmark.lib.harness import say

#: conv, chunked scan (prefill, extend), one-token step (decode), grouped
#: attention, and the expert layer (router, held experts, shared MLP)
SCOPES = ("ssm_conv", "ssm_scan", "ssm_step", "gqa_attend", "moe_experts")
SSM = ("ssm_conv", "ssm_scan", "ssm_step")
DECODE = "jit_decode"
PREFILL = "jit_prefill"


def scope_seconds(run: Any) -> Optional[Dict[str, Dict[str, float]]]:
    """``{program: {scope: seconds, "busy": seconds}}`` on the first device,
    over the ops that enclose no others."""
    path = annotations.trace_path(run)
    if path is None or run.profile is None or not run.profile.ops:
        return None
    return annotations._once(run, "scope_seconds_hybrid",
                             lambda: _scope_seconds(run, path))


def _scope_seconds(run, path):
    names = annotations.trace_scopes(path)
    if not names:
        return None
    modules = scope_time._module_intervals(annotations.profile_of(run))
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
    if not any(s in row for row in out.values() for s in SSM):
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


def roofline(run: Any, scope: str, program: str,
             cost: Dict[str, float]) -> Optional[float]:
    """Percent: the least time ``cost`` (``bytes`` and, if any, ``flops``)
    takes at the chip's peaks over the device time of ``scope`` in the
    programs whose name starts with ``program``."""
    table = scope_seconds(run)
    if not table or run.peaks is None or not cost.get("bytes"):
        return None
    mine = [row for name, row in table.items() if name.startswith(program)]
    # a trace that cannot tell its programs apart files every op under "?"
    secs = sum(row.get(scope, 0.0) for row in mine or [table.get("?", {})])
    if not secs:
        return None
    by_bytes = cost["bytes"] / run.peaks["hbm_bytes_per_s"]
    by_flops = cost.get("flops", 0.0) / run.peaks["bf16_flops_per_s"]
    least = max(by_bytes, by_flops)
    say(f"  {scope}: {secs:.4f} s in {program}* against "
        f"{cost['bytes'] / 1e9:.3f} GB"
        + (f" and {cost['flops'] / 1e12:.3f} TFLOP" if by_flops else "")
        + f" it had to move ({least:.4f} s at the peaks, "
        f"{'compute' if by_flops > by_bytes else 'memory'} bound)")
    return 100.0 * least / secs
