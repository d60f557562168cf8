#!/usr/bin/env python3
"""Print the shape of a profiler trace: planes, lines, event counts, and a
few events with their stats. Look at a trace by hand with this before
changing ``lib/xplane.py``.

    python benchmark/rehearsal/dump_trace.py [trace_dir] [needle ...]

Events whose name or string stats contain a ``needle`` are shown in full.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import xplane  # noqa: E402


def main(argv):
    trace_dir = argv[0] if argv else os.path.join(ROOT, "benchmark", ".trace")
    needles = argv[1:] or ["flash_", "fused_ce", "all-reduce"]
    path = xplane.find_xplane(trace_dir)
    print("trace file:", path, os.path.getsize(path), "bytes")
    profile = xplane.load(path)
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            span = (max(e.start_ns + e.duration_ns for e in events)
                    - min(e.start_ns for e in events)) / 1e9
            print(f"  line {line.name!r}: {len(events)} events over {span:.3f}s, "
                  f"first start {events[0].start_ns}")
            shown = 0
            for e in events[:3]:
                print(f"    {e.name!r} dur {e.duration_ns}ns stats "
                      f"{[(k, str(v)[:80]) for k, v in e.stats]}")
            for e in events:
                if any(n in e.name for n in needles) and shown < 4:
                    shown += 1
                    print(f"    MATCH {e.name!r} dur {e.duration_ns}ns stats "
                          f"{[(k, str(v)[:160]) for k, v in e.stats]}")
    red = xplane.reduce(profile)
    if red is not None:
        print("reduction:", red._replace(ops={}))


if __name__ == "__main__":
    main(sys.argv[1:])
