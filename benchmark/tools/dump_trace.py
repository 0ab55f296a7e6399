#!/usr/bin/env python3
"""Print what a profiler trace holds, for looking at one by hand.

    python benchmark/tools/dump_trace.py <trace dir or .xplane.pb> [--events 8]

Planes, their lines, how many events each holds, the first events of each
line, and per device line the names that took most time.
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--events", type=int, default=8)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    from benchmark.reduce import trace as tr

    path = args.path
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    print("file", path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            for ev in events[: args.events]:
                stats = {k: v for k, v in list(ev.stats)[:6]}
                print(f"    {ev.name!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} stats={stats}")
            if plane.name.startswith("/device:") and events:
                acc = defaultdict(lambda: [0, 0.0])
                for ev in events:
                    acc[ev.name][0] += 1
                    acc[ev.name][1] += ev.duration_ns
                ranked = sorted(acc.items(), key=lambda kv: -kv[1][1])
                for name, (n, ns) in ranked[: args.top]:
                    print(f"    TOP {name!r} n={n} total_ms={ns / 1e6:.3f}")
    reduced = tr.load(path)
    print("window_s", reduced.window_s, "busy_mean_s", tr.busy_mean_s(reduced),
          "markers", reduced.markers[:6])
    print("top_ops", tr.top_ops(reduced))
    print("gaps", tr.attribute_gaps(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main())
