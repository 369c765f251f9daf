#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, event counts, the
events with most time on each line and the statistics they carry.

    python3 benchmark/tools/trace_summary.py <dir or .xplane.pb> [events]
"""

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmark.harness import trace as tr

    path = argv[0] if argv[0].endswith(".pb") else tr.newest_xplane(argv[0])
    show = int(argv[1]) if len(argv) > 1 else 8
    print(f"{path}: {os.path.getsize(path) / 2**20:.1f} MiB")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{first * 1e-9:.6f} .. {last * 1e-9:.6f} s")
            time_of = collections.Counter()
            count_of = collections.Counter()
            sample = {}
            for e in events:
                time_of[e.name] += e.duration_ns
                count_of[e.name] += 1
                sample.setdefault(e.name, e)
            for name, ns in time_of.most_common(show):
                stats = {k: (v[:120] if isinstance(v, str) else v)
                         for k, v in sample[name].stats}
                print(f"    {ns * 1e-6:10.3f} ms x{count_of[name]:<6} "
                      f"{name[:80]!r} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
