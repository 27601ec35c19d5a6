"""Look at a trace by hand: planes, lines, a few events of each with their
stats.  ``python3 perfbench/inspect_trace.py <trace dir> [out file]``."""

from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def main(argv) -> int:
    from jax.profiler import ProfileData

    from perfbench.trace_reduce import find_xplane

    path = find_xplane(argv[0])
    out = open(argv[1], "w") if len(argv) > 1 else sys.stdout
    print(path, os.path.getsize(path), file=out)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines), file=out)
        for line in lines:
            events = list(line.events)
            print("  LINE", line.name, len(events), file=out)
            seen = {}
            for e in events:
                key = e.name.split("(")[0][:40]
                seen.setdefault(key, [0, 0.0, e])
                seen[key][0] += 1
                seen[key][1] += e.duration_ns
            top = sorted(seen.items(), key=lambda kv: -kv[1][1])[:25]
            for key, (n, dur, e) in top:
                stats = [(k, str(v)[:160]) for k, v in e.stats][:12]
                print(f"     {n:6d} {dur / 1e6:10.3f} ms  {e.name[:80]}  {stats}", file=out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
