"""Prints what a profiler trace holds: planes, lines, event counts and a
few events with their stats per device line.  For looking at one trace
by hand before writing a reader against it.

    python3 bench/tools/dump_trace.py <trace dir or .xplane.pb> [n]
"""
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(argv) -> int:
    from jax.profiler import ProfileData

    from harness import trace
    path = argv[0]
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    n = int(argv[1]) if len(argv) > 1 else 5
    print("file", path, os.path.getsize(path), "bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for ln in lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r} events={len(evs)}")
            if plane.name.startswith("/device:") or n > 5:
                names = Counter(e.name for e in evs)
                print("    top names:", names.most_common(12))
                for e in evs[:n]:
                    print(f"    {e.name!r} start={e.start_ns} "
                          f"dur={e.duration_ns} stats={dict(e.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
