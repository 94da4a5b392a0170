"""Print what a recorded trace holds: planes, lines, event counts and the
most frequent event names of each line (looked at by hand before the
reduction was trusted)."""

import collections
import glob
import os
import sys


def main() -> int:
    import jax
    root = sys.argv[1]
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(paths[-1])
    print(paths[-1], os.path.getsize(paths[-1]))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name[:70] for e in evs)
            dur = sum(e.duration_ns for e in evs) / 1e9
            print(f"  LINE {line.name!r}: {len(evs)} events, {dur:.3f}s; "
                  f"{names.most_common(6)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
