"""Look at one trace by hand before writing code against it: planes,
their lines, and the first events of each line with every stat.

    python3 chipbench/tools/xplane_dump.py <file.xplane.pb> [events per line]
"""

from __future__ import annotations

import sys


def dump(path: str, per_line: int = 4, out=sys.stdout) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for e in events[:per_line]:
                stats = {k: (str(v)[:120]) for k, v in e.stats}
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={stats}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
