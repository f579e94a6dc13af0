"""Print what an ``.xplane.pb`` holds: planes, lines, event counts and a
few events of each line.  For looking at a trace by hand before trusting a
reduction of it.

    python3 benchmark/tools/dump_trace.py <file.xplane.pb>
"""

from __future__ import annotations

import sys


def main(path: str) -> int:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:4]:
                print(f"    {ev.name[:90]!r} start={ev.start_ns:.0f} dur={ev.duration_ns:.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
