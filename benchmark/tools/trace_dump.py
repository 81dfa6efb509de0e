"""Look at one trace by hand: planes, lines, and the longest events with
their stats.  python3 -m benchmark.tools.trace_dump <file.xplane.pb>"""

import sys
from collections import defaultdict

from jax.profiler import ProfileData


def dump(path: str, top: int = 12) -> None:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{first:.0f}..{last:.0f} ns")
            total, sample = defaultdict(float), {}
            for e in events:
                total[e.name] += e.duration_ns
                sample.setdefault(e.name, e)
            for name in sorted(total, key=total.get, reverse=True)[:top]:
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in sample[name].stats}
                print(f"    {total[name] / 1e6:10.3f} ms  {name[:80]!r}  "
                      f"{stats}")


if __name__ == "__main__":
    dump(sys.argv[1])
