"""Print what a profiler trace holds, to match names by hand.

    python3 benchmarks/chip/trace_names.py TRACE_DIR [--top 40]

Per plane and line: the number of events, and the names that take the
most time with their count and the stats of one event of each.
"""

from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __package__ in (None, ""):
    sys.path[0] = str(ROOT)

from benchmarks.chip.tracefile import find_xplane  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(args.trace_dir))
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            stats = {}
            for e in line.events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
                if e.name not in stats:
                    stats[e.name] = {k: str(v)[:120] for k, v in e.stats}
            print(f"  LINE {line.name}: {sum(count.values())} events, "
                  f"{len(count)} names")
            for name, ns in total.most_common(args.top):
                print(f"    {ns / 1e6:12.3f} ms  x{count[name]:<7d} {name[:100]}"
                      f"  {stats[name]}")


if __name__ == "__main__":
    main()
