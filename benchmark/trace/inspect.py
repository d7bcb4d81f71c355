"""Look at one trace by hand: ``python -m benchmark.trace.inspect <dir>``
prints every plane and line of the newest xplane file under ``<dir>`` with
its event count, summed duration and most frequent event names."""
from __future__ import annotations

import glob
import os
import sys
from collections import Counter


def main(directory):
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    if not files:
        sys.exit(f"no xplane file under {directory}")
    print(files[-1], os.path.getsize(files[-1]), "bytes")
    for plane in ProfileData.from_file(files[-1]).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            names = Counter(e.name for e in events)
            total = sum(e.duration_ns for e in events) / 1e6
            print(f"  line {line.name!r}: {len(events)} events, {total:.2f} ms, "
                  f"from {min(e.start_ns for e in events):.0f} ns")
            for name, n in names.most_common(12):
                ms = sum(e.duration_ns for e in events if e.name == name) / 1e6
                print(f"    {n:6d} x {name[:110]}  ({ms:.2f} ms)")
            if plane.name.startswith("/device") and line.name == "XLA Ops":
                e = events[len(events) // 2]
                print("    stats of one event:", {k: str(v)[:80] for k, v in e.stats})


if __name__ == "__main__":
    main(sys.argv[1])
