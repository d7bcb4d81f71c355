"""Mean SELF time of the engine's ``schedule`` host span inside the window:
its duration less what the spans it holds cover (every span of its thread
that lies inside it, whatever its name: ``admit``, ``prefill``,
``decode_build``, ``decode_step`` and theirs). This is the part of a
scheduler step that no span names yet."""
from bisect import bisect_left

from benchmark.trace import reduce as R


def read(run):
    if run["spans"] is None:
        return None
    sched = run["spans"].named("schedule", *run["span_window_ns"])
    rows = sorted(run["spans"].rows, key=lambda r: r[1])
    starts = [r[1] for r in rows]
    total = 0
    for s in sched:
        _, t0, t1, tid, _ = s
        held = [[r[0], r[1], r[2] - r[1]]
                for r in rows[bisect_left(starts, t0):bisect_left(starts, t1)]
                if r[3] == tid and r[2] <= t1 and r is not s]
        total += (t1 - t0) - R.busy_ns(held)
    return total / len(sched) / 1e6 if sched else None
