"""Mean SELF time of the engine's ``decode_step`` host span inside the
window: its duration less the ``decode_readback`` and ``decode_land`` spans
it holds (found by containment among the spans of its thread). What is left
is the program lookup, the key split, the host-to-device transfers and the
enqueue. None where the program's ``decode_step`` holds no such spans (it
then closes after the dispatch and times another stretch)."""
from bisect import bisect_left

CHILDREN = ("decode_readback", "decode_land")


def read(run):
    if run["spans"] is None:
        return None
    steps = run["spans"].named("decode_step", *run["span_window_ns"])
    inner = sorted((r for r in run["spans"].rows if r[0] in CHILDREN),
                   key=lambda r: r[1])
    starts = [r[1] for r in inner]
    total, n = 0, 0
    for _, t0, t1, tid, _ in steps:
        held = [r for r in inner[bisect_left(starts, t0):bisect_left(starts, t1)]
                if r[3] == tid and r[2] <= t1]
        if held:
            total += (t1 - t0) - sum(r[2] - r[1] for r in held)
            n += 1
    return total / n / 1e6 if n else None
