"""Mean host time of one decode step inside the window, from the START of
the engine's ``decode_step`` span to the END of the ``schedule`` span that
holds it: table build, dispatch, the blocking read-back of the tokens and
their landing in the streams. (The ``decode_step`` span alone closes after
the dispatch, before the read-back: about 2.5 ms beside a step of 104 ms,
which is what PR 22's 2.39 ms was; my chip run, PR 23, call 4.)"""


def read(run):
    if run["spans"] is None:
        return None
    t0, t1 = run["span_window_ns"]
    sched = sorted(run["spans"].named("schedule", t0, t1), key=lambda r: r[1])
    steps = sorted(run["spans"].named("decode_step", t0, t1), key=lambda r: r[1])
    total, n, j = 0, 0, 0
    for s in sched:
        while j < len(steps) and steps[j][1] < s[1]:
            j += 1
        if j < len(steps) and steps[j][2] <= s[2]:
            total += s[2] - steps[j][1]
            n += 1
    return total / n / 1e6 if n else None
