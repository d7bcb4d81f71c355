"""Share of the traced window in which a collective (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute) ran on a device while no
other operation did, averaged over the chips. Cells on one chip have no
collectives and report nothing."""
from benchmark.trace import reduce as R, summary


def read(run):
    if run["trace"] is None or run["chips"] < 2:
        return None
    ops = summary.device_ops(run["trace"])
    t0, t1 = summary.window_ns(run["trace"])
    exposed = [R.exposed_collective_ns(ev) for ev in ops.values()]
    return 100.0 * sum(exposed) / len(exposed) / (t1 - t0)
