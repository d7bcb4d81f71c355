"""Share of the traced window in which no operation ran on the device (1 -
union of the device's operation intervals / window), averaged over the chips
of the cell. Serving cells."""
from benchmark.trace import summary


def read(run):
    if run["trace"] is None:
        return None
    b = summary.busy_and_window(run["trace"])
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
