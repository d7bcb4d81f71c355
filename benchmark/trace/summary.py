"""What the result line carries from the trace: ``busy_s`` / ``window_s`` and
the ``breakdown`` (top device operations, longest idle gaps by what the host
was doing)."""
from __future__ import annotations

from . import reduce as R


def window_ns(reduced) -> tuple:
    """The traced window on the trace's clock: between the two sync
    annotations, else the span of the device events."""
    t0, t1 = reduced.get("t0_ns"), reduced.get("t1_ns")
    if t0 is None or t1 is None:
        ev = [e for d in reduced["devices"].values() for e in d["ops"]]
        t0, t1 = min(e[1] for e in ev), max(e[1] + e[2] for e in ev)
    return t0, t1


def device_ops(reduced) -> dict:
    """{device: leaf operation events inside the traced window}."""
    t0, t1 = window_ns(reduced)
    return {name: R.clip(d["ops"], t0, t1)
            for name, d in sorted(reduced["devices"].items()) if d["ops"]}


def host_spans(reduced, spans) -> list:
    """The benchmark's own annotations plus the program's host spans, moved
    onto the trace's clock by the sync annotation."""
    rows = [h for h in reduced["host"] if not h[0].startswith("bench.sync")]
    if spans is not None and reduced.get("sync_ns") is not None:
        off = reduced["sync_ns"]
        rows += [[name, t0 + off, t1 - t0] for name, t0, t1, _, _ in spans.rows]
    return rows


def busy_and_window(reduced) -> dict:
    t0, t1 = window_ns(reduced)
    per_dev = [R.busy_ns(ev) for ev in device_ops(reduced).values()]
    if not per_dev or not sum(per_dev):
        raise RuntimeError("the trace holds no device operation")
    return {"busy_s": sum(per_dev) / len(per_dev) / 1e9, "window_s": (t1 - t0) / 1e9}


def breakdown(reduced, spans) -> dict:
    t0, t1 = window_ns(reduced)
    ops = device_ops(reduced)
    first = next(iter(ops.values()))
    return {"device_ops": R.top_ops(list(ops.values()), 10),
            "idle_gaps": R.gap_breakdown(first, t0, t1, host_spans(reduced, spans))}
