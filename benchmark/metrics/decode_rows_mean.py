"""Mean number of live rows in a decode step inside the window: the ``rows``
attribute of the engine's ``decode_step`` host spans."""


def read(run):
    if run["spans"] is None:
        return None
    rows = run["spans"].named("decode_step", *run["span_window_ns"])
    return sum(r[4]["rows"] for r in rows) / len(rows) if rows else None
