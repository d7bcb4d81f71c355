"""Mean duration of the engine's ``decode_build`` host span inside the
window: what the scheduler does before a decode step can be dispatched (map
the blocks the step writes, guard shared ones, choose the bucket, fill the
tables, positions, tokens and temperatures). None where the program has no
such span."""


def read(run):
    if run["spans"] is None:
        return None
    return run["spans"].mean_ms("decode_build", *run["span_window_ns"])
