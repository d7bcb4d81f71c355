"""Mean duration of the engine's ``decode_land`` host span inside the
window: a step's tokens into their streams, with the retirements and page
frees that follow (Python, beside the clients' threads). None where the
program has no such span."""


def read(run):
    if run["spans"] is None:
        return None
    return run["spans"].mean_ms("decode_land", *run["span_window_ns"])
