"""Mean duration of the engine's ``decode_readback`` host span inside the
window: the blocking copy of a decode step's tokens to the host, which
holds the wait for the step's program on the device. None where the program
has no such span."""


def read(run):
    if run["spans"] is None:
        return None
    return run["spans"].mean_ms("decode_readback", *run["span_window_ns"])
