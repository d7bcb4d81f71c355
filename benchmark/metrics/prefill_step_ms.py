"""Mean duration of the engine's ``prefill`` host span inside the window: one
prefill program from its arguments to the host read-back of its logits and
the landing of the first tokens."""


def read(run):
    if run["spans"] is None:
        return None
    return run["spans"].mean_ms("prefill", *run["span_window_ns"])
