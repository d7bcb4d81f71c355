"""Mean over the window's decode steps of the tokens inside the live rows'
windows (the ``window_tokens`` attribute of the engine's ``decode_step`` host
spans: the sum over the live rows of ``min(context, sliding_window)``): what
each window layer reads a step, whatever the context has grown to."""


def read(run):
    return getattr(run["family"], "span_mean", lambda run, key: None)(run, "window_tokens")
