"""Mean over the window's decode steps of the context the attention layers
hold for the step's live rows (the ``paged_kv_tokens`` attribute of the
engine's ``decode_step`` host spans: the sum over the live rows of their
context). Each attention layer reads this many tokens' K and V a step; rows
that pad the bucket read none, and the convolution layers read none of it."""


def read(run):
    return getattr(run["family"], "span_mean", lambda run, key: None)(run, "paged_kv_tokens")
