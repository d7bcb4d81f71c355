"""Mean over the window's decode steps of the context the ONE cached layer
holds for the step's live rows (the ``shared_kv_tokens`` attribute of the
engine's ``decode_step`` host spans: the sum over the live rows of their
context). Every reader of that pool, the layer itself and the cross layers,
reads this many tokens' K and V a step; rows that pad the bucket read none."""


def read(run):
    return getattr(run["family"], "span_mean", lambda run, key: None)(run, "shared_kv_tokens")
