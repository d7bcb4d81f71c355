"""Mean duration of the engine's ``prefill`` host spans of the LARGEST bucket
the window's prompts reached (``bucket_t``: 8,192 positions in the cell, whose
long mode is 4,608-6,656 tokens a prompt, one row a call): one long prompt
from its arguments to the host read-back of its logits, which is the stall it
puts into every live stream. ``prefill_step_ms`` is the mean over every
bucket."""


def read(run):
    if run["spans"] is None or not hasattr(run["family"], "band_flops"):
        return None
    rows = [r for r in run["spans"].named("prefill", *run["span_window_ns"])
            if "bucket_t" in r[4]]
    if not rows:
        return None
    widest = max(r[4]["bucket_t"] for r in rows)
    took = [r[2] - r[1] for r in rows if r[4]["bucket_t"] == widest]
    return sum(took) / len(took) / 1e6
