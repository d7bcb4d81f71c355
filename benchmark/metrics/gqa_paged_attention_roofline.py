"""Roofline share of the block-table read over grouped 128-wide heads as they
lie (the ``paged_attention`` kernel's calls inside the decode steps of the
traced window: one a step for each of the ``afmoe`` family's layers, twelve
over the window layers' rings and four over the full layers' pages, a K/V head
serving eight query heads) against HBM bandwidth: what the calls MUST read (K
and V of every live row's context a full layer, of its part inside the window
a window layer: the spans' mean ``paged_kv_tokens`` and ``window_tokens`` a
step x the family's ``kv_bytes_per_token``; each row's queries and their
results a call: ``paged_read_bytes``) over the HBM peak, over the summed device
time of those calls. What a call reads beyond that (the tail of a row's last
block) and the (token, head) lines of the other three K/V heads that every
query's product crosses are what this share shows."""
from benchmark import flops

KERNEL = "paged_attention"


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):
        return None
    facts = fam.decode_trace_facts(run)
    ctx = fam.span_mean(run, "paged_kv_tokens", traced=True)
    win = fam.span_mean(run, "window_tokens", traced=True)
    if facts is None or ctx is None or win is None:
        return None
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    cfg = run["config"]
    full, ring = fam.layers_of(cfg, "full_attention"), fam.layers_of(cfg, "sliding_attention")
    steps = calls / (full + ring)  # every layer of a step is one call
    need = steps * (full * fam.paged_read_bytes(cfg, facts["rows"], ctx)
                    + ring * fam.paged_read_bytes(cfg, facts["rows"], win))
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "gqa_paged_attention_roofline")
