"""Roofline share of the block-table read over two-heads-a-line K/V (the
``paged_attention`` kernel's calls inside the decode steps of the traced
window: one a step for each attention layer of the ``lfm2`` family, whose
64-wide key/value heads lie two a 128-lane line and whose queries are padded
to match) against HBM bandwidth: what each call MUST read (K and V of every
live row's context, the spans' mean ``paged_kv_tokens`` a step x the family's
``kv_bytes_per_token``, and each row's padded queries and their results: the
family's ``paged_read_bytes``) over the HBM peak, over the summed device time
of those calls. What a call reads beyond that (the tail of a row's last
block) and the half of every product that the padding wastes are what this
share shows."""
from benchmark import flops

KERNEL = "paged_attention"


def read(run):
    fam = run["family"]
    if not hasattr(fam, "paged_read_bytes"):
        return None
    facts = fam.decode_trace_facts(run)
    ctx = fam.span_mean(run, "paged_kv_tokens", traced=True)
    if facts is None or ctx is None:
        return None
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    need = calls * fam.paged_read_bytes(run["config"], facts["rows"], ctx)
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "packed_kv_attention_roofline")
