"""Roofline share of the decode reads of differential attention (the
``paged_attention`` kernel's calls inside the decode steps of the traced
window: eight window layers over their rings, the full-attention layer and
seven cross layers over ONE pool) against HBM bandwidth: the K and V each
call MUST read (the live context of every row for a reader of the paged pool,
the tokens inside the window for a window layer: the spans' mean
``shared_kv_tokens`` and ``window_tokens`` a step x the family's
``kv_bytes_per_token``) and each row's padded queries and output, over the
HBM peak, over the summed device time of those calls. What a call reads
beyond that (the tail of a row's last block) is the waste this share shows."""
from benchmark import flops

KERNEL = "paged_attention"


def read(run):
    fam = run["family"]
    facts = getattr(fam, "trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    cfg = run["config"]
    kinds = fam.layer_kinds(cfg)
    readers = fam.paged_readers(cfg) + kinds.count("window")
    steps = calls / readers  # every attention layer of a step is one call
    # queries and outputs: H heads of 2 h a row, in and out
    rows_io = facts["rows"] * 2 * 2 * cfg["hidden_size"] * 2
    need = steps * (fam.paged_readers(cfg) * facts["shared_kv_tokens"] * fam.kv_bytes_per_token(cfg)
                    + facts["window_tokens"] * fam.window_bytes_per_token(cfg)
                    + readers * rows_io)
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "diff_attention_roofline")
