"""Roofline share of the latent decode-attention kernel
(``mla_paged_attention``) against HBM bandwidth: the bytes its calls in the
traced window MUST read (the cached latent row, ``latent_bytes_per_token``,
of every live row's context once a layer, and each row's absorbed queries
and output) over the HBM peak, over the summed device time of those calls.
What the kernel reads beyond that (the padding of a pooled row to whole lane
tiles, the tail of a row's last block) is the waste this share shows."""
from benchmark import flops

KERNEL = "mla_paged_attention"


def read(run):
    fam = run["family"]
    facts = getattr(fam, "decode_trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    cfg = run["config"]
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    rows_io = facts["rows"] * cfg["num_attention_heads"] * (width + cfg["kv_lora_rank"]) * 2
    # a call reads one layer's rows of one step's live contexts
    need = calls * (facts["context_tokens"] / facts["steps"] * fam.latent_bytes_per_token(cfg)
                    + rows_io)
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "latent_attention_roofline")
