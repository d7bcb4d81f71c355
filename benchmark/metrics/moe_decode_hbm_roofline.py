"""Roofline share of the decode programs of a routed-expert model against HBM
bandwidth: the bytes the decode steps inside the traced window MUST read (the
weights every step reads whatever its routing, the family's
``dense_bytes_per_step``; the experts the step's live rows hit, the spans'
``experts_touched`` x ``expert_bytes``; the latent cache of every live row at
its real context length, ``latent_bytes_per_token`` a layer) over the HBM
peak, over the device time of the ``jit_step`` programs in the window. It
is for a routed model what ``decode_hbm_roofline`` is for a dense one, whose
static ``weight_bytes`` cannot follow a routing (so that share lists the dense
cell alone)."""
from benchmark import flops


def read(run):
    fam = run["family"]
    facts = getattr(fam, "decode_trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    cfg = run["config"]
    need = facts["steps"] * (fam.dense_bytes_per_step(cfg)
                             + facts["touched"] * fam.expert_bytes(cfg)) \
        + facts["context_tokens"] * cfg["num_hidden_layers"] * fam.latent_bytes_per_token(cfg)
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], facts["step_ns"] / 1e9,
                       "moe_decode_hbm_roofline")
