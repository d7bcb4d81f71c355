"""Roofline share of the routed-expert kernel in the decode programs
(``moe_experts_t16``: the 16-row tiles of a decode batch; prefill's tiles are
wider and carry another name) against HBM bandwidth: the bytes its calls in
the traced window MUST read (the three matrices of each expert the step's
live rows hit, the spans' ``experts_touched`` spread over the expert layers,
and each (row, choice) pair's input and output row) over the HBM peak, over
the summed device time of those calls. At a few dozen rows the kernel is
memory-bound: its FLOPs over the bf16 peak are a tenth of this."""
from benchmark import flops

KERNEL = "moe_experts_t16"


def read(run):
    fam = run["family"]
    facts = getattr(fam, "decode_trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    cfg = run["config"]
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    pairs = facts["rows"] * cfg["num_experts_per_tok"]
    per_call = facts["touched"] / fam.expert_layers(cfg) * fam.expert_bytes(cfg) \
        + 2 * pairs * cfg["hidden_size"] * 2
    return flops.share(calls * per_call / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "expert_ffn_roofline")
