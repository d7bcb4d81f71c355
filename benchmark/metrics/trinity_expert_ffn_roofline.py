"""Roofline share of the routed-expert kernel inside the decode programs of
the ``afmoe`` family (``moe_experts_t16``, and ``moe_experts_t256`` in the
64-row bucket, whose 512 (row, choice) pairs pass the kernel's tile rule; 16
HELD experts of 2048 x 1024 of the router's 128, read out of the stack of the
scanned repetitions where they lie) against HBM bandwidth: the bytes its calls
in the traced window MUST read (the three matrices of each held expert the
step's live rows hit, the spans' ``experts_touched`` spread over the expert
layers, and the input and output row of each (row, choice) pair that fell on
a held expert, the spans' ``expert_assignments``) over the HBM peak, over the
summed device time of those calls. The prefill programs' calls carry the same
names and are left out: only calls that start inside a ``jit_step`` program
are read."""
from benchmark import flops

KERNELS = ("moe_experts_t16", "moe_experts_t256")


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):
        return None
    facts = fam.decode_trace_facts(run)
    pairs = fam.span_mean(run, "expert_assignments", traced=True)
    if facts is None or pairs is None:
        return None
    hits = [fam.calls_inside(run, k, r"^jit_step\(") for k in KERNELS]
    spent, calls = sum(h[0] for h in hits), sum(h[1] for h in hits)
    if not calls:
        return None
    cfg = run["config"]
    layers = fam.expert_layers(cfg)
    per_call = facts["touched"] / layers * fam.expert_bytes(cfg) \
        + 2 * pairs / layers * cfg["hidden_size"] * 2
    return flops.share(calls * per_call / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "trinity_expert_ffn_roofline")
