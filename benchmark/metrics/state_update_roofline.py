"""Roofline share of the one-token scan kernel (``state_update``): what its
calls in the traced window MUST do (a call is one scan layer of one decode
step: each live row's state read and written, the family's
``state_update_bytes`` and ``state_update_flops`` at the spans' mean live
rows) against the bound that sets its least time (HBM: a state entry is moved
twice and used six times), over the summed device time of those calls."""
from benchmark import flops

KERNEL = "state_update"


def read(run):
    fam = run["family"]
    facts = getattr(fam, "trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    cfg = run["config"]
    least, _ = flops.roofline_seconds(
        calls * fam.state_update_flops(cfg, facts["rows"]),
        calls * fam.state_update_bytes(cfg, facts["rows"]), run["peaks"])
    return flops.share(least, spent / 1e9, "state_update_roofline")
