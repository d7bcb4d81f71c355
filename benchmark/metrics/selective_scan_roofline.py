"""Roofline share of the prompt scan kernel (``selective_scan``): what its
calls in the traced window MUST do (a call is one scan layer of one prefill
program; over a prefill's scan layers the REAL prompt tokens, the ``prefill``
spans' ``scan_tokens``, each with its Delta, c, B, C read and its y written,
and a last state a row: the family's ``selective_scan_bytes`` and
``selective_scan_flops``) against the bound that sets its least time, over
the summed device time of those calls. The recurrence runs a token at a time
on the vector unit, so the share is far from either roof by construction;
the padding of a bucket is the rest of the waste it shows."""
from benchmark import flops

KERNEL = "selective_scan"


def read(run):
    fam = run["family"]
    facts = getattr(fam, "trace_facts", lambda run: None)(run)
    if facts is None or not facts["scan_tokens"]:
        return None
    spent, calls = fam.kernel_ns(facts["ops"], KERNEL)
    if not calls:
        return None
    cfg = run["config"]
    layers = fam.layer_kinds(cfg).count("mamba")
    least, _ = flops.roofline_seconds(
        layers * fam.selective_scan_flops(cfg, facts["scan_tokens"]),
        layers * fam.selective_scan_bytes(cfg, facts["scan_tokens"], facts["scan_rows"]),
        run["peaks"])
    return flops.share(least, spent / 1e9, "selective_scan_roofline")
