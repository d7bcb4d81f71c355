"""Roofline share of the prompt-attention kernel (``window_flash``: causal,
grouped, a band of ``sliding_window`` keys in a window layer, every key before
the query in a full one) against the MXU's bfloat16 peak: the FLOPs its calls
MUST do (two products of ``head_dim`` for every (query, key) pair inside the
band and every query head: the ``band_tokens_window`` and ``band_tokens_full``
of the program's ``prefill`` spans x the family's layers of each kind,
``band_flops``; the key blocks the kernel skips and the bucket's padding are
NOT in them) over that peak, over the summed device time of the kernel's calls.
Only the prefills whose host span lies wholly inside the traced window are
read, each with the kernel calls that start inside its span. The kernel's
bytes (queries in, results out, K and V once a call: ``band_bytes``) are 1-2%
of what the HBM peak moves in the same time: it is bound by the MXU and, at
128-wide heads on this chip, by the vector unit's softmax beside it."""
from benchmark import flops

KERNEL = "window_flash"


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):
        return None
    fills, calls = fam.prefill_spans(run), fam.kernel_calls(run, KERNEL)
    if not fills or not calls:
        return None
    cfg = run["config"]
    need = spent = 0.0
    for sp in fills:
        if "band_tokens_window" not in sp:
            continue
        ns, n = fam.calls_between(calls, sp["t0_ns"], sp["t1_ns"])
        if not n:
            continue
        spent += ns
        need += (fam.band_flops(cfg, sp["band_tokens_window"])
                 * fam.layers_of(cfg, "sliding_attention")
                 + fam.band_flops(cfg, sp["band_tokens_full"])
                 * fam.layers_of(cfg, "full_attention"))
    if not spent:
        return None
    return flops.share(need / run["peaks"]["bf16_flops_per_s"], spent / 1e9,
                       "window_flash_roofline")
