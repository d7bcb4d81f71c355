"""Roofline share of the prefill-call attention kernel
(``mla_prefill_attention``: a call of query positions against the latent rows
the cache already holds for the row and its own, causal) against the MXU's
bfloat16 peak: the FLOPs its calls MUST do over that peak, over the summed
device time of the kernel's calls. The FLOPs are the EXPANDED form's count
(``families/kimivl.call_attention_flops``: a product of 192 and one of 128 for
every live (query, key) pair and every head) of the pairs the program's
chunked ``prefill`` spans report (``attended_pairs``: a fed position sees what
is cached and the fed up to itself; the bucket's padding and the key blocks
the kernel skips are NOT in them), a layer a call of the kernel, WHATEVER form
the program runs: an absorbed kernel does 3.4 x the products a pair and reads
a lower share, and a later change of form keeps the yardstick. Only the calls
whose host span lies wholly inside the traced window are read, each with the
kernel calls inside its own ``jit_prefill`` program on the device
(``chunked_prefill_spans``' ``program``). The
context's up-projection and its gather from the pool are XLA's, beside the
kernel, and not in its time. At 128-wide heads on this chip the kernel is
bound by the vector unit's softmax beside the MXU (``window_flash_roofline``
reads 40%)."""
from benchmark import flops

KERNEL = "mla_prefill_attention"


def read(run):
    fam = run["family"]
    if not hasattr(fam, "call_attention_flops"):  # another family's cell
        return None
    fills, calls = fam.chunked_prefill_spans(run), fam.kernel_calls(run, KERNEL)
    if not fills or not calls:
        return None
    cfg = run["config"]
    need = spent = 0.0
    for sp in fills:
        if sp["program"] is None or "attended_pairs" not in sp:
            continue
        at, took = sp["program"]
        ns, n = fam.calls_between(calls, at, at + took)
        if n != cfg["num_hidden_layers"]:
            continue  # a program cut by the edge of the traced stretch
        spent += ns
        need += n * fam.call_attention_flops(cfg, sp["attended_pairs"])
    if not spent:
        return None
    return flops.share(need / run["peaks"]["bf16_flops_per_s"], spent / 1e9,
                       "latent_prefill_attention_roofline")
