"""The WHOLE decode step's share of the HBM peak, for the ``kimivl`` family:
the bytes the decode steps inside the traced window MUST read (the family's
``decode_step_bytes``: the weights every step reads whatever its routing; the
three matrices, 17.3 MB, of each expert the step's live rows hit, the spans'
``experts_touched``; the cached latent row, 1,152 B a layer, of every live
row's context) over the HBM peak, over the device time of the ``jit_step``
programs in the window. Experts and context are the means of the program's
``decode_step`` spans there and of the clients' tokens
(``decode_trace_facts``). The name carries ``mfu`` because it is the share of
a peak taken over the whole step (PERF.md section 7)."""
from benchmark import flops


def read(run):
    fam = run["family"]
    if not hasattr(fam, "call_attention_flops"):  # another family's cell
        return None
    facts = fam.decode_trace_facts(run)
    if facts is None:
        return None
    need = facts["steps"] * fam.decode_step_bytes(
        run["config"], facts["touched"], facts["context_tokens"] / facts["steps"])
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], facts["step_ns"] / 1e9,
                       "kimivl_decode_hbm_mfu_pct")
