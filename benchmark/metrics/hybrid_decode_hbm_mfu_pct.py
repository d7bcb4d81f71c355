"""The WHOLE decode step's share of the HBM peak, for a model whose caches
are of several kinds (``phi4flash``): the bytes the decode steps inside the
traced window MUST move (the family's ``decode_step_bytes``: every weight
once; the ONE cached layer's K and V over each live row's context, once for
each layer that reads it; the tokens inside the windows once a window layer;
every live row's scan states and convolution tails, read and written) over
the HBM peak, over the device time of the ``jit_step`` programs in the
window. Rows, contexts and window tokens are the means of the program's
``decode_step`` spans there. It is for this model what ``decode_hbm_roofline``
is for a dense one; the name carries ``mfu`` because it is the share of a
peak taken over the whole step (PERF.md section 7)."""
from benchmark import flops


def read(run):
    fam = run["family"]
    facts = getattr(fam, "trace_facts", lambda run: None)(run)
    if facts is None:
        return None
    need = facts["steps"] * fam.decode_step_bytes(
        run["config"], facts["rows"], facts["shared_kv_tokens"], facts["window_tokens"])
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], facts["step_ns"] / 1e9,
                       "hybrid_decode_hbm_mfu_pct")
