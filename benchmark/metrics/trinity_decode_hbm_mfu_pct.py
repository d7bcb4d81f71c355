"""The WHOLE decode step's share of the HBM peak, for the window-and-full /
routed-expert share (``afmoe``): the bytes the decode steps inside the traced
window MUST move (the family's ``decode_step_bytes``: every weight outside the
routed experts once, of the embedding only the fed rows; the three matrices of
each HELD expert the step's live rows hit, the spans' ``experts_touched``; K
and V of the four full layers over each live row's context, the spans'
``paged_kv_tokens``; of the twelve window layers over its part inside the
window, ``window_tokens``) over the HBM peak, over the device time of the
``jit_step`` programs in the window. Rows, experts and tokens are the means of
the program's ``decode_step`` spans there. The name carries ``mfu`` because it
is the share of a peak taken over the whole step (PERF.md section 7)."""
from benchmark import flops


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):  # another family's cell
        return None
    facts = fam.decode_trace_facts(run)
    ctx = fam.span_mean(run, "paged_kv_tokens", traced=True)
    win = fam.span_mean(run, "window_tokens", traced=True)
    if facts is None or ctx is None or win is None:
        return None
    need = facts["steps"] * fam.decode_step_bytes(
        run["config"], facts["rows"], facts["touched"], ctx, win)
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], facts["step_ns"] / 1e9,
                       "trinity_decode_hbm_mfu_pct")
