"""95th percentile of the gaps between consecutive tokens of a stream at the
client, pooled over the window. In the saturated cell about a third of the
decode steps carry a prefill beside them, and this percentile lies inside
that third: it follows which prompts were prefilled in the window, spread by
7.7% over six seeds (my chip runs, PR 23, call 4) and cannot carry a bound."""
import numpy as np


def read(run):
    return float(np.percentile(run["gaps_ms"], 95)) if len(run["gaps_ms"]) else None
