"""Milliseconds of prefill a second, for the ``kimivl`` family's cell: the
summed device time of the ``jit_prefill`` programs inside the traced window
over the window's length: how much of the serving loop the prefill calls
take from the decode steps that share it (1,000 would be all of it). The host
spans do not say it: the span of a call that is not its prompt's last closes
at the dispatch, and the decode step enqueued behind it waits on the device.

Beside its number the reader PRINTS what share of the whole window's gaps
followed a prefill call (``families/kimivl.gaps_behind_a_call``, from the host
spans): the number ISSUE 47 holds the cell's placement to, here so that it has
a committed source."""
from benchmark.trace import reduce as R, summary


def read(run):
    fam = run["family"]
    if not hasattr(fam, "call_attention_flops"):  # another family's cell
        return None
    if not fam.chunked_prefill_spans(run):  # a program without such calls
        return None
    behind = fam.gaps_behind_a_call(run)
    if behind:
        print(f"kimivl_prefill_ms_per_s: {behind[0]:.1f}% of the window's {behind[1]} gaps "
              "follow a prefill call (their step shared its scheduler pass with one)",
              flush=True)
    red = run["trace"]
    t0, t1 = summary.window_ns(red)
    spent = sum(R.total_ns(R.clip(dev["modules"], t0, t1), r"^jit_prefill\(")[0]
                for dev in red["devices"].values())
    return spent / 1e6 / ((t1 - t0) / 1e9) if spent and t1 > t0 else None
