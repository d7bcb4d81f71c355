"""Median DEVICE time of a full prefill call of the ``kimivl`` family's cell:
the ``jit_prefill`` programs of the traced window whose call fed a whole
``prefill_chunk`` (8,192 positions: the chunked ``prefill`` spans' ``feed``),
each span with its own program on the device (``chunked_prefill_spans``). It
is the stall one call puts into every live stream; a prompt's last call feeds
less and is padded to the same program, whose attention and routed experts
skip the padding."""
import statistics


def read(run):
    fam = run["family"]
    if not hasattr(fam, "call_attention_flops"):  # another family's cell
        return None
    fills = fam.chunked_prefill_spans(run)
    if not fills:
        return None
    chunk = run["traffic"].get("engine", {}).get("prefill_chunk")
    took = [sp["program"][1] for sp in fills
            if sp["feed"] == chunk and sp.get("rows") == 1 and sp["program"]]
    return statistics.median(took) / 1e6 if took else None
