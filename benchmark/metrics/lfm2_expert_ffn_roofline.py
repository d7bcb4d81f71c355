"""Roofline share of the routed-expert kernel in the decode programs of the
``lfm2`` family (``moe_experts_t16`` at 2048 x 1536, its layer's experts read
out of the stack of the scanned repetitions where they lie) against HBM
bandwidth: ``expert_ffn_roofline``'s arithmetic, which this reader CALLS, with
this family's counts (``expert_layers``, ``expert_bytes``); a metric of its
own name because the accepted one lists the other routed cell, and the two
kernels' shapes differ. Only a family that counts ``paged_kv_tokens`` (this
one) is read."""
from pathlib import Path

from benchmark.manifest import _load

_shared = _load(Path(__file__).with_name("expert_ffn_roofline.py"),
                "benchmark_metric_expert_ffn_roofline").read


def read(run):
    if not hasattr(run["family"], "paged_read_bytes"):
        return None
    return _shared(run)
