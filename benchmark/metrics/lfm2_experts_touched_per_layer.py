"""Mean number of distinct routed experts that the live rows of a decode step
hit in one expert layer of the ``lfm2`` family, inside the window:
``experts_touched_per_layer``'s reduction, which this reader CALLS (the
spans' ``experts_touched`` over the family's eight expert layers); a metric
of its own name because the accepted one lists the other routed cell. With 4
of 64 experts a token and R live rows about 64 (1 - (15/16)^R)."""
from pathlib import Path

from benchmark.manifest import _load

_shared = _load(Path(__file__).with_name("experts_touched_per_layer.py"),
                "benchmark_metric_experts_touched_per_layer").read


def read(run):
    if not hasattr(run["family"], "paged_read_bytes"):
        return None
    return _shared(run)
