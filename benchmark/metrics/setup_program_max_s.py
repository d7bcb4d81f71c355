"""The costliest row of the set-up account: the largest ``trace_s + lower_s +
backend_s`` of one span before the window opened. In a serving cell the
widest prefill or decode program; in the four-chip cell the larger of
``step_lower`` and ``step_compile``, which the engine times apart. What one
program for all batch widths, or scanned layers, would have to shorten. None
where the program keeps no account or nothing compiled."""
from pathlib import Path

from benchmark.manifest import _load

_account = _load(Path(__file__).with_name("setup_programs.py"),
                 "benchmark_metric_setup_programs")


def read(run):
    found = _account.rows(run)
    if not found:
        return None
    return float(max(r["trace_s"] + r["lower_s"] + r["backend_s"] for r in found))
