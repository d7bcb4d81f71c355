"""Programs that were compiled and WRITTEN to the persistent compilation cache
before the window opened: the sum of ``cache_misses`` over the rows of the
program's set-up account (``/jax/compilation_cache/cache_misses``, charged to
the span it fired under). A warm run reads 0; a checkout at a new path, a new
jax or a new shape does not, and then ``setup_s`` is a cold start's. None where
the program keeps no account."""
from pathlib import Path

from benchmark.manifest import _load

_account = _load(Path(__file__).with_name("setup_programs.py"),
                 "benchmark_metric_setup_programs")


def read(run):
    found = _account.rows(run)
    return None if found is None else float(sum(r["cache_misses"] for r in found))
