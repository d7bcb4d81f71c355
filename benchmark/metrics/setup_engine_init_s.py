"""Seconds inside ``serving.Engine.__init__`` (the ``engine_init`` rows of the
program's set-up account, their whole duration: the cache pools' allocation,
``pool_alloc``, and where they run ``pack_params`` / ``quantize_params``).
None where the program keeps no account or built no engine."""
from pathlib import Path

from benchmark.manifest import _load

_account = _load(Path(__file__).with_name("setup_programs.py"),
                 "benchmark_metric_setup_programs")


def read(run):
    return _account.seconds_of(run, "engine_init")
