"""Seconds the hybrid engine spent writing out the compiled dp step's
scheduled text and reading it for its collectives' counts (the ``step_text``
rows of the program's set-up account: ``exe.as_text()`` plus
``engine._step_counts``; the row carries ``text_bytes`` and the seven
counts). Tracing is code: this is what that piece of it costs a start. None
where the program keeps no account or compiled no such step."""
from pathlib import Path

from benchmark.manifest import _load

_account = _load(Path(__file__).with_name("setup_programs.py"),
                 "benchmark_metric_setup_programs")


def read(run):
    return _account.seconds_of(run, "step_text")
