"""Seconds the spans that compiled a program took beyond the compile stages
charged to them, summed over the programs of the set-up account
(``first_run_s``: the span's time less its trace, lower and backend stages and
less the kept spans inside it): each program's first run and, in a serving
cell, the rest of the scheduler step it ran in. None where the program keeps
no account."""
from pathlib import Path

from benchmark.manifest import _load

_account = _load(Path(__file__).with_name("setup_programs.py"),
                 "benchmark_metric_setup_programs")


def read(run):
    found = _account.programs(run)
    return None if found is None else float(sum(r["first_run_s"] for r in found))
