"""The repo's yardstick: cells, traffic, metrics, peaks, the plain reference
and the trace reduction, all found by name from ``BENCHMARK.json``.

Nothing here is imported by ``paddle_tpu``; later PRs add files and entries
and edit none that exist (see ``README.md``).
"""
