"""Seconds from the first to the last line of ``paddle_tpu/__init__.py``
(counter ``setup_import_ns``): the package's own modules and, when the
package is the first to import it, ``import jax``. The harness imports the
package right after the device check, so jax is loaded by then and the
backend's start is the harness's. None where the program has no such
counter."""


def read(run):
    from paddle_tpu import profiler

    ns = profiler.counters().get("setup_import_ns")
    return None if ns is None else ns / 1e9
