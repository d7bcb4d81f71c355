"""Seconds of host time inside the initializer calls of
``Layer.create_parameter`` (counter ``param_init_ns``; ``param_init_bytes``
and ``param_init_leaves`` beside it): what the model's constructors draw.
The harness builds the model through the program's public classes and then
lays the seeded weights over it, so what is counted here was drawn to be
thrown away. 0 where a model is built holding given arrays and draws
nothing; None where the program has no such counters (``setup_import_ns``
dates from the same change and is always there)."""


def read(run):
    from paddle_tpu import profiler

    c = profiler.counters()
    if "setup_import_ns" not in c:
        return None
    return c.get("param_init_ns", 0) / 1e9
