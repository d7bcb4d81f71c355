"""Seconds of ``setup_s`` spent in XLA backend compilation or in loading a
program from the persistent cache: the sum of ``jax.monitoring``'s
backend-compile durations that ended before the window opened."""


def read(run):
    return run["compiles"].seconds_before(run["window"][0])
