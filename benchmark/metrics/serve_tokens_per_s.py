"""Output tokens that reached the clients inside the window, over its length:
what the saturated engine completes. Every host stall of the engine's
synchronous loop counts against it, so on a one-chip machine that shares its
host it spread by 1.0% in one set of six runs and by 7.3% in the other (same
seeds, another machine; my chip runs, PR 23, calls 5 and 7): it cannot carry
a bound, and stands beside ``token_gap_p50_ms``, which those stalls hardly
move."""


def read(run):
    return float(run["tokens_per_s"])
