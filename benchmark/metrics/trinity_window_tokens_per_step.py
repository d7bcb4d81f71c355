"""Mean over the window's decode steps of the tokens inside the live rows'
windows (the ``window_tokens`` attribute of the engine's ``decode_step`` host
spans: the sum over the live rows of ``min(context, sliding_window)``): what
each of the ``afmoe`` family's window layers reads of its rings a step,
whatever the contexts have grown to. ``window_tokens_per_step``'s reduction
under a name of its own: the accepted entry's ``workloads`` is held to the
hybrid's cell alone by ``tests/benchmark/test_benchmark_phi4flash.py``."""


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):
        return None
    return fam.span_mean(run, "window_tokens")
