"""Mean over the window's decode steps of the context the full layers hold for
the step's live rows (the ``paged_kv_tokens`` attribute of the engine's
``decode_step`` host spans: the sum over the live rows of their whole
context): what each of the ``afmoe`` family's full layers reads by block table
a step. Beside ``trinity_window_tokens_per_step`` it says how far the contexts
have passed the window. ``paged_kv_tokens_per_step``'s reduction under a name
of its own: the accepted entry's ``workloads`` is held to the convolution
arch's cell alone by ``tests/benchmark/test_benchmark_lfm2.py``."""


def read(run):
    fam = run["family"]
    if not hasattr(fam, "band_flops"):
        return None
    return fam.span_mean(run, "paged_kv_tokens")
