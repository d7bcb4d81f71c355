"""Mean over the window's decode steps of the live cached positions the step
reads a layer (the ``context_tokens`` attribute of the engine's
``decode_step`` host spans: the sum over the step's live rows of their whole
context), for the ``kimivl`` family's cell: at 1,152 B a position a layer it
is the latent cache's part of ``kimivl_decode_hbm_mfu_pct``, and what grows
with the prompts' length where the experts' part does not."""


def read(run):
    fam = run["family"]
    if not hasattr(fam, "call_attention_flops"):  # another family's cell
        return None
    return fam.span_mean(run, "context_tokens")
