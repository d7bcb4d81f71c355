"""Imbalance of the routing as a decode step sees it: the tokens of the step's
busiest expert (``expert_tokens_max``, over every expert of every expert layer)
over the tokens of its mean expert (``expert_assignments`` over expert layers x
experts), averaged over the ``decode_step`` spans inside the window. 1.0 is a
step whose rows spread evenly; a few dozen rows over 64 experts read several
times that. The table by layer and expert over an engine's whole life is
``Engine.stats()["expert_tokens"]``; a reader is handed no engine, so the
window's share of it comes from the spans."""


def read(run):
    layers = getattr(run["family"], "expert_layers", None)
    experts = run["config"].get("n_routed_experts")
    if run["spans"] is None or layers is None or not experts:
        return None
    pairs = layers(run["config"]) * experts
    rows = [r[4]["expert_tokens_max"] * pairs / r[4]["expert_assignments"]
            for r in run["spans"].named("decode_step", *run["span_window_ns"])
            if r[4].get("expert_assignments")]
    return sum(rows) / len(rows) if rows else None
