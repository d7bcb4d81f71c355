"""Mean number of distinct routed experts that the live rows of a decode step
hit in one expert layer, inside the window: the ``experts_touched`` attribute
of the engine's ``decode_step`` host spans (summed over the expert layers
there) over the family's count of expert layers. These are the experts whose
weights the step reads; rows that pad the bucket touch none."""


def read(run):
    layers = getattr(run["family"], "expert_layers", None)
    if run["spans"] is None or layers is None:
        return None
    rows = [r[4]["experts_touched"]
            for r in run["spans"].named("decode_step", *run["span_window_ns"])
            if "experts_touched" in r[4]]
    return sum(rows) / len(rows) / layers(run["config"]) if rows else None
