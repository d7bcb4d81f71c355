"""Seconds the program spent tracing its jitted functions to jaxprs and
lowering those to MLIR, as the program itself counts them
(``compile_trace_ns`` + ``compile_lower_ns`` of ``paddle_tpu.profiler``:
``jax.monitoring``'s stages, charged to the program span they fired under,
nested traces once) at the end of the run. With ``compiles_in_window.*`` at
0 all of it is set-up: the part of ``setup_s`` that a warm compilation cache
does not save. None where the program has no such counters."""


def read(run):
    from paddle_tpu import profiler

    c = profiler.counters()
    if "compile_trace_ns" not in c and "compile_lower_ns" not in c:
        return None
    return (c.get("compile_trace_ns", 0) + c.get("compile_lower_ns", 0)) / 1e9
