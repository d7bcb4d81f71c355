"""paddle.cost_model (reference python/paddle/cost_model/cost_model.py):
per-op and whole-program cost estimation.

The reference ships a measured static table (static_op_benchmark.json) plus
a profiler-measured mode. TPU-first: costs come from XLA itself —
``jit(...).lower().compile().cost_analysis()`` gives flops/bytes per
compiled program, and per-op timings are measured on the live backend, so
the numbers track the REAL compiler and chip instead of a frozen table.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

__all__ = ["CostModel", "executable_memory", "device_peaks"]

# Peaks by ``jax.Device.device_kind``, each with where it comes from. A device
# that is not listed is an error, not a default: an estimate priced against
# another chip's peaks looks exactly like a real one.
_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,      # bf16 FLOP/s
        "hbm_bw": 819e9,      # bytes/s
        "ici_bw": 200e9,      # bytes/s (1,600 Gbit/s chip-to-chip)
        "overhead_ms": 2e-3,  # per grid program / collective launch; nominal
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
    "cpu": {
        "flops": 1e11, "hbm_bw": 5e10, "ici_bw": 5e9, "overhead_ms": 2e-2,
        "source": "nominal host figures: the CPU tier only ORDERS candidates "
                  "with them, no time is reported from them",
    },
}


def device_peaks() -> Dict[str, object]:
    """The peaks row of the default device. Raises for a ``device_kind`` the
    table does not hold."""
    import jax

    kind = jax.devices()[0].device_kind
    try:
        return _PEAKS[kind]
    except KeyError:
        raise RuntimeError(
            f"cost_model: no peaks recorded for device_kind {kind!r} (known: "
            f"{sorted(_PEAKS)}); add a row with its source to "
            "cost_model._PEAKS") from None


def executable_memory(compiled) -> Optional[Dict[str, int]]:
    """Per-executable memory footprint from XLA's ``memory_analysis()``
    (the memory-side sibling of the ``cost_analysis()`` wrap above):
    argument/output/temp/alias bytes plus the derived ``peak_bytes``
    (argument + output + temp − alias — the aliased share reuses donated
    input buffers, so it must not count twice). None when the backend
    doesn't expose the analysis. fault/memory.py keys these dicts like the
    lazy executable cache and feeds the preflight HBM admission check."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def g(name):
        return int(getattr(ma, name, 0) or 0)

    arg = g("argument_size_in_bytes")
    out = g("output_size_in_bytes")
    tmp = g("temp_size_in_bytes")
    alias = g("alias_size_in_bytes")
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        "peak_bytes": max(arg + out + tmp - alias, 0),
    }


class CostModel:
    def __init__(self):
        self._static_cache: Dict[tuple, dict] = {}

    # -- whole-program analysis (reference profile_measure) ------------------
    def profile_measure(self, program=None, startup_program=None,
                        device="tpu", fetch_cost_list=("time",), fn=None,
                        args=None, iters=10):
        """Measure a compiled program. Either pass a ``static.Program``-backed
        callable via ``fn``/``args`` or a traced Program with a runner.
        Returns {"time": ms_per_iter, "flops": ..., "bytes": ...}."""
        import jax

        if fn is None and program is not None and hasattr(program, "_fn"):
            fn, args = program._fn, program._example_args
        if fn is None:
            raise ValueError("pass fn=<jittable callable>, args=<inputs>")
        from ..core import lazy as lazy_mod

        jitted = jax.jit(fn)
        out = jitted(*args)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
        # monotonic clock (wall time jumps under NTP/VM migration — the
        # analysis monotonic-deadline class) and an ATTRIBUTED device wait:
        # the readback rides lazy.timed_block so it lands as a `block` span
        # (+ lazy_block_ns) instead of hiding inside a host fetch, with an
        # unconditional barrier behind it (timed_block is a no-op for
        # already-ready arrays and when FLAGS_lazy_async is off).
        t0 = time.monotonic()
        for _ in range(iters):
            out = jitted(*args)
        leaves = jax.tree_util.tree_leaves(out)
        lazy_mod.timed_block(leaves, "cost_model.profile_measure")
        jax.block_until_ready(leaves)
        dt = (time.monotonic() - t0) / iters
        cost = {}
        try:
            analysis = jitted.lower(*args).compile().cost_analysis()
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0]
            cost["flops"] = float(analysis.get("flops", 0.0))
            cost["bytes"] = float(analysis.get("bytes accessed", 0.0))
        except Exception:
            pass
        cost["time"] = dt * 1e3  # ms, reference units
        return cost

    # -- kernel-config cost estimates (ops/kernels autotune ordering) --------
    def kernel_estimate(self, name, key, config):
        """Analytic cost estimate (ms-scale score) for one tunable-kernel
        config at one shape bucket — the ordering heuristic that decides
        which candidates the measured-timing search visits FIRST under its
        budget (``ops/kernels/autotune.candidates``). The model is the
        standard roofline sum the XLA ``cost_analysis`` numbers decompose
        into — flops/peak + bytes/bandwidth — plus the two terms XLA's
        per-program numbers miss but block-size tuning lives on: a
        per-grid-program launch overhead and the padding waste when a block
        doesn't tile its axis. Relative order is all that matters; an
        unknown kernel scores 0.0 (neutral — stub kernels keep declared
        order)."""
        peaks = device_peaks()
        peak_flops, peak_bw = peaks["flops"], peaks["hbm_bw"]
        overhead_ms = peaks["overhead_ms"]

        def pad(n, b):
            b = max(int(b), 1)
            return (-(-int(n) // b)) * b

        if name == "flash_attention":
            bh, h, t, t_kv, d, dtype, causal = key
            bq, bk = int(config["block_q"]), int(config["block_k"])
            tq, tk = pad(t, bq), pad(t_kv, bk)
            flops = 4.0 * bh * tq * tk * d * (0.5 if causal else 1.0)
            bytes_ = 2.0 * bh * (tq + 2 * tk) * d * 4
            progs = bh * (tq // min(bq, tq))
            # VMEM pressure: both tiles plus accumulators must fit
            vmem = (bq * d + 2 * bk * d + bq * bk) * 4
            spill = 4.0 if vmem > 8 * 1024 * 1024 else 1.0
        elif name == "fused_ce":
            n, d, v, dtype = key
            br = int(config["block_rows"])
            nr = pad(n, br)
            # fwd + remat-bwd: 3 block-logits gemms over the padded rows
            flops = 3.0 * 2.0 * nr * d * v
            bytes_ = (nr * d + 2 * v * d + br * v) * 4.0
            progs = nr // br
            vmem = br * v * 4
            spill = 4.0 if vmem > 16 * 1024 * 1024 else 1.0
        elif name == "paged_attention":
            b, mb, bs, kv, rep, d, dtype = key
            c = int(config["blocks_per_chunk"])
            # a row reads its live blocks, about half the table on average,
            # rounded up to whole chunks of MXU work
            t_live = pad(max(mb // 2, 1), c) * bs
            flops = 4.0 * b * kv * rep * t_live * kv * d
            bytes_ = 2.0 * b * t_live * kv * d * 2.0 + b * kv * rep * d * 4
            progs = b  # one row a grid step
            # two double-buffered chunks of K and of V
            vmem = 4 * c * bs * kv * d * 4
            spill = 4.0 if vmem > 8 * 1024 * 1024 else 1.0
        elif name == "int8_matmul":
            m, k_dim, n, transpose_w, dtype = key
            bn = int(config["block_n"])
            nn = pad(n, min(bn, n))
            flops = 2.0 * m * k_dim * nn
            bytes_ = k_dim * nn * 1.0 + m * k_dim * 4 + m * nn * 4
            progs = nn // min(bn, nn)
            vmem = (min(bn, nn) * k_dim + m * k_dim) * 4
            spill = 4.0 if vmem > 8 * 1024 * 1024 else 1.0
        elif name == "tp_collective":
            # per-decode-step tensor-parallel all_gather term (serving
            # PR 19): key = (wire_bytes, tp). A ring gather moves
            # (tp-1)/tp of the payload per hop over the slowest link;
            # count the whole payload once (upper bound, ordering-safe)
            # plus one launch overhead per collective boundary — the
            # engine uses this as the shed-ETA floor while its measured
            # decode EMA is still cold.
            wire_bytes, tp = key
            boundaries = max(int(tp) - 1, 1)
            return (float(wire_bytes) / peaks["ici_bw"]) * 1e3 \
                + boundaries * overhead_ms
        else:
            return 0.0
        ms = (flops / peak_flops + bytes_ / peak_bw) * 1e3 * spill
        return ms + progs * overhead_ms

    # -- per-op costs (reference static_cost_data/get_static_op_time) --------
    def static_cost_data(self):
        """The measured per-op table built so far (op → cost dict)."""
        return {f"{k[0]}/{k[1]}/{k[2]}/{k[3]}": v
                for k, v in self._static_cache.items()}

    def get_static_op_time(self, op_name, forward=True, dtype="float32",
                           shape=(1024, 1024)):
        """Measure (and cache) one op's time on the live backend — the role
        of the reference's frozen static_op_benchmark.json, but tracking the
        real compiler/chip. Returns {"op_time": ms, "flops": ...}."""
        import jax
        import jax.numpy as jnp

        from ..ops.registry import all_ops

        key = (op_name, bool(forward), str(dtype), tuple(shape))
        if key in self._static_cache:
            return self._static_cache[key]
        ops = all_ops()
        op = ops.get(op_name) or ops.get(f"functional.{op_name}")
        if op is None:
            raise KeyError(f"unknown op {op_name!r} (registry has {len(ops)})")
        rng = np.random.RandomState(0)
        x = rng.rand(*shape).astype(dtype) + 0.5

        import paddle_tpu as paddle

        xt = paddle.to_tensor(x)
        if forward:
            def run():
                return op(xt)
        else:
            xt.stop_gradient = False

            def run():
                out = op(xt)
                out = out[0] if isinstance(out, (tuple, list)) else out
                out.sum().backward()
                g = xt.grad
                xt.clear_grad()
                return g
        out = run()
        t0 = time.monotonic()
        for _ in range(5):
            out = run()
        o = out[0] if isinstance(out, (tuple, list)) else out
        # Tensor.numpy() routes through the lazy.timed_block funnel, so the
        # sync that closes the timed region is already an attributed block
        float(np.asarray(o.numpy()).ravel()[0])
        cost = {"op_time": (time.monotonic() - t0) / 5 * 1e3, "dtype": str(dtype)}
        self._static_cache[key] = cost
        return cost
