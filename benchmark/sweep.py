"""Find the highest rate a serving cell's engine sustains: ONE process, one
warm engine, the cell's mix offered at each of ``--rates`` in turn.

    python -m benchmark.sweep --workload serve-xl-chat-sat --rates 3,4,5,6,7 \\
        --seconds 20 --seed 7

A rate is sustained when the queue does not grow over its window: the tool
prints, for each rate, live rows and queue depth over the window's thirds,
tokens completed per second and the gap and TTFT percentiles. The knee goes
into the traffic file as a number (a cell below it takes about 0.8 x it, the
saturated cell about 1.25 x); no run searches for it."""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import generator as G, serve_job
from .manifest import Manifest
from .run import Ctx, _cache_dir, _device


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    manifest = Manifest(args.manifest)
    ctx = Ctx(manifest, args)
    print("device:", json.dumps(_device(ctx)), flush=True)
    _cache_dir()
    rates = [float(r) for r in args.rates.split(",")]
    vocab = ctx.config["vocab_size"]
    widest = G.build_schedule({**ctx.traffic, "rate_per_s": max(rates)},
                              ctx.seconds, ctx.seed, vocab)
    model, eng = serve_job.setup(ctx, widest)
    try:
        for k, rate in enumerate(rates):
            sched = G.build_schedule({**ctx.traffic, "rate_per_s": rate},
                                     ctx.seconds, ctx.seed + k, vocab)
            loop, t_open, t_close, _, _, samples = serve_job.drive(
                ctx, eng, sched, sample_every=0.5)
            served = loop.drain(float(ctx.traffic["drain_s"]))
            st = G.window_stats(served, sched, t_open)
            third = max(len(samples) // 3, 1)
            rows = [float(np.mean([s[1] for s in samples[i:i + third]]))
                    for i in (0, third, 2 * third)]
            queue = [float(np.mean([s[2] for s in samples[i:i + third]]))
                     for i in (0, third, 2 * third)]
            g, f = st["gaps"] * 1e3, st["ttft"] * 1e3
            print(f"rate {rate:g}/s: {st['attempted']} due, {st['failed']} failed, "
                  f"tokens/s {st['tokens_in_window'] / sched.seconds:.1f}, rows by third "
                  f"{[round(x, 1) for x in rows]} (max {max(s[1] for s in samples)}), "
                  f"queue by third {[round(x, 1) for x in queue]}, "
                  f"gap p50/p95 {np.percentile(g, 50):.2f}/{np.percentile(g, 95):.2f} ms, "
                  f"ttft p50/p90 {np.percentile(f, 50):.1f}/{np.percentile(f, 90):.1f} ms, "
                  f"late p99 {np.percentile(st['late'], 99) * 1e3:.2f} ms", flush=True)
    finally:
        eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
