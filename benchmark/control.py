"""The control of the correctness check, on the chip at the cell's own size:
the family's plain reference put in the program's place and computed in float8 e4m3, a
precision below the bfloat16 the configurations state. It has to
come out as NOT correct; the limits in ``cells/<cell>.json`` are set between
the largest number sound runs give and the smallest the control gives
(PERF.md section 2).

    python -m benchmark.control --workload <cell> --seeds 3 [--seconds 10]

Training cells need no window: the float8 reference follows the job's first
steps beside the float32 one. Serving cells run a short window at the
cell's own load per seed and read, at each position of the sampled requests'
prompts and served tokens, the reference gap of the token the control puts first
(beside the program's own gap on the same tokens).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from . import check, generator as G, serve_job, train_job, weights as W
from .manifest import Manifest
from .run import Ctx, _cache_dir, _device


def train_control(ctx, seed):
    import jax

    cfg, job, specs = ctx.config, ctx.traffic, ctx.family.leaf_specs(ctx.config)
    names = [s[0] for s in specs]
    first = lambda leaf: W.make_leaf(cfg, seed, specs, names.index(leaf))
    packs = {}
    for mode in ("f32", ctx.args.mode):
        ref = ctx.family.TrainReference(cfg, W.make_weights(cfg, seed, specs), job["optimizer"],
                                        mode=mode, devices=jax.devices()[:ctx.chips])
        for k in range(job["check_steps"]):
            ref.step(train_job.feed_ids(job, cfg["vocab_size"], seed, k))
        packs[mode] = {"loss": ref.losses, "grad_norm": ref.grad_norms,
                       "change_norm": ref.change_norms(first)}
        del ref
        gc.collect()
    return check.train_numbers(packs[ctx.args.mode], packs["f32"])


def serve_control(ctx, seed):
    import numpy as np

    cfg, family = ctx.config, ctx.family
    ctx.seed = seed
    sched = G.build_schedule(ctx.traffic, ctx.seconds, seed, cfg["vocab_size"])
    model, eng = serve_job.setup(ctx, sched)
    try:
        loop, t_open, *_ = serve_job.drive(ctx, eng, sched)
        served = loop.drain(float(ctx.traffic["drain_s"]))
    finally:
        eng.close()
    picked = serve_job.sample_finished(served, sched, seed, int(ctx.cell["check_requests"]))
    del eng, model, loop
    gc.collect()
    weights = W.make_weights(cfg, seed, family.leaf_specs(cfg))
    sound, ctl, n = 0.0, 0.0, 0
    for r in picked:
        prompt = sched.prompts[r.index]
        sound = max(sound, float(serve_job.served_gap(family, cfg, weights, prompt, r.tokens).max()))
        g = serve_job.served_gap(family, cfg, weights, prompt, r.tokens, mode=ctx.args.mode)
        ctl, n = max(ctl, float(g.max())), n + len(g)
    return {"served_logit_gap": (ctl, f"the control's first token, {n} positions"),
            "program_served_logit_gap": (sound, f"the program's tokens, {n} positions")}


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace, args.seed, args.mode = 0, args.first_seed, "fp8"
    manifest = Manifest(args.manifest)
    ctx = Ctx(manifest, args)
    print("device:", json.dumps(_device(ctx)), flush=True)
    _cache_dir()
    kind = ctx.traffic["kind"]
    limits = ctx.cell["limits"]
    for k in range(args.seeds):
        seed = args.first_seed + 7 * k
        numbers = train_control(ctx, seed) if kind == "train" else serve_control(ctx, seed)
        failed = [n for n, (v, _) in numbers.items() if n in limits and not v <= limits[n]]
        for n, (v, where) in sorted(numbers.items()):
            print(f"control: seed {seed} {n} = {v:.6g} (limit "
                  f"{limits.get(n, float('nan')):.6g}, at {where})", flush=True)
        print(f"control: seed {seed} {'NOT correct, as it must be: ' + ', '.join(failed) if failed else 'PASSED: the limits do not hold it'}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
