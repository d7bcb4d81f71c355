"""A training cell: one compiled step with its state, driven from the seed
through its first steps (which the plain reference follows afterwards) and
then, the same object, through the measured window."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import check, weights as W
from .reference.common import diff_norm

ENGINES = ("compile_train_step", "hybrid")
# steps the host may run ahead of the device in the window: with two, two of
# twelve runs lost 1% to one stall of about 0.4 s (PR 23, call 7)
IN_FLIGHT = 8


def feed_ids(job: dict, vocab: int, seed: int, k: int) -> np.ndarray:
    """Batch ``k`` of the seeded stream: (batch, seq+1) token ids, every row
    different."""
    rng = np.random.default_rng([int(seed), 0x7EED, k])
    return rng.integers(0, vocab, (job["batch"], job["seq"] + 1), dtype=np.int64)


def _build_step(job, model):
    import paddle_tpu as paddle

    o = job["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    loss_fn = lambda m, ids, labels: m.loss(ids, labels)
    if job["engine"] == "compile_train_step":
        return opt, paddle.jit.compile_train_step(model, loss_fn, opt)
    if job["engine"] == "hybrid":
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.engine import HybridParallelEngine

        mesh = fleet.get_hybrid_communicate_group().mesh
        return opt, HybridParallelEngine(model, opt, loss_fn, mesh=mesh).train_step
    raise ValueError(f"unknown train engine {job['engine']!r}; know {ENGINES}")


def init_mesh(job):
    """The hybrid engine's mesh has to exist before the model is built."""
    if job["engine"] != "hybrid":
        return
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": job["dp"], "mp_degree": job["mp"],
                               "pp_degree": 1, "sharding_degree": 1, "sp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)


def _norms(arrays):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                            for x in xs])
    return [float(v) for v in f(list(arrays))]


def _first_gradient_norms(opt, params, beta1):
    """The first gradient as the optimizer got it, from its state after one
    step: moment1 = (1 - beta1) * g."""
    state = opt.state_dict()
    leaves = list(params)
    moments = [state[params[k].name + ".moment1"]._data for k in leaves]
    return {k: v / (1.0 - beta1) for k, v in zip(leaves, _norms(moments))}


def _change_norms(cfg, seed, specs, params):
    import jax

    out = {}
    for i, (leaf, _, _) in enumerate(specs):
        now = params[leaf]._data
        first = jax.device_put(W.make_leaf(cfg, seed, specs, i), now.sharding)
        out[leaf] = float(diff_norm(now, first))
    return out


def run(ctx) -> dict:
    import jax
    import paddle_tpu as paddle

    cfg, job, seed, family = ctx.config, ctx.traffic, ctx.seed, ctx.family
    specs = family.leaf_specs(cfg)
    tokens_per_step = job["batch"] * job["seq"]
    init_mesh(job)
    t = time.monotonic()
    weights = W.make_weights(cfg, seed, specs)
    jax.block_until_ready(weights)
    ctx.note("setup_weights_s", time.monotonic() - t)
    t = time.monotonic()
    model, params = family.build(cfg, weights)
    del weights
    opt, step = _build_step(job, model)
    ctx.note("setup_model_s", time.monotonic() - t)

    n_check = job["check_steps"]
    ids = [feed_ids(job, cfg["vocab_size"], seed, k) for k in range(job["feed_batches"])]
    feed = [(paddle.to_tensor(a[:, :-1]), paddle.to_tensor(a[:, 1:])) for a in ids]

    # the first steps, through the window's own call and feed
    t = time.monotonic()
    losses = [step(*feed[0])]
    jax.block_until_ready(losses[0]._data)
    ctx.note("setup_first_step_s", time.monotonic() - t)
    program = {"grad_norm": _first_gradient_norms(opt, params, job["optimizer"]["beta1"])}
    losses += [step(*feed[k % len(feed)]) for k in range(1, n_check)]
    program["loss"] = [float(l._data) for l in losses]
    program["change_norm"] = _change_norms(cfg, seed, specs, params)
    for k in range(n_check, n_check + 2):  # settle the dispatch queue
        last = step(*feed[k % len(feed)])
    jax.block_until_ready(last._data)

    # the window: steps back to back, dispatched up to IN_FLIGHT ahead so that
    # a host stall of a second or two (a one-chip machine shares its host's
    # cores) starves nothing; one host read of the loss when it closes
    k, pending = n_check + 2, []
    t_open = time.monotonic()
    spans = ctx.open_window(t_open)
    while time.monotonic() - t_open < ctx.seconds:
        pending.append(step(*feed[k % len(feed)])._data)
        k += 1
        if len(pending) > IN_FLIGHT:
            pending.pop(0).block_until_ready()
        ctx.tick()  # a traced run starts its profiler from this thread
    ctx.end_work()
    jax.block_until_ready(pending)
    t_close = time.monotonic()
    steps = k - (n_check + 2)
    final_loss = float(pending[-1])
    ctx.close_window(t_open, t_close)
    peak = ctx.memory_peak()

    # free the program's state, then let the plain reference follow
    del model, opt, step, feed, pending, last, losses, params
    gc.collect()
    t = time.monotonic()
    ref = family.TrainReference(cfg, W.make_weights(cfg, seed, specs), job["optimizer"],
                                devices=jax.devices()[:ctx.chips])
    for a in ids[:n_check]:
        ref.step(a)
    names = [s[0] for s in specs]
    reference = {"loss": ref.losses, "grad_norm": ref.grad_norms,
                 "change_norm": ref.change_norms(
                     lambda leaf: W.make_leaf(cfg, seed, specs, names.index(leaf)))}
    ctx.note("reference_s", time.monotonic() - t)

    numbers = check.train_numbers(program, reference)
    numbers["final_loss_finite"] = (0.0 if np.isfinite(final_loss) else 1.0,
                                    f"loss {final_loss:.4f} after {k} steps")
    elapsed = t_close - t_open
    return {
        "numbers": numbers, "attempted": steps, "failed": 0,
        "t_open": t_open, "t_close": t_close, "memory_peak_bytes": peak,
        "counts": {"steps": steps, "tokens": steps * tokens_per_step},
        "end_to_end": {"tokens_per_s_chip": steps * tokens_per_step / elapsed / ctx.chips},
        "facts": {"tokens_per_step": tokens_per_step, "seq": job["seq"],
                  "batch": job["batch"], "steps": steps, "spans": spans},
    }
