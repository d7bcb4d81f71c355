"""Lead / whole periods / tail: how the decoders whose layers repeat a short
pattern behind a few leading dense layers (``models/lfm2_moe.py``,
``models/afmoe.py``) hold and run them. The dense layers and a last, partial
period are unrolled with leaves of their own; the whole periods are held
STACKED over their repetitions and run under one ``lax.scan``, so a program
holds one period whatever the depth. An arch brings its layer function and
its leaves; nothing here asks which arch calls.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax


class PeriodicLayers:
    """What a config with ``layer_types``, ``num_hidden_layers`` and
    ``num_dense_layers`` says of its pattern."""

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern that the layers behind the dense ones repeat
        (the last repetition may be cut short)."""
        body = self.layer_types[self.num_dense_layers:]
        for p in range(1, len(body) + 1):
            if all(body[i] == body[i % p] for i in range(len(body))):
                return body[:p]
        return ()

    @property
    def periods(self) -> int:
        """Whole repetitions of ``period``: what the programs scan over."""
        p = len(self.period)
        return (self.num_hidden_layers - self.num_dense_layers) // p if p else 0

    @property
    def tail_start(self) -> int:
        """The first layer behind the last whole period."""
        return self.num_dense_layers + self.periods * len(self.period)

    def is_expert_layer(self, i: int) -> bool:
        return i >= self.num_dense_layers


def scan_stack(cfg, params, x, pools, layer):
    """Every layer over ``x``, lead and tail unrolled, the whole periods
    scanned. ``layer(kind, w, x, read) -> (x, counts (experts,) or None)`` is
    the arch's one layer of ``kind`` with weights ``w``. ``read(fn)`` hands it
    a program's read of a cache as ``call(*a) -> out``: ``fn(pools, i, *a) ->
    (pools, out)`` is told which layer OF ITS KIND it serves (``i``, a traced
    scalar inside the scan) and may write what the layer caches into
    ``pools``, which the scan carries. Returns ``(x, pools, counts (expert
    layers, experts) or None)``."""
    kinds, period, P = cfg.layer_types, cfg.period, cfg.periods
    seen = dict.fromkeys(kinds, 0)   # layers of each kind so far
    counts = []

    def one(kind, w, x, pools, i):
        box = {"pools": pools}

        def read(fn):
            def call(*a):
                box["pools"], out = fn(box["pools"], i, *a)
                return out
            return call

        x, c = layer(kind, w, x, read)
        return x, box["pools"], c

    def unrolled(ws, first, x, pools):
        for l, w in enumerate(ws, first):
            x, pools, c = one(kinds[l], w, x, pools, seen[kinds[l]])
            seen[kinds[l]] += 1
            if c is not None:
                counts.append(c[None])
        return x, pools

    x, pools = unrolled(params["lead"], 0, x, pools)
    if P:
        def turn(carry, xs):
            x, pools = carry
            ws, p = xs
            rank, cs = dict(seen), []
            for j, kind in enumerate(period):
                # the experts' stacks whole, the turn as a scalar: the grouped
                # kernel reads its layer's experts where they lie
                w = {**ws[j], **params["body_experts"][j], "experts_layer": p}
                x, pools, c = one(kind, w, x, pools, rank[kind] + p * period.count(kind))
                rank[kind] += 1
                cs.append(c)
            return (x, pools), jnp.stack(cs)

        (x, pools), cs = lax.scan(
            turn, (x, pools), (params["body"], jnp.arange(P, dtype=jnp.int32)))
        for kind in seen:
            seen[kind] += P * period.count(kind)
        counts.append(cs.reshape((-1, cs.shape[-1])))
    x, pools = unrolled(params["tail"], cfg.tail_start, x, pools)
    return x, pools, jnp.concatenate(counts) if counts else None


def layer_trees(cfg, leaf_kinds, short, experts, sd):
    """``{"lead", "tail", "body", "body_experts"}`` of :func:`scan_stack` from
    ``{state_dict key: array}`` (arrays or their shapes). ``leaf_kinds``
    names ``model.layers.<i>.*`` for the unrolled layers and
    ``model.body.<j>.*`` for position ``j`` of the period, ONE leaf over its
    repetitions; ``short(name)`` is the layer function's name for a leaf.
    ``lead`` / ``tail`` are a dict a layer, ``body`` a dict a position of the
    period, which the scan slices, and beside it ``body_experts``, the leaves
    named in ``experts`` of the same positions, which it does not."""
    layers, body = {}, {}
    for key, _, _ in leaf_kinds:
        parts = key.split(".")
        if parts[1] in ("layers", "body"):
            into = layers if parts[1] == "layers" else body
            name = ".".join(parts[3:])
            name = name[:-len(".weight")] if name.endswith(".weight") else name
            into.setdefault(int(parts[2]), {})[short(name)] = sd[key]
    split = lambda w, mine: {k: v for k, v in w.items() if (k in experts) == mine}
    return {"lead": [layers[i] for i in sorted(layers) if i < cfg.num_dense_layers],
            "tail": [layers[i] for i in sorted(layers) if i >= cfg.tail_start],
            "body": [split(body[j], False) for j in sorted(body)],
            "body_experts": [split(body[j], True) for j in sorted(body)]}
