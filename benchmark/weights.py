"""Seeded weights, made by the benchmark (not by the program) in one jitted
call on the device, in the type they are trained and served in.

Which leaves a model has is its family's: ``specs`` is what
``families/<family>.py``'s ``leaf_specs(cfg)`` returns, ``[(name, shape,
kind)]`` in a fixed order with kind ``normal`` (mean 0) or ``gain`` (mean 1),
a leaf of layer ``i`` named ``h<i>.<suffix>``. The drawing, the grouping and
the keys are here and the same for every family; of the configuration they
read ``initializer_range`` and ``dtype``.

The program's model and the plain reference are both given these values, so
neither takes anything the other has made. Every leaf has a key of
its own (folded from the seed, its group and its layer), so a single leaf can
be made again later
(the parameter-change norms of the training check need the first values
without keeping a second copy of the model on the device).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _draw(key, shape, kind, std, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * std
    if kind == "gain":
        x = x + 1.0
    return x.astype(dtype)


def root_key(seed: int):
    # --seed may exceed 31 bits; the key takes 32 of them and the rest fold in
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _groups(specs):
    """Leaves of one name and shape across the layers are drawn together:
    {suffix: (first index, [indices])}; a leaf outside the layers is a group
    of its own. Sixteen draws instead of three hundred keep the program that
    makes the weights small (it is loaded from the cache in every run)."""
    groups = {}
    for i, (name, _, _) in enumerate(specs):
        suffix = name.split(".", 1)[1] if name[0] == "h" and name[1].isdigit() else name
        groups.setdefault(suffix, []).append(i)
    return groups


def _leaf_key(key, group_first, member):
    return jax.random.fold_in(jax.random.fold_in(key, group_first), member)


@partial(jax.jit, static_argnames=("specs", "std", "dtype"))
def _make_all(key, specs, std, dtype):
    out = {}
    for members in _groups(specs).values():
        _, shape, kind = specs[members[0]]
        keys = jax.vmap(lambda m: _leaf_key(key, members[0], m))(jnp.arange(len(members)))
        stack = jax.vmap(lambda k: _draw(k, shape, kind, std, dtype))(keys)
        for m, i in enumerate(members):
            out[specs[i][0]] = stack[m]
    return out


@partial(jax.jit, static_argnames=("shape", "kind", "std", "dtype"))
def _make_one(key, group_first, member, shape, kind, std, dtype):
    return _draw(_leaf_key(key, group_first, member), shape, kind, std, dtype)


def make_weights(cfg: dict, seed: int, specs) -> dict:
    """Every leaf, one jitted call, on the default device."""
    specs = tuple((n, tuple(s), k) for n, s, k in specs)
    return _make_all(root_key(seed), specs, float(cfg["initializer_range"]),
                     cfg["dtype"])


def make_leaf(cfg: dict, seed: int, specs, index: int):
    """Leaf ``index`` of ``specs`` alone: the same values as in
    ``make_weights``."""
    name, shape, kind = specs[index]
    members = next(m for m in _groups(specs).values() if index in m)
    return _make_one(root_key(seed), members[0], members.index(index), tuple(shape),
                     kind, float(cfg["initializer_range"]), cfg["dtype"])
