"""Plain reference for the GPT family: forward, loss, gradients and AdamW in
straightforward ``jax.numpy``, float32, every matmul at ``HIGHEST``
precision. No kernels, no cache, no batching tricks, nothing imported from
the program and nothing the program has made (the weights are the
benchmark's own, ``weights.make_weights``).

Layer equations (Brown et al. 2020 = GPT-2's block, pre-LN):
    h   = x + proj(attn(split_heads(qkv(ln1(x)))))      causal, 1/sqrt(D)
    out = h + down(gelu_tanh(up(ln2(h))))
    logits = ln_f(x_L) @ wte^T                           tied head
Departures from the paper are the configuration file's ``assumed`` entries.

State (parameters, AdamW moments) is STORED in the configuration's dtype and
every computation upcasts to float32, because the configuration states
bfloat16 state; a float32 state would not be the job the cell runs.

``mode="fp8"`` is the control of the benchmark's correctness check: the same
reference with the operands of every linear layer and of the head rounded to
float8 e4m3 (``common.py``, shared by every family's reference).

The harness reaches this module through ``benchmark/families/gpt.py``
alone, which hands it ``forward_logits`` and ``TrainReference``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, HI, diff_norm, linear, operands

LAYER_LEAVES = ("ln1.g", "ln1.b", "qkv.w", "qkv.b", "proj.w", "proj.b",
                "ln2.g", "ln2.b", "up.w", "up.b", "down.w", "down.b")


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attend_row(qkv_row, heads):
    """One sequence: (T, 3, H, D) -> (T, H*D), exact causal softmax."""
    t = qkv_row.shape[0]
    q, k, v = qkv_row[:, 0], qkv_row[:, 1], qkv_row[:, 2]
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(q.shape[-1])
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(t, -1)


def layer(p, x, heads, eps, mode):
    """One decoder block. ``p`` maps LAYER_LEAVES to float32 arrays; ``x`` is
    (B, T, d). Attention runs one sequence at a time (blocks of rows), so
    the (H, T, T) scores of one row are all that is live."""
    b, t, d = x.shape
    qkv = linear(layer_norm(x, p["ln1.g"], p["ln1.b"], eps),
                 p["qkv.w"], p["qkv.b"], mode)
    qkv = qkv.reshape(b, t, 3, heads, d // heads)
    a = jax.lax.map(partial(_attend_row, heads=heads), qkv)
    x = x + linear(a, p["proj.w"], p["proj.b"], mode)
    h = linear(layer_norm(x, p["ln2.g"], p["ln2.b"], eps),
               p["up.w"], p["up.b"], mode)
    return x + linear(gelu_tanh(h), p["down.w"], p["down.b"], mode)


def embed(wte, wpe, ids):
    return wte[ids] + wpe[jnp.arange(ids.shape[-1])]


def head_logits(x, g, b, wte, eps, mode):
    h, wte = operands(layer_norm(x, g, b, eps), wte, mode)
    return jnp.matmul(h, wte.T, precision=HI)


def head_loss(x, g, b, wte, labels, eps, mode):
    """Mean next-token cross entropy over every position of the batch."""
    logits = head_logits(x, g, b, wte, eps, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def _up(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


@partial(jax.jit, static_argnames=("heads", "eps", "mode"))
def _layer_fwd(p, x, heads, eps, mode):
    return layer(_up(p), x, heads, eps, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _logits(x, g, b, wte, eps, mode):
    return head_logits(x, g.astype(F32), b.astype(F32), wte.astype(F32), eps, mode)


@jax.jit
def _embed(wte, wpe, ids):
    return embed(wte.astype(F32), wpe.astype(F32), ids)


def layer_params(weights: dict, i: int) -> dict:
    return {k: weights[f"h{i}.{k}"] for k in LAYER_LEAVES}


def forward_logits(cfg: dict, weights: dict, ids, mode: str = "f32"):
    """Logits (B, T, V), float32, layer by layer. ``weights`` may be held in
    any dtype; each layer is upcast as it is used."""
    eps, heads = float(cfg["layer_norm_epsilon"]), int(cfg["num_heads"])
    ids = jnp.asarray(ids, jnp.int32)
    x = _embed(weights["wte"], weights["wpe"], ids)
    for i in range(cfg["num_layers"]):
        x = _layer_fwd(layer_params(weights, i), x, heads, eps, mode)
    return _logits(x, weights["lnf.g"], weights["lnf.b"], weights["wte"], eps, mode)


# -- training ---------------------------------------------------------------
def _adamw(p, g, m, v, t, hp):
    """AdamW with decoupled decay on float32 views of one leaf; returns the
    new (p, m, v) rounded to the state's dtype."""
    dt = p.dtype
    p, m, v, g = p.astype(F32), m.astype(F32), v.astype(F32), g.astype(F32)
    lr, b1, b2 = hp["lr"], hp["beta1"], hp["beta2"]
    p = p * (1.0 - lr * hp["weight_decay"])
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    p = p - lr_t * m / (jnp.sqrt(v) + hp["epsilon"])
    return p.astype(dt), m.astype(dt), v.astype(dt)


def _update_tree(p, g, m, v, t, hp):
    out = {k: _adamw(p[k], g[k], m[k], v[k], t, hp) for k in p}
    norms = {k: jnp.sqrt(jnp.sum(jnp.square(g[k].astype(F32)))) for k in p}
    return ({k: o[0] for k, o in out.items()}, {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()}, norms)


@partial(jax.jit, static_argnames=("heads", "eps", "mode", "hp"),
         donate_argnums=(0, 1, 2))
def _layer_bwd_update(p, m, v, x_in, dy, t, heads, eps, mode, hp):
    _, vjp = jax.vjp(lambda pp, xx: layer(pp, xx, heads, eps, mode), _up(p), x_in)
    gp, dx = vjp(dy)
    p, m, v, norms = _update_tree(p, gp, m, v, t, dict(hp))
    return dx, p, m, v, norms


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head_bwd(x, g, b, wte, labels, eps, mode):
    f = lambda x_, g_, b_, w_: head_loss(x_, g_, b_, w_, labels, eps, mode)
    loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3))(
        x, g.astype(F32), b.astype(F32), wte.astype(F32))
    return loss, grads


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 1, 2))
def _leaves_update(p, m, v, g, t, hp):
    return _update_tree(p, g, m, v, t, dict(hp))


@partial(jax.jit, static_argnames=("positions",))
def _embed_bwd(dwte_head, dx0, ids, positions):
    dwte = dwte_head.at[ids.reshape(-1)].add(dx0.reshape(-1, dx0.shape[-1]))
    dwpe = jnp.zeros((positions, dx0.shape[-1]), F32).at[:dx0.shape[1]].add(
        jnp.sum(dx0, axis=0))
    return dwte, dwpe


class TrainReference:
    """Follows the job's first steps: loss of each, per-leaf norm of the first
    gradient, per-leaf norm of the parameters' change at the end.

    ``weights`` (leaf -> array in the state dtype) become the reference's own
    state and are donated step by step. ``devices`` spreads the layers'
    state over several chips when one cannot hold it; the arithmetic is the
    same, only where a layer's arrays live differs.
    """

    def __init__(self, cfg, weights, hp, mode="f32", devices=None):
        self.cfg, self.mode = cfg, mode
        self.hp = tuple(sorted((k, float(hp[k])) for k in
                               ("lr", "beta1", "beta2", "epsilon", "weight_decay")))
        self.devices = list(devices or [jax.devices()[0]])
        self.p = {k: jax.device_put(w, self._dev(k)) for k, w in weights.items()}
        self.m = {k: jnp.zeros_like(w) for k, w in self.p.items()}
        self.v = {k: jnp.zeros_like(w) for k, w in self.p.items()}
        self.t = 0
        self.losses, self.grad_norms = [], None

    def _dev(self, leaf):
        if leaf[0] == "h" and leaf[1].isdigit():
            return self.devices[int(leaf.split(".")[0][1:]) % len(self.devices)]
        return self.devices[0]

    def _take(self, tree, keys, prefix=""):
        return {k: tree[prefix + k] for k in keys}

    def _put(self, prefix, p, m, v):
        for k in p:
            self.p[prefix + k], self.m[prefix + k], self.v[prefix + k] = p[k], m[k], v[k]

    def step(self, ids):
        """``ids`` is (B, T+1): inputs ``[:, :-1]``, labels ``[:, 1:]``."""
        cfg, mode = self.cfg, self.mode
        eps, heads = float(cfg["layer_norm_epsilon"]), int(cfg["num_heads"])
        n = cfg["num_layers"]
        ids = np.asarray(ids)
        x_ids = jnp.asarray(ids[:, :-1], jnp.int32)
        labels = jnp.asarray(ids[:, 1:], jnp.int32)
        self.t += 1
        t = jnp.asarray(float(self.t), F32)
        xs = [_embed(self.p["wte"], self.p["wpe"], x_ids)]
        for i in range(n):
            xs[-1] = jax.device_put(xs[-1], self._dev(f"h{i}."))
            xs.append(_layer_fwd(layer_params(self.p, i), xs[-1], heads, eps, mode))
        x_last = jax.device_put(xs.pop(), self.devices[0])
        loss, (dx, dg, db, dwte) = _head_bwd(
            x_last, self.p["lnf.g"], self.p["lnf.b"], self.p["wte"], labels, eps, mode)
        norms = {}
        for i in reversed(range(n)):
            pre = f"h{i}."
            dev = self._dev(pre)
            dx, p, m, v, nm = _layer_bwd_update(
                self._take(self.p, LAYER_LEAVES, pre), self._take(self.m, LAYER_LEAVES, pre),
                self._take(self.v, LAYER_LEAVES, pre), xs.pop(),
                jax.device_put(dx, dev), jax.device_put(t, dev), heads, eps, mode, self.hp)
            self._put(pre, p, m, v)
            norms.update({pre + k: x for k, x in nm.items()})
        dx = jax.device_put(dx, self.devices[0])
        dwte, dwpe = _embed_bwd(dwte, dx, x_ids, self.p["wpe"].shape[0])
        keys = ("wte", "wpe", "lnf.g", "lnf.b")
        p, m, v, nm = _leaves_update(
            self._take(self.p, keys), self._take(self.m, keys), self._take(self.v, keys),
            {"wte": dwte, "wpe": dwpe, "lnf.g": dg, "lnf.b": db}, t, self.hp)
        self._put("", p, m, v)
        norms.update(nm)
        self.losses.append(float(loss))
        if self.grad_norms is None:
            self.grad_norms = {k: float(x) for k, x in norms.items()}
        return self.losses[-1]

    def change_norms(self, first_leaf):
        """Per-leaf ||theta_now - theta_0||; ``first_leaf(name)`` makes leaf
        ``name`` as it was before the first step."""
        return {k: float(diff_norm(w, jax.device_put(first_leaf(k), self._dev(k))))
                for k, w in self.p.items()}
