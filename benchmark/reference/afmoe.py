"""Plain reference of the ``afmoe`` family (arcee-ai Trinity: window layers with
rotary positions beside full layers without any, a sigmoid gate on the
attention output, norms before and after both sub-layers, an embedding scaled
by sqrt(d), a sigmoid router over routed experts with a shared one): the
forward pass in float32 ``jax.numpy`` with ``HIGHEST`` matmuls, no kernel, no
cache, no batching, nothing taken from ``paddle_tpu``. Weights are the
configuration's bfloat16 leaves (``families/afmoe.py`` lists them); what a
server keeps of a token (K after norm and rotation, V) is rounded to that
dtype as a cache would hold it.

The equations, per token (``d`` hidden, RMS norms with ``rms_norm_eps`` and a
gain, no bias anywhere, H query heads on G key/value heads of D):

    x_0 = E[tok] * sqrt(d)                                    (mup_enabled)
    a = RMSNorm(x; g_in);  [q | k | v | z] = a W_qkvg         (H, G, G, H heads)
    q, k <- RMSNorm over D with gains g_q, g_k
    layer_types[i] == "sliding_attention": q, k <- RoPE (theta rope_theta, all D
      dimensions, half-split pairs, no scaling); a query at p sees p' with
      0 <= p - p' < sliding_window
    "full_attention": NO rotation; every p' <= p
    o = softmax(q k^T / sqrt(D)) v, H / G query heads a K/V head
    o <- o * sigmoid(z);  x' = x + RMSNorm(o W_o; g_post_attn)
    b = RMSNorm(x'; g_pre_mlp);  x'' = x' + RMSNorm(F(b); g_post_mlp)
    logits = RMSNorm(x_L; g_f) W_head                         (untied)

    F, i < num_dense_layers: (silu(b W_g) * b W_u) W_d
    others: s = sigmoid(b W_r) in float32 over ALL published experts; the k
      largest of s + bias;  g = s[picked] / sum(s[picked]) * route_scale
      F = Shared(b) + sum over the picked experts HELD HERE of g_e Expert_e(b)

``num_experts`` of a configuration is how many experts this chip HOLDS (the
first ones); the router's width is ``published.num_experts``. What the absent
experts would have added is left out, as the program leaves it out.

Blocked so that a context of 8,192 fits beside the leaves: a layer at a time,
a K/V group of heads at a time and, inside it, a BLOCK OF QUERY ROWS at a time
(the float32 scores of 32 heads over 8,192 x 8,192 are 8.6 GB; of 8 heads over
512 x 8,192, 134 MB), experts one at a time (dense and masked), the head in
slices of the vocabulary. ``mode`` is ``reference/common``'s.

``forward`` also returns, for every position, the narrowest margin by which
the membership of an expert HELD HERE was decided (``held_margin``), and can
withhold its verdict where that is under ``min_margin``; ``forward_logits`` is
the whole reference.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, HI
from .lfm2 import period, rope  # noqa: F401  (generic over layer_types)
from .xing4 import _Static, expert_ffn, gated, head_logits, mm, rms, routing  # noqa: F401

QUERY_ROWS = 512
# the lengths a sequence is run at: a window up to a window, whole multiples of
# 3.5 windows beyond (every layer is causal: what lies behind a position does
# not reach it), so that a run compiles the layers for TWO lengths, not nine
SHORT_PAD, LONG_PAD = 2048, 7168


def layer_leaves(cfg, weights, i: int) -> dict:
    """Layer ``i``'s leaves by suffix: ``h<i>.*`` for an unrolled layer; a layer
    of a whole period is repetition ``(i - num_dense_layers) // len(period)`` of
    ``h<j>.stack.*``, ``j`` the FIRST layer at its position of the period (the
    leaves of the layers that repeat it are stacked on it, as the program
    scans them)."""
    own = f"h{i}."
    if any(k.startswith(own) and not k.startswith(own + "stack.") for k in weights):
        return {k[len(own):]: v for k, v in weights.items() if k.startswith(own)}
    nd = cfg["num_dense_layers"]
    turn, j = divmod(i - nd, len(period(cfg)))
    own = f"h{nd + j}.stack."
    return {k[len(own):]: v[turn] for k, v in weights.items() if k.startswith(own)}


def held(cfg) -> tuple:
    """The routed experts this chip holds, ``num_experts`` of the published
    ``published.num_experts``: the first ones, or ``held_experts`` (another
    chip's share)."""
    return tuple(cfg.get("held_experts") or range(cfg["num_experts"]))


def router_width(cfg) -> int:
    return int((cfg.get("published") or {}).get("num_experts", cfg["num_experts"]))


def _router(cfg):
    """The configuration under the names ``reference/xing4.py``'s router and
    expert product read."""
    return _Static({"n_routed_experts": router_width(cfg),
                    "num_experts_per_tok": cfg["num_experts_per_tok"],
                    "norm_topk_prob": cfg["route_norm"],
                    "routed_scaling_factor": cfg["route_scale"],
                    "n_shared_experts": cfg["num_shared_experts"]})


def held_margin(cfg, w, n, mode):
    """(T,) float32: how far the nearest expert HELD HERE is from changing
    sides, in ``score + bias``: a held expert among the ``k`` picked above the
    first score left out, a held expert left out below the last one picked.
    The pick is a step function of the scores; a flip among experts held
    elsewhere moves nothing here but the gates' common denominator, by the
    difference of two scores that nearly tie."""
    k, mine = cfg["num_experts_per_tok"], jnp.asarray(held(cfg))
    sc = jax.nn.sigmoid(mm(n, w["mlp.router.w"], mode)) + w["mlp.router.e_bias"].astype(F32)
    top, _ = jax.lax.top_k(sc, k + 1)
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    here = sc[:, mine]
    picked = here >= last_in
    return jnp.minimum(jnp.min(jnp.where(picked, here - first_out, jnp.inf), axis=-1),
                       jnp.min(jnp.where(picked, jnp.inf, last_in - here), axis=-1))


def attention(cfg, w, sliding, n, mode):
    """Grouped-query attention of one sequence, in blocks of query rows; n
    (T, d). ``sliding`` (a traced bool: one compiled program serves both kinds
    of layer) says whether the layer rotates and has a window."""
    T, H, G, D = (n.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, W = cfg["rms_norm_eps"], cfg["sliding_window"]
    qkvg = mm(n, w["attn.qkvg.w"], mode)
    q = rms(qkvg[:, :H * D].reshape(T, H, D), w["attn.q_norm.g"], eps)
    k = rms(qkvg[:, H * D:(H + G) * D].reshape(T, G, D), w["attn.k_norm.g"], eps)
    v = qkvg[:, (H + G) * D:(H + 2 * G) * D].reshape(T, G, D)
    z = qkvg[:, (H + 2 * G) * D:]
    theta = float(cfg["rope_theta"])
    q, k = jnp.where(sliding, rope(q, theta), q), jnp.where(sliding, rope(k, theta), k)
    store = w["attn.qkvg.w"].dtype  # what a cache holds, in the dtype it holds it
    k, v = k.astype(store).astype(F32), v.astype(store).astype(F32)
    bq = math.gcd(T, QUERY_ROWS) if T > QUERY_ROWS else T
    kpos = jnp.arange(T)[None, :]

    def group(qkv):  # the H / G query heads of one K/V head: (rep, T, D), (T, D)
        qg, kg, vg = qkv

        def rows(i):  # one block of query rows against every key, masked
            qb = jax.lax.dynamic_slice_in_dim(qg, i * bq, bq, axis=1)
            qpos = (i * bq + jnp.arange(bq))[:, None]
            sees = (kpos <= qpos) & (~sliding | (qpos - kpos < W))
            s = jnp.einsum("rqd,kd->rqk", qb, kg, precision=HI) * D ** -0.5
            p = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
            return jnp.einsum("rqk,kd->rqd", p, vg, precision=HI)

        o = jax.lax.map(rows, jnp.arange(T // bq))       # (blocks, rep, bq, D)
        return jnp.moveaxis(o, 0, 1).reshape(qg.shape)

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0).reshape(G, H // G, T, D),
                            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o.reshape(H, T, D), 0, 1).reshape(T, H * D) * jax.nn.sigmoid(z)
    return mm(o, w["attn.o.w"], mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer(cfg, w, sliding, x, mode):
    eps = cfg["rms_norm_eps"]
    n = rms(x, w["in_norm.g"], eps)
    x = x + rms(attention(cfg, w, sliding, n, mode), w["post_attn_norm.g"], eps)
    n = rms(x, w["pre_mlp_norm.g"], eps)
    if "mlp.router.w" not in w:
        y = gated(n, w["mlp.gate.w"], w["mlp.up.w"], w["mlp.down.w"], mode)
        margin = jnp.full((x.shape[0],), jnp.inf, F32)
    else:
        y = expert_ffn(_router(cfg), w, n, mode, held(cfg))
        margin = held_margin(cfg, w, n, mode)
    return x + rms(y, w["post_mlp_norm.g"], eps), margin


def layer(cfg, w, kind, x, mode):
    """One layer of ``kind`` over one sequence ``x`` (T, d); ``w`` holds its
    leaves by suffix (``mlp.router.w`` or a dense FFN). Returns ``(x,
    margin)``: each token's ``held_margin`` here, infinite where nothing
    routes."""
    return _layer(cfg, w, jnp.asarray(kind == "sliding_attention"), x, mode)


def static(cfg) -> _Static:
    """A configuration as a static argument of ``jax.jit``: its scalars, its
    lists as tuples, the router's published width."""
    flat = {k: tuple(v) if isinstance(v, list) else v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str, type(None), list, tuple))}
    return _Static({**flat, "published": _Static({"num_experts": router_width(cfg)})})


def forward(cfg, weights, ids, mode="f32", min_margin=0.0):
    """``(logits (B, T, vocab), margin (B, T))`` float32 of ``ids`` (B, T), one
    sequence at a time, padded to ``SHORT_PAD`` or to whole ``LONG_PAD``s;
    ``margin`` is a position's narrowest ``held_margin`` over the expert
    layers. With ``min_margin`` above 0 the logits of a position decided by
    less come back all zeros (``reference/xing4.py``'s ``forward`` says why)."""
    cfg = static(cfg)
    ids = np.asarray(ids)
    T = ids.shape[1]
    to = min(SHORT_PAD, cfg["max_position_embeddings"]) if T <= SHORT_PAD else LONG_PAD
    ids = np.pad(ids, ((0, 0), (0, -T % to)))
    out, margins = [], []
    for row in ids:
        x = weights["wte"][jnp.asarray(row)].astype(F32)
        if cfg.get("mup_enabled", True):
            x = x * math.sqrt(cfg["hidden_size"])
        narrowest = jnp.full((len(row),), jnp.inf, F32)
        for i, kind in enumerate(cfg["layer_types"]):
            x, margin = layer(cfg, layer_leaves(cfg, weights, i), kind, x, mode)
            narrowest = jnp.minimum(narrowest, margin)
        keep = (narrowest >= min_margin).astype(F32)
        out.append(head_logits(x, weights["norm.g"], weights["head.w"],
                               cfg["rms_norm_eps"], mode, keep)[:, :T])
        margins.append(narrowest[None, :T])
    return jnp.concatenate(out), jnp.concatenate(margins)


def forward_logits(cfg, weights, ids, mode="f32"):
    """Logits (B, T, vocab) float32 of ``ids`` (B, T): a verdict at every
    position (``forward`` with ``min_margin`` 0)."""
    return forward(cfg, weights, ids, mode)[0]
