"""Plain reference of the ``lfm2`` family (LiquidAI LFM2-MoE: gated short
convolutions beside grouped-query attention, a sigmoid router over routed
experts with no shared one): the forward pass in float32 ``jax.numpy`` with
``HIGHEST`` matmuls, no kernel, no cache, no batching, nothing taken from
``paddle_tpu``. Weights are the configuration's bfloat16 leaves
(``families/lfm2.py`` lists them); what a server keeps a token or a row (K
and V after norm and rotation, the convolution's input ``z``) is rounded to
that dtype as a cache would hold it.

The equations, per token (``d`` hidden, RMS norms with ``norm_eps`` and a
gain, no bias anywhere):

    x_0 = E[tok];  x' = x + Op_i(RMSNorm(x));  x'' = x' + FFN_i(RMSNorm(x'))
    logits = E RMSNorm(x_L)                       (the head is the embedding)

    Op, layer_types[i] == "conv":  [B, C, u] = split3(n W_in);  z = B * u
      c_t = sum_{j < K} w[j] * z_{t-K+1+j}        K = conv_L_cache taps, depthwise,
                                                  zeros before the first token
      Op = (C * c) W_out
    Op, "full_attention":  [q | k | v] = n W_qkv  (H, G, G heads of D = d / H)
      q, k <- RMSNorm over D with gains g_q, g_k, THEN RoPE (theta rope_theta,
      all D dimensions, half-split pairs, no scaling)
      o = causal_softmax(q k^T / sqrt(D)) v, H / G query heads a K/V head
      Op = o W_o

    FFN, i < num_dense_layers: (silu(n W_g) * n W_u) W_d
    others: s = sigmoid(n W_r) in float32; the k largest of s + b;
      g = s[picked] / sum(s[picked]) * routed_scaling_factor
      y = sum_k g_k Expert_k(n)        no shared expert, no capacity, nothing dropped

Blocked so that it fits beside 10.5 GB of leaves: a layer at a time, a K/V
group of heads at a time, experts one at a time (dense and masked: every
expert over every token, kept where the token chose it), the head in slices
of the vocabulary. ``mode`` is ``reference/common``'s: ``"fp8"`` rounds the
operands of every matrix product with a learned matrix to float8 e4m3.

The leaves of the layers behind the dense ones are STACKED over the
repetitions of the layer pattern (``body.<j>.*``: position ``j`` of the
period), as the program holds them; ``layer_leaves`` finds a layer's.

``forward`` also returns, for every position, the narrowest margin by which
its routing was decided, and can withhold its verdict where that is under
``min_margin`` (as ``reference/xing4.py`` does, whose ``routing``,
``routing_margin`` and ``head_logits`` these are); ``forward_logits`` is the
whole reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, HI
from .xing4 import _Static, gated, head_logits, mm, rms, routing, routing_margin

PAD_TO = 2048


def period(cfg) -> tuple:
    """The shortest pattern the layers behind the dense ones repeat."""
    body = tuple(cfg["layer_types"][cfg["num_dense_layers"]:])
    for p in range(1, len(body) + 1):
        if all(body[i] == body[i % p] for i in range(len(body))):
            return body[:p]
    return ()


def layer_leaves(cfg, weights, i: int) -> dict:
    """Layer ``i``'s leaves by suffix: ``h<i>.*`` for an unrolled layer, else
    repetition ``(i - num_dense_layers) // len(period)`` of ``body.<j>.*``."""
    own = f"h{i}."
    if any(k.startswith(own) for k in weights):
        return {k[len(own):]: v for k, v in weights.items() if k.startswith(own)}
    p = len(period(cfg))
    turn, j = divmod(i - cfg["num_dense_layers"], p)
    own = f"body.{j}."
    return {k[len(own):]: v[turn] for k, v in weights.items() if k.startswith(own)}


def rope(x, theta):
    """x (T, heads, D) at positions 0..T-1, half-split pairs."""
    T, D = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    ang = (jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32))[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def gated_conv(cfg, w, n, mode):
    """The gated short convolution of one sequence; n (T, d)."""
    T, K = n.shape[0], cfg["conv_L_cache"]
    gate_b, gate_c, u = jnp.split(mm(n, w["conv.in_proj.w"], mode), 3, axis=-1)
    store = w["conv.in_proj.w"].dtype
    z = (gate_b * u).astype(store).astype(F32)  # what a row's state holds
    zp = jnp.pad(z, ((K - 1, 0), (0, 0)))
    taps = w["conv.conv.w"].astype(F32)
    c = sum(taps[j] * zp[j:j + T] for j in range(K))
    return mm(gate_c * c, w["conv.out_proj.w"], mode)


def attention(cfg, w, n, mode):
    """Causal grouped-query attention of one sequence; n (T, d)."""
    T, H, G = n.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps = cfg["hidden_size"] // H, cfg["norm_eps"]
    theta = float((cfg.get("rope_parameters") or cfg)["rope_theta"])
    qkv = mm(n, w["attn.qkv.w"], mode)
    q = qkv[:, :H * D].reshape(T, H, D)
    k = qkv[:, H * D:(H + G) * D].reshape(T, G, D)
    v = qkv[:, (H + G) * D:].reshape(T, G, D)
    store = w["attn.qkv.w"].dtype  # what a cache holds, in the dtype it holds it
    q = rope(rms(q, w["attn.q_norm.g"], eps), theta)
    k = rope(rms(k, w["attn.k_norm.g"], eps), theta).astype(store).astype(F32)
    v = v.astype(store).astype(F32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def group(qkv):  # the H / G query heads of one K/V head: (rep, T, D), (T, D)
        qg, kg, vg = qkv
        s = jnp.einsum("rqd,kd->rqk", qg, kg, precision=HI) * D ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->rqd", p, vg, precision=HI)

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0).reshape(G, H // G, T, D),
                            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return mm(jnp.moveaxis(o.reshape(H, T, D), 0, 1).reshape(T, H * D),
              w["attn.o.w"], mode)


def _router(cfg):
    """The configuration under the names ``reference/xing4.py``'s router
    reads."""
    return _Static({"n_routed_experts": cfg["num_experts"],
                    "num_experts_per_tok": cfg["num_experts_per_tok"],
                    "norm_topk_prob": cfg["norm_topk_prob"],
                    "routed_scaling_factor": cfg["routed_scaling_factor"]})


def experts(cfg, w, n, mode):
    """The routed experts, dense and masked, one at a time."""
    combine = routing(_router(cfg), {"mlp.router.w": w["mlp.router.w"],
                                     "mlp.router.e_bias": w["mlp.router.expert_bias"]},
                      n, mode)

    def body(acc, e):
        y = gated(n, w["mlp.experts.gate"][e], w["mlp.experts.up"][e],
                  w["mlp.experts.down"][e], mode)
        return acc + combine[:, e][:, None] * y, None

    return jax.lax.scan(body, jnp.zeros_like(n), jnp.arange(cfg["num_experts"]))[0]


@partial(jax.jit, static_argnames=("cfg", "mode"))
def layer(cfg, w, x, mode):
    """One layer over one sequence ``x`` (T, d); ``w`` holds its leaves by
    suffix (the keys say what it is: ``conv.*`` or ``attn.*``, ``mlp.router.w``
    or a dense FFN). Returns ``(x, margin)``: each token's
    ``routing_margin`` here, infinite where nothing routes."""
    n = rms(x, w["op_norm.g"], cfg["norm_eps"])
    op = gated_conv if "conv.conv.w" in w else attention
    x = x + op(cfg, w, n, mode)
    n = rms(x, w["ffn_norm.g"], cfg["norm_eps"])
    if "mlp.router.w" not in w:
        return x + gated(n, w["mlp.gate.w"], w["mlp.up.w"], w["mlp.down.w"], mode), \
            jnp.full((x.shape[0],), jnp.inf, F32)
    margin = routing_margin(_router(cfg), {"mlp.router.w": w["mlp.router.w"],
                                           "mlp.router.e_bias": w["mlp.router.expert_bias"]},
                            n, mode)
    return x + experts(cfg, w, n, mode), margin


def static(cfg) -> _Static:
    """A configuration as a static argument of ``jax.jit``: its scalars, its
    lists as tuples, ``rope_parameters``."""
    return _Static({k: (tuple(v) if isinstance(v, list) else _Static(v)
                        if isinstance(v, dict) else v) for k, v in cfg.items()
                    if isinstance(v, (int, float, bool, str, type(None), list, tuple))
                    or k == "rope_parameters"})


def forward(cfg, weights, ids, mode="f32", min_margin=0.0):
    """``(logits (B, T, vocab), margin (B, T))`` float32 of ``ids`` (B, T), one
    sequence at a time; ``margin`` is a position's narrowest
    ``routing_margin`` over the expert layers. With ``min_margin`` above 0 the
    logits of a position whose routing is decided by less come back all zeros
    (``reference/xing4.py``'s ``forward`` says why)."""
    cfg = static(cfg)
    ids = np.asarray(ids)
    # ONE length for every request (causal: what lies behind a position does
    # not reach it), so that a run compiles the layers once and not once a length
    T = ids.shape[1]
    ids = np.pad(ids, ((0, 0), (0, -T % min(PAD_TO, cfg["max_position_embeddings"]))))
    out, margins = [], []
    for row in ids:
        x = weights["wte"][jnp.asarray(row)].astype(F32)
        narrowest = jnp.full((len(row),), jnp.inf, F32)
        for i in range(cfg["num_hidden_layers"]):
            x, margin = layer(cfg, layer_leaves(cfg, weights, i), x, mode)
            narrowest = jnp.minimum(narrowest, margin)
        keep = (narrowest >= min_margin).astype(F32)
        out.append(head_logits(x, weights["norm.g"], weights["wte"].T,
                               cfg["norm_eps"], mode, keep)[:, :T])
        margins.append(narrowest[None, :T])
    return jnp.concatenate(out), jnp.concatenate(margins)


def forward_logits(cfg, weights, ids, mode="f32"):
    """Logits (B, T, vocab) float32 of ``ids`` (B, T): a verdict at every
    position (``forward`` with ``min_margin`` 0)."""
    return forward(cfg, weights, ids, mode)[0]
