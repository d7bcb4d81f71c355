"""Plain reference of the ``xing4`` family (Xing4.0-29B-A4B: DeepSeek-V2 latent
attention with YaRN, DeepSeek-V3 sigmoid router with a shared expert,
manifold-constrained hyper-connections over four residual streams): the
forward pass in float32 ``jax.numpy`` with ``HIGHEST`` matmuls, no kernel, no
cache, no batching, nothing taken from ``paddle_tpu``. Weights are the
configuration's bfloat16 leaves (``families/xing4.py`` lists them), and the
one piece of state a server keeps, the cached latent row ``(ckv, kr)`` after
norm and RoPE, is rounded to that dtype as a cache would hold it.

The equations, per token (``d`` hidden, ``n = hc_mult`` streams, ``X`` an
``n x d`` matrix):

    X_0 = repeat(E[tok], n);  logits = RMSNorm_g(sum_j X_L[j]) W_head

    wrap(F), around attention and around the FFN of every layer:
      xb = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)
      z  = alpha (*) (xb phi) + bias          phi = [phi_pre | phi_post | phi_res]
      H_pre = sigmoid(z_pre); H_post = 2 sigmoid(z_post)
      M = exp(clip(mat(z_res), clamp)); hc_sinkhorn_iters times:
          M <- M / (colsum(M) + hc_eps); M <- M / (rowsum(M) + hc_eps)
      u = sum_j H_pre[j] X[j];  y = F(RMSNorm_g(u))
      X'[i] = sum_j M[i, j] X[j] + H_post[i] y
    (hc_mult 1: X' = X + F(RMSNorm_g(X)))

    attention: cq = RMSNorm_g(u W_dq); [q_nope ; q_rope]_h = cq W_uq
      [ckv ; kr] = u W_dkv; ckv = RMSNorm_g(ckv); kr = RoPE(kr); q_rope = RoPE(q_rope)
      [k_nope ; v]_h = ckv W_ukv
      s = (q_nope . k_nope + q_rope . kr) qk_head_dim^-0.5 m^2, m = 0.1 mscale_all_dim ln(factor) + 1
      o = concat_h(causal_softmax(s) v) W_o
    RoPE: half-split pairs; YaRN frequencies blend f and f / factor by the
    linear ramp between the correction dims of beta_fast / beta_slow.

    FFN, layers below first_k_dense_replace: (silu(x W_g) * x W_u) W_d
    others: sc = sigmoid(x W_r); the k largest of sc + e_bias;
      g = sc[picked] / sum(sc[picked]) * routed_scaling_factor
      y = sum_k g_k Expert_k(x) + Shared(x)     no capacity, nothing dropped

Blocked so that it fits beside 11.3 GB of leaves: a layer at a time, heads in
groups, experts one at a time (dense and masked: every expert over every
token, kept where the token chose it), the head in slices of the vocabulary.
``mode`` is ``reference/common``'s: ``"fp8"`` rounds the operands of every
matrix product with a learned matrix to float8 e4m3 (the head's scale is per
slice of the vocabulary).

``forward`` also returns, for every position, the narrowest margin by which
its routing was decided (``routing_margin``), and can withhold its verdict
where that is under ``min_margin``; ``forward_logits`` is the whole reference.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, HI, operands

HEAD_SLICES = 8
PAD_TO = 2048
HEADS_AT_ONCE = 8


def mm(x, w, mode):
    x, w = operands(x, w.astype(F32), mode)
    return jnp.matmul(x, w, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(F32)


def yarn(cfg):
    """(inverse frequencies, cos/sin amplitude, softmax scale)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    rs = cfg.get("rope_scaling")
    if not rs:
        return f, 1.0, scale
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    corr = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    msc = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    m_all = msc(rs.get("mscale_all_dim", 0) or 0)
    return f / factor * ramp + f * (1 - ramp), msc(rs.get("mscale", 1)) / m_all, \
        scale * m_all ** 2


def rope(x, inv, amp):
    """x (T, ..., D) at positions 0..T-1, half-split pairs."""
    T, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv, F32)
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def wrap(cfg, hc, norm_g, X, fn, mode):
    """One sub-layer inside its hyper-connection; X (T, n, d)."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    if n == 1:
        return X + fn(rms(X[:, 0], norm_g, eps))[:, None]
    T = X.shape[0]
    flat = X.reshape(T, -1)
    xb = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + cfg["hc_eps"])
    a = hc["alpha"].astype(F32)
    alpha = jnp.concatenate([jnp.full((n,), a[0]), jnp.full((n,), a[1]),
                             jnp.full((n * n,), a[2])])
    z = mm(xb, hc["phi"], mode) * alpha + hc["bias"].astype(F32)
    h_pre = jax.nn.sigmoid(z[:, :n])
    h_post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
    m = jnp.exp(jnp.clip(z[:, 2 * n:], cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])).reshape(T, n, n)
    def sinkhorn(_, m):
        m = m / (m.sum(axis=1, keepdims=True) + cfg["hc_eps"])     # columns
        return m / (m.sum(axis=2, keepdims=True) + cfg["hc_eps"])  # rows

    m = jax.lax.fori_loop(0, cfg["hc_sinkhorn_iters"], sinkhorn, m)
    u = jnp.sum(h_pre[:, :, None] * X, axis=1)
    y = fn(rms(u, norm_g, eps))
    return jnp.sum(m[:, :, :, None] * X[:, None, :, :], axis=2) + h_post[:, :, None] * y[:, None, :]


def attention(cfg, w, u, mode):
    """Causal latent attention of one sequence, expanded form; u (T, d)."""
    T, H, eps = u.shape[0], cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], cfg["kv_lora_rank"])
    inv, amp, scale = yarn(cfg)
    if cfg.get("q_lora_rank"):
        q = mm(rms(mm(u, w["attn.q_a.w"], mode), w["attn.q_a_norm.g"], eps),
               w["attn.q_b.w"], mode)
    else:
        q = mm(u, w["attn.q.w"], mode)
    q = q.reshape(T, H, nope + dr)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv, amp)
    kv = mm(u, w["attn.kv_a.w"], mode)
    # what a cache holds, in the dtype it holds it
    store = w["attn.kv_a.w"].dtype
    ckv = rms(kv[:, :r], w["attn.kv_a_norm.g"], eps).astype(store).astype(F32)
    kr = rope(kv[:, r:], inv, amp).astype(store).astype(F32)
    kvb = mm(ckv, w["attn.kv_b.w"], mode).reshape(T, H, nope + dv)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(group):  # a group of heads at a time: (G, T, ...) each
        qn, qr, kn, v = group
        s = (jnp.einsum("hqn,hkn->hqk", qn, kn, precision=HI)
             + jnp.einsum("hqr,kr->hqk", qr, kr, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkv->hqv", p, v, precision=HI)

    G = min(HEADS_AT_ONCE, H)
    by_group = lambda x: jnp.moveaxis(x, 1, 0).reshape((H // G, G) + (T, x.shape[-1]))
    o = jax.lax.map(heads, (by_group(q_nope), by_group(q_rope),
                            by_group(kvb[..., :nope]), by_group(kvb[..., nope:])))
    o = jnp.moveaxis(o.reshape(H, T, dv), 0, 1).reshape(T, H * dv)
    return mm(o, w["attn.o.w"], mode)


def gated(x, g, u, d, mode):
    return mm(jax.nn.silu(mm(x, g, mode)) * mm(x, u, mode), d, mode)


def routing(cfg, w, x, mode):
    """(T, E) float32: the gate of each expert for each token, 0 where the
    token did not choose it. Over ALL the experts, whatever is held."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    sc = jax.nn.sigmoid(mm(x, w["mlp.router.w"], mode))
    _, pick = jax.lax.top_k(sc + w["mlp.router.e_bias"].astype(F32), k)
    g = jnp.take_along_axis(sc, pick, -1)
    if cfg.get("norm_topk_prob", True):
        g = g / g.sum(-1, keepdims=True)
    g = g * cfg["routed_scaling_factor"]
    return jnp.sum(g[..., None] * (pick[..., None] == jnp.arange(E)), axis=1)


def routing_margin(cfg, w, x, mode):
    """(T,) float32: how far the last expert a token picks lies above the first
    it leaves out, in ``score + e_bias``. The pick is a step function of the
    scores: where this margin is narrower than the rounding of a lower
    precision moves a score, that precision may pick the other expert and be
    no less right (``forward``'s ``min_margin``)."""
    k = cfg["num_experts_per_tok"]
    sc = jax.nn.sigmoid(mm(x, w["mlp.router.w"], mode)) + w["mlp.router.e_bias"].astype(F32)
    top, _ = jax.lax.top_k(sc, k + 1)
    return top[:, k - 1] - top[:, k]


def expert_ffn(cfg, w, x, mode, held=None):
    """Routed experts (those in ``held``; default all) and the shared one."""
    held = list(range(cfg["n_routed_experts"])) if held is None else list(held)
    combine = routing(cfg, w, x, mode)[:, jnp.asarray(held)]

    def body(acc, e):
        y = gated(x, w["mlp.experts.gate"][e], w["mlp.experts.up"][e],
                  w["mlp.experts.down"][e], mode)
        return acc + combine[:, e][:, None] * y, None

    y, _ = jax.lax.scan(body, jnp.zeros_like(x), jnp.arange(len(held)))
    if cfg.get("n_shared_experts"):
        y = y + gated(x, w["mlp.shared.gate.w"], w["mlp.shared.up.w"],
                      w["mlp.shared.down.w"], mode)
    return y


class _Static(dict):
    """A configuration as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))


def _hc(cfg, w, sub):
    return {k: w[f"{sub}_hc.{k}"] for k in ("phi", "alpha", "bias")} \
        if cfg["hc_mult"] > 1 else None


@partial(jax.jit, static_argnames=("cfg", "mode"))
def attention_sublayer(cfg, w, X, mode):
    """The attention half of a layer inside its hyper-connection (one
    compiled program for every layer: their attention leaves are alike)."""
    return wrap(cfg, _hc(cfg, w, "attn"), w["attn_norm.g"], X,
                lambda u: attention(cfg, w, u, mode), mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def ffn_sublayer(cfg, w, X, mode):
    """The feed-forward half: routed + shared experts where the layer has a
    router, the dense gated MLP where not. Returns ``(X', margin)``: each
    token's ``routing_margin`` in this layer, infinite where nothing routes."""
    margin = [jnp.full((X.shape[0],), jnp.inf, F32)]
    if "mlp.router.w" in w:
        def ffn(u):
            margin[0] = routing_margin(cfg, w, u, mode)
            return expert_ffn(cfg, w, u, mode)
    else:
        ffn = lambda u: gated(u, w["mlp.gate.w"], w["mlp.up.w"], w["mlp.down.w"], mode)
    X = wrap(cfg, _hc(cfg, w, "ffn"), w["ffn_norm.g"], X, ffn, mode)
    return X, margin[0]


def layer(cfg, w, X, mode):
    """One decoder layer over one sequence; ``w`` holds the layer's leaves by
    suffix (``attn_hc.phi``, ``attn.q_a.w``, ``mlp.router.w``, ...). Returns
    ``(X', margin)`` as ``ffn_sublayer`` does."""
    part = lambda *heads: {k: v for k, v in w.items() if k.startswith(heads)}
    X = attention_sublayer(cfg, part("attn"), X, mode)
    return ffn_sublayer(cfg, part("ffn", "mlp"), X, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def head_logits(h, g, w, eps, mode, keep):
    """(1, T, vocab): the head in slices of the vocabulary, so that the
    float32 copy of its matrix is never whole; the row of a position that is
    not in ``keep`` (T,) is all zeros."""
    x, V = rms(h, g, eps) * keep[:, None], w.shape[1]
    step = -(-V // HEAD_SLICES)
    return jnp.concatenate([mm(x, w[:, v0:v0 + step], mode)
                            for v0 in range(0, V, step)], -1)[None]


def forward(cfg, weights, ids, mode="f32", min_margin=0.0):
    """``(logits (B, T, vocab), margin (B, T))`` float32 of ``ids`` (B, T), one
    sequence at a time. ``margin`` is a position's narrowest ``routing_margin``
    over the expert layers (infinite for a model that routes nothing).

    ``min_margin``: top-k routing is a step function, so where two scores
    nearly tie, float32 and the configuration's bfloat16 pick different experts
    and BOTH are the model: no reference computed in one precision can say
    which tokens the other may serve there. With ``min_margin`` above 0 the
    reference gives a verdict only where its own routing is decided by at
    least that much in every expert layer, and the logits of every other
    position come back all zeros (any token's gap below the best is then 0).
    It looks at nothing but its own float32 scores."""
    cfg = _Static({k: v for k, v in cfg.items()
                   if isinstance(v, (int, float, bool, str, type(None)))
                   or k == "rope_scaling"})
    if cfg.get("rope_scaling"):
        cfg["rope_scaling"] = _Static(cfg["rope_scaling"])
    n = cfg["hc_mult"]
    ids = np.asarray(ids)
    head = weights["head.w"] if "head.w" in weights else weights["wte"].T
    # ONE length for every request (causal: what lies behind a position does
    # not reach it), so that a run compiles the layers once and not once a
    # length; the logits come back at the length asked for
    T = ids.shape[1]
    pad = min(PAD_TO, cfg["max_position_embeddings"])
    ids = np.pad(ids, ((0, 0), (0, -T % pad)))
    out, margins = [], []
    for row in ids:
        X = jnp.repeat(weights["wte"][jnp.asarray(row)].astype(F32)[:, None, :], n, axis=1)
        narrowest = jnp.full((len(row),), jnp.inf, F32)
        for i in range(cfg["num_hidden_layers"]):
            p = f"h{i}."
            w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
            X, margin = layer(cfg, w, X, mode)
            narrowest = jnp.minimum(narrowest, margin)
        keep = (narrowest >= min_margin).astype(F32)
        out.append(head_logits(X.sum(axis=1), weights["norm.g"], head,
                               cfg["rms_norm_eps"], mode, keep)[:, :T])
        margins.append(narrowest[None, :T])
    return jnp.concatenate(out), jnp.concatenate(margins)


def forward_logits(cfg, weights, ids, mode="f32"):
    """Logits (B, T, vocab) float32 of ``ids`` (B, T): a verdict at every
    position (``forward`` with ``min_margin`` 0)."""
    return forward(cfg, weights, ids, mode)[0]
