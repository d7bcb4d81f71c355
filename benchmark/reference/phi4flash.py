"""Plain reference of the ``phi4flash`` family (Phi-4-mini-flash-reasoning:
the SambaY decoder-hybrid-decoder of arXiv:2507.06607, Mamba-1 of
arXiv:2312.00752, differential attention of arXiv:2410.05258): the forward
pass in float32 ``jax.numpy`` with ``HIGHEST`` matmuls, one sequence at a
time, a ``lax.scan`` over the tokens for the recurrence, no kernel, no cache,
nothing taken from ``paddle_tpu``. Weights are the configuration's leaves
(``families/phi4flash.py`` lists them; the layers of a stack are one leaf a
matrix, stacked).

With ``d`` hidden, ``L`` layers, ``H`` query and ``G`` key/value heads of ``h
= d / H``, ``W = sliding_window``, ``d_i = mamba_expand d``, ``N =
mamba_d_state``, ``K = mamba_d_conv``, ``R = ceil(d / 16)``; LN a LayerNorm
with gain and bias, no positional encoding anywhere:

    x_0 = E[tok];  logits = LN(x_L) E^T
    every layer i:  x <- x + Mixer_i(LN_a(x));  [gate | up] = LN_b(x) W_up
                    x <- x + (up * silu(gate)) W_down

    Mamba (i even, i <= L/2), u = LN_a(x):
      [a | z] = u W_in;  c_t = silu(b_conv + sum_{k<K} w_conv[k] * a_{t-K+1+k})
      [r | B | C] = c_t W_x;  Delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
      S_t = exp(Delta_t A) * S_{t-1} + B_t (Delta_t c_t)^T      (N x d_i, S_{-1} = 0)
      y_t = S_t^T C_t + D * c_t;   out = (y_t * silu(z_t)) W_out
      layer L/2 also gives m_t = y_t to the layers behind it
    differential attention (i odd: i < L/2 over keys t - W < s <= t, i = L/2 + 1
    over s <= t):  [q | k | v] = u W_qkv
      neighbouring heads pair: query pairs (q1, q2) = heads (2j, 2j + 1), key
      pairs (k1, k2) = heads (2g, 2g + 1), a value head [v_2g | v_2g+1] of 2 h;
      key pair g serves the query pairs j with j // (H / G) = g
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
      lambda_init = 0.8 - 0.6 exp(-0.3 i)
      O = (softmax(q1 k1^T / sqrt h) - lambda softmax(q2 k2^T / sqrt h)) v
      O <- (1 - lambda_init) g * O / sqrt(mean(O^2) + eps)   over its 2 h numbers
      out = concat_j(O) W_o
    gated memory unit (i even, i > L/2 + 1):  out = (m_t * silu(u W_1)) W_2
    cross attention (i odd, i > L/2 + 1):  q = u W_q; k, v are layer L/2 + 1's;
      the same differential form, causal, its own lambda vectors and gain

``mode`` is ``reference/common``'s: ``"fp8"`` rounds the operands of every
matrix product with a learned matrix to float8 e4m3 (the head's scale is per
slice of the vocabulary).
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


def mm(x, w, mode):
    x, w = operands(x, w.astype(F32), mode)
    return jnp.matmul(x, w, precision=HI)


def ln(x, w, name, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w[name + ".g"].astype(F32)
            + w[name + ".b"].astype(F32))


def layer_kind(cfg, i):
    half = cfg["num_hidden_layers"] // 2
    if i <= half:
        return "mamba" if i % 2 == 0 else "window"
    if i == half + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def dt_rank(cfg):
    r = cfg.get("mamba_dt_rank", "auto")
    return math.ceil(cfg["hidden_size"] / 16) if r == "auto" else int(r)


class _Static(dict):
    """A configuration as a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))


@partial(jax.jit, static_argnames=("cfg", "mode"))
def ffn(cfg, w, x, mode):
    gate, up = jnp.split(mm(ln(x, w, "ffn_norm", cfg["layer_norm_eps"]),
                            w["up.w"], mode), 2, axis=-1)
    return x + mm(up * jax.nn.silu(gate), w["down.w"], mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def mamba(cfg, w, x, mode):
    """``(x + mixer, y)`` over one sequence ``x`` (T, d): ``y`` (T, d_i) is
    the scan's output before the gate."""
    T, N, K, R = x.shape[0], cfg["mamba_d_state"], cfg["mamba_d_conv"], dt_rank(cfg)
    a, z = jnp.split(mm(ln(x, w, "norm", cfg["layer_norm_eps"]), w["in_proj.w"],
                        mode), 2, axis=-1)
    ap = jnp.pad(a, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(w["conv.b"].astype(F32) + sum(
        w["conv.w"][k].astype(F32) * ap[k:k + T] for k in range(K)))
    rbc = mm(c, w["x_proj.w"], mode)
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    dt = jax.nn.softplus(mm(r, w["dt_proj.w"], mode) + w["dt_proj.b"].astype(F32))
    A, D = -jnp.exp(w["A_log"].astype(F32)), w["D"].astype(F32)

    def token(S, xs):
        dt_t, c_t, b_t, c_out = xs
        S = jnp.exp(dt_t[None, :] * A) * S + b_t[:, None] * (dt_t * c_t)[None, :]
        return S, jnp.sum(S * c_out[:, None], axis=0) + D * c_t

    _, y = jax.lax.scan(token, jnp.zeros(A.shape, F32), (dt, c, Bm, Cm))
    return x + mm(y * jax.nn.silu(z), w["out_proj.w"], mode), y


def _pairs(x, heads, h):
    """(T, heads h) -> the first and the second of each neighbouring pair,
    (T, heads / 2, h) each."""
    x = x.reshape(x.shape[0], heads // 2, 2, h)
    return x[:, :, 0], x[:, :, 1]


@partial(jax.jit, static_argnames=("cfg", "mode", "window"))
def diff_attention(cfg, w, x, kv, lam_init, window, mode):
    """``(x + mixer, (k, v))`` over one sequence. ``kv`` None: the layer's
    own keys and values (returned, (T, G h) each); else another layer's.
    ``window``: how many keys a query sees, itself among them (0: all)."""
    T, d = x.shape
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, eps = d // H, cfg["layer_norm_eps"]
    u = ln(x, w, "norm", eps)
    if kv is None:
        qkv = mm(u, w["qkv.w"], mode)
        q, kv = qkv[:, :H * h], (qkv[:, H * h:(H + G) * h], qkv[:, (H + G) * h:])
    else:
        q = mm(u, w["q.w"], mode)
    q1, q2 = _pairs(q, H, h)
    k1, k2 = (jnp.repeat(k, H // G, axis=1) for k in _pairs(kv[0], G, h))
    v = jnp.repeat(kv[1].reshape(T, G // 2, 2 * h), H // G, axis=1)
    t = jnp.arange(T)
    live = t[None, :] <= t[:, None]
    if window:
        live &= t[:, None] - t[None, :] < window
    soft = lambda q, k: jax.nn.softmax(jnp.where(
        live[None], jnp.einsum("qjh,kjh->jqk", q, k, precision=HI) * h ** -0.5,
        -jnp.inf), axis=-1)
    lam = (jnp.exp(jnp.sum(w["lambda_q1"].astype(F32) * w["lambda_k1"].astype(F32)))
           - jnp.exp(jnp.sum(w["lambda_q2"].astype(F32) * w["lambda_k2"].astype(F32)))
           + lam_init)
    O = jnp.einsum("jqk,kjc->qjc", soft(q1, k1) - lam * soft(q2, k2), v, precision=HI)
    O = O * jax.lax.rsqrt(jnp.mean(O * O, -1, keepdims=True) + eps)
    O = (1.0 - lam_init) * O * w["subln.g"].astype(F32)
    return x + mm(O.reshape(T, H * h), w["o.w"], mode), kv


@partial(jax.jit, static_argnames=("cfg", "mode"))
def gmu(cfg, w, x, m, mode):
    u = ln(x, w, "norm", cfg["layer_norm_eps"])
    return x + mm(m * jax.nn.silu(mm(u, w["in_proj.w"], mode)), w["out_proj.w"], mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def head_logits(x, w, wte, eps, mode):
    """(1, T, vocab): the tied head in slices of the vocabulary, so that the
    float32 copy of the embedding is never whole."""
    x, V = ln(x, w, "norm", eps), wte.shape[0]
    step = -(-V // HEAD_SLICES)
    return jnp.concatenate([mm(x, wte[v0:v0 + step].T, mode)
                            for v0 in range(0, V, step)], -1)[None]


def layer_leaves(cfg, weights, i):
    """Layer ``i``'s leaves by suffix, out of the stacks."""
    half, kind = cfg["num_hidden_layers"] // 2, layer_kind(cfg, i)
    if i in (half, half + 1):
        stack, at = "mid", None
    elif i < half:
        stack, at = "front", i // 2
    else:
        stack, at = "back", (i - half - 2) // 2
    p = f"{stack}.{ {'window': 'attn', 'full': 'attn'}.get(kind, kind)}."
    return {k[len(p):]: (v if at is None else v[at])
            for k, v in weights.items() if k.startswith(p)}


def forward_logits(cfg, weights, ids, mode="f32"):
    """Logits (B, T, vocab) float32 of ``ids`` (B, T), one sequence at a
    time, a layer at a time."""
    cfg = _Static({k: v for k, v in cfg.items()
                   if isinstance(v, (int, float, bool, str, type(None)))})
    L, W = cfg["num_hidden_layers"], cfg["sliding_window"]
    # ONE length for every request (causal in every layer: what lies behind a
    # position does not reach it), so that a run compiles the layers once and
    # not once a length; the logits come back at the length asked for
    ids = np.asarray(ids)
    T = ids.shape[1]
    ids = np.pad(ids, ((0, 0), (0, -T % min(PAD_TO, cfg["max_position_embeddings"]))))
    out = []
    for row in ids:
        x = weights["wte"][jnp.asarray(row)].astype(F32)
        m = kv = None
        for i in range(L):
            kind, w = layer_kind(cfg, i), layer_leaves(cfg, weights, i)
            if kind == "mamba":
                x, y = mamba(cfg, w, x, mode)
                m = y if i == L // 2 else m
            elif kind == "gmu":
                x = gmu(cfg, w, x, m, mode)
            else:
                x, own = diff_attention(
                    cfg, w, x, kv if kind == "cross" else None, lambda_init(i),
                    W if kind == "window" else 0, mode)
                kv = own if kind == "full" else kv
            x = ffn(cfg, w, x, mode)
        final = {"norm.g": weights["norm.g"], "norm.b": weights["norm.b"]}
        out.append(head_logits(x, final, weights["wte"], cfg["layer_norm_eps"],
                               mode)[:, :T])
    return jnp.concatenate(out)
