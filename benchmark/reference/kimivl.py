"""Plain reference of the ``kimivl`` family (the decoder of Kimi-VL-A3B:
DeepSeek-V2 latent attention with a direct query projection and plain rotary
positions, DeepSeek-V3's sigmoid router over 64 experts at 6 a token beside
two shared experts, one residual stream): the forward pass in float32
``jax.numpy`` with ``HIGHEST`` matmuls, no kernel, no cache, no batching,
nothing taken from ``paddle_tpu``. Weights are the configuration's bfloat16
leaves (``families/kimivl.py`` lists them), and the one piece of state a
server keeps, the cached latent row ``(ckv, kr)`` after norm and RoPE, is
rounded to that dtype as a cache would hold it.

The equations are ``reference/xing4.py``'s with ``hc_mult`` 1 (``X' = X +
F(RMSNorm_g(X))``), a null ``q_lora_rank`` (``[q_nope ; q_rope]_h = u W_q``)
and a null ``rope_scaling`` (plain frequencies, score scale
``qk_head_dim^-0.5``), and its functions are what this file calls wherever
they apply: ``rms``, ``mm``, ``rope``, ``yarn``, ``wrap``, ``gated``,
``routing_margin``, ``expert_ffn``, ``head_logits``.

Departures, all of them about SIZE (a request of this family's cell is up to
24,960 positions, where that file's whole (heads, T, T) scores would be 42 GB
and a whole (T, vocabulary) logits array 16 GB):

- attention in blocks of ``QUERY_ROWS`` query rows against every key, masked
  by position: float32 scores of one block, (heads, rows, T);
- the feed-forward in blocks of ``FFN_ROWS`` rows (the dense layer's three
  (T, 11,264) float32 products would be 3.5 GB beside 8.5 GB of leaves);
- every context over ``SHORT`` positions is padded to a multiple of ``LONG``,
  so that a run's sample compiles the layers ONCE (causal: what lies behind a
  position does not reach it);
- ``forward`` returns the logits as ``Logits``: the final hidden states, with
  the final norm and the head applied to the rows that are ASKED for
  (``logits[0, a:b]``), in ``reference/xing4.head_logits``'s slices of the
  vocabulary. ``forward_logits`` is the whole array, for tests at test sizes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import xing4 as X
from .common import F32, HI

QUERY_ROWS = 256
FFN_ROWS = 3200
SHORT, SHORT_PAD, LONG = 2048, 256, 25600


def _whole(cfg) -> dict:
    """The file's keys with the mechanisms this model lacks switched off by
    the keys ``reference/xing4.py`` reads."""
    return {"hc_mult": 1, **cfg}


def attention(cfg, w, u, mode):
    """Causal latent attention of one sequence, expanded form, in blocks of
    query rows; u (T, d)."""
    T, H, eps = u.shape[0], cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], cfg["kv_lora_rank"])
    inv, amp, scale = X.yarn(cfg)
    q = X.mm(u, w["attn.q.w"], mode).reshape(T, H, nope + dr)
    q_nope, q_rope = q[..., :nope], X.rope(q[..., nope:], inv, amp)
    kv = X.mm(u, w["attn.kv_a.w"], mode)
    # what a cache holds, in the dtype it holds it
    store = w["attn.kv_a.w"].dtype
    ckv = X.rms(kv[:, :r], w["attn.kv_a_norm.g"], eps).astype(store).astype(F32)
    kr = X.rope(kv[:, r:], inv, amp).astype(store).astype(F32)
    kvb = X.mm(ckv, w["attn.kv_b.w"], mode).reshape(T, H, nope + dv)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    rows = min(QUERY_ROWS, T)
    kpos = jnp.arange(T)[None, None, :]

    def block(i):  # query rows i * rows .. : (rows, H, ...) each
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * rows, rows)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * rows, rows)
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope, precision=HI)
             + jnp.einsum("qhr,kr->hqk", qr, kr, precision=HI)) * scale
        sees = kpos <= (i * rows + jnp.arange(rows))[None, :, None]
        p = jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision=HI)

    o = jax.lax.map(block, jnp.arange(T // rows)).reshape(T, H * dv)
    return X.mm(o, w["attn.o.w"], mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def attention_sublayer(cfg, w, Xs, mode):
    return X.wrap(cfg, None, w["attn_norm.g"], Xs,
                  lambda u: attention(cfg, w, u, mode), mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def ffn_sublayer(cfg, w, Xs, mode):
    """``reference/xing4.ffn_sublayer`` over blocks of rows: ``(X', margin)``."""
    T = Xs.shape[0]
    rows = T if T <= 2 * FFN_ROWS or T % FFN_ROWS else FFN_ROWS
    out, margin = jax.lax.map(lambda xb: X.ffn_sublayer(cfg, w, xb, mode),
                              Xs.reshape((T // rows, rows) + Xs.shape[1:]))
    return out.reshape(Xs.shape), margin.reshape(T)


class Logits:
    """Logits (B, T, vocab) that are never whole: the hidden states behind
    the last layer (B rows of (T, d) float32) and each position's verdict;
    ``logits[b, a:b]`` applies the final norm and the head to those rows."""

    def __init__(self, hidden, keep, norm_g, head, eps, mode):
        self.hidden, self.keep = hidden, keep
        self.norm_g, self.head, self.eps, self.mode = norm_g, head, eps, mode
        self.shape = (len(hidden), hidden[0].shape[0], head.shape[1])

    def __getitem__(self, key):
        key = key if isinstance(key, tuple) else (key,)
        if len(key) > 2 or not isinstance(key[0], (int, np.integer)):
            raise IndexError("Logits: index one row of the batch, then a slice of "
                             f"positions (logits[0, a:b]), not {key!r}")
        rows = key[1] if len(key) == 2 else slice(None)
        if self.mode == "f32" and isinstance(rows, slice) and rows.start:
            # the harness asks for the served positions behind a prompt
            kept = np.asarray(self.keep[key[0]][rows])
            print(f"reference: a verdict at {int(kept.sum())} of {kept.size} served "
                  f"positions behind a prompt of {rows.start + 1}", flush=True)
        return X.head_logits(self.hidden[key[0]][rows], self.norm_g, self.head,
                             self.eps, self.mode, self.keep[key[0]][rows])[0]


def forward(cfg, weights, ids, mode="f32", min_margin=0.0):
    """``(Logits of (B, T, vocab), margin (B, T))`` of ``ids`` (B, T), one
    sequence at a time; ``margin`` and ``min_margin`` as in
    ``reference/xing4.forward``: a position's narrowest routing margin over
    the expert layers, and the least at which the reference gives a verdict
    (the logits of every other position are all zeros)."""
    cfg = X._Static({k: v for k, v in _whole(cfg).items()
                     if isinstance(v, (int, float, bool, str, type(None)))})
    ids = np.asarray(ids)
    head = weights["head.w"] if "head.w" in weights else weights["wte"].T
    T = ids.shape[1]
    pad = SHORT_PAD if T <= SHORT else LONG
    ids = np.pad(ids, ((0, 0), (0, -T % min(pad, cfg["max_position_embeddings"]))))
    hidden, keeps, margins = [], [], []
    for row in ids:
        Xs = weights["wte"][jnp.asarray(row)].astype(F32)[:, None, :]
        narrowest = jnp.full((len(row),), jnp.inf, F32)
        for i in range(cfg["num_hidden_layers"]):
            p = f"h{i}."
            w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
            part = lambda *heads: {k: v for k, v in w.items() if k.startswith(heads)}
            Xs = attention_sublayer(cfg, part("attn"), Xs, mode)
            Xs, margin = ffn_sublayer(cfg, part("ffn", "mlp"), Xs, mode)
            narrowest = jnp.minimum(narrowest, margin)
        hidden.append(Xs[:T, 0])
        keeps.append((narrowest[:T] >= min_margin).astype(F32))
        margins.append(narrowest[None, :T])
    return (Logits(hidden, keeps, weights["norm.g"], head, cfg["rms_norm_eps"], mode),
            jnp.concatenate(margins))


def forward_logits(cfg, weights, ids, mode="f32"):
    """Logits (B, T, vocab) float32 of ``ids`` (B, T), WHOLE: a verdict at
    every position. For tests at test sizes."""
    logits = forward(cfg, weights, ids, mode)[0]
    return jnp.stack([logits[b] for b in range(logits.shape[0])])
