"""The decoder-hybrid-decoder of Phi-4-mini-flash (SambaY, arXiv:2507.06607):
five kinds of layer in one model, of which only ONE caches a row a token.

With ``L`` layers (a multiple of 4), every layer ``x <- x + Mixer(LN(x))``
then ``x <- x + W_down (up * silu(gate))``, ``[gate | up] = W_up LN(x)``;
LayerNorms with gain and bias, a tied head, no positional encoding (the scans
carry order). The mixer of layer ``i``:

- ``i < L/2`` even, and ``i = L/2``: **Mamba-1** (arXiv:2312.00752), a causal
  depthwise convolution and a selective scan; its state is ``N x d_i`` float32
  and ``K - 1`` convolution inputs a row, whatever the context. Layer ``L/2``
  also hands its scan output ``m_t`` (before the gate) to the layers behind it;
- ``i < L/2`` odd: **differential attention** (arXiv:2410.05258) over the last
  ``sliding_window`` tokens;
- ``i = L/2 + 1``: the same attention over the whole context: the model's only
  cache that grows with it;
- ``i > L/2 + 1`` even: a **gated memory unit**, ``W_2 (m_t * silu(W_1 u))``:
  no state;
- ``i > L/2 + 1`` odd: **cross attention**: a query of its own against layer
  ``L/2 + 1``'s keys and values.

Differential attention pairs neighbouring heads: ``(softmax(q1 k1^T) - lambda
softmax(q2 k2^T)) [v1 | v2]``. Here it is ONE attention over heads twice as
wide: keys and values are the pairs side by side (``[k1 | k2]``, ``[v1 |
v2]``, ``2 h`` wide), a query is padded with zeros on the other half (``[q1 |
0]``, ``[0 | q2]``), so ``q . k`` is the score of its own half, and the two
results are subtracted afterwards. What reads the cache is then the grouped
attention every arch has, at a head width (128) the block-table kernel takes.

The layer equations are the functions below, ONE set: the serving programs of
``models/generation.py`` give them the read of the context (``attend``) and
the form of the recurrence (a whole prompt, or one token against the pooled
state). Layers of one kind are stacked leaf by leaf (``front``: the ``L/4``
[Mamba, window] pairs, ``back``: the ``L/4 - 1`` [GMU, cross] pairs) and the
programs scan over the pairs.

Served only: no training step, no dense ``generate()`` loop.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..core.tensor import Tensor
from .mla_moe import hold_parameters

F32 = jnp.float32


@dataclass
class PhiFlashConfig:
    vocab_size: int = 32000
    hidden_size: int = 256
    num_hidden_layers: int = 8
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    intermediate_size: int = 1024
    max_position_embeddings: int = 2048
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    initializer_range: float = 0.02
    # the Mamba class's own defaults in the published modelling code
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Union[int, str] = "auto"

    @classmethod
    def from_dict(cls, d: dict) -> "PhiFlashConfig":
        """A published ``config.json`` (or a benchmark configuration): the
        keys this class has are taken, the others say nothing of the shape."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def __post_init__(self):
        L, H, G = (self.num_hidden_layers, self.num_attention_heads,
                   self.num_key_value_heads)
        refuse = lambda what: NotImplementedError(f"PhiFlash: {what}")
        if self.mb_per_layer != 2:
            raise refuse(f"mb_per_layer {self.mb_per_layer}; the layout "
                         "implemented alternates a Mamba layer with one of "
                         "another kind (mb_per_layer 2)")
        if L < 4 or L % 4:
            raise refuse(f"num_hidden_layers {L}; the self-decoder and the "
                         "cross-decoder are each whole [Mamba, attention] / "
                         "[GMU, cross] pairs: a multiple of 4")
        if self.hidden_act != "silu":
            raise refuse(f"hidden_act {self.hidden_act!r}; the gated FFN "
                         "implemented is the silu one")
        if self.mlp_bias or self.lm_head_bias or not self.tie_word_embeddings:
            raise refuse("mlp_bias / lm_head_bias / an untied head; the "
                         "published model has none of them")
        if H % 2 or G % 2 or (H // 2) % (G // 2) or self.hidden_size % H:
            raise refuse(f"differential attention pairs neighbouring heads: "
                         f"{H} query and {G} key/value heads do not pair")
        if self.sliding_window < 1:
            raise refuse(f"sliding_window {self.sliding_window}")

    # -- sizes ---------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return (math.ceil(self.hidden_size / 16)
                if self.mamba_dt_rank == "auto" else int(self.mamba_dt_rank))

    @property
    def front_pairs(self) -> int:
        return self.num_hidden_layers // 4

    @property
    def back_pairs(self) -> int:
        return self.num_hidden_layers // 4 - 1

    @property
    def kv_pairs(self) -> int:
        """Key/value heads as the cache holds them: neighbouring pairs."""
        return self.num_key_value_heads // 2

    @property
    def kv_row(self) -> tuple:
        """What the cache holds of a token in an attention layer, for K and
        for V: the pairs side by side."""
        return (self.kv_pairs, 2 * self.head_dim)

    def layer_kind(self, i: int) -> str:
        """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``."""
        half = self.num_hidden_layers // 2
        if i <= half:
            return "mamba" if i % 2 == 0 else "window"
        if i == half + 1:
            return "full"
        return "gmu" if i % 2 == 0 else "cross"

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


# -- the layer equations ---------------------------------------------------------

def layer_norm(x, g, b, eps):
    x32 = x.astype(F32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mu).mean(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def ffn(cfg: PhiFlashConfig, w, x):
    """``x + W_down (up * silu(gate))`` of the layer's second norm."""
    with jax.named_scope("mlp"):
        u = layer_norm(x, w["ffn_norm_g"], w["ffn_norm_b"], cfg.layer_norm_eps)
        gate, up = jnp.split(u @ w["up"], 2, axis=-1)
        return x + (up * jax.nn.silu(gate)) @ w["down"]


def mamba_mixer(cfg: PhiFlashConfig, w, x, conv, recur):
    """``x`` (B, T, d) through a Mamba layer's mixer. ``conv(a) -> (a over
    the K taps (B, T, K, d_i), what it keeps)``: the convolution's inputs at
    each position, from the prompt itself or from the row's cached tail;
    ``recur(dt, c, Bm, Cm, A, D) -> (y (B, T, d_i) float32, what it keeps)``:
    the recurrence, over a prompt or one token against the pooled state.
    Returns ``(x, m, kept by conv, kept by recur)`` with ``m`` the scan's
    output before the gate."""
    N, R = cfg.mamba_d_state, cfg.dt_rank
    with jax.named_scope("mamba"):
        u = layer_norm(x, w["norm_g"], w["norm_b"], cfg.layer_norm_eps)
        a, z = jnp.split(u @ w["in_proj"], 2, axis=-1)
        taps, tail = conv(a)
        c = jax.nn.silu(jnp.einsum("btkc,kc->btc", taps.astype(F32),
                                   w["conv_w"].astype(F32))
                        + w["conv_b"].astype(F32)).astype(x.dtype)
        rbc = c @ w["x_proj"]
        r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
        dt = jax.nn.softplus((r @ w["dt_proj"]).astype(F32)
                             + w["dt_bias"].astype(F32))
        y, state = recur(dt, c.astype(F32), Bm.astype(F32), Cm.astype(F32),
                         -jnp.exp(w["A_log"].astype(F32)), w["D"].astype(F32))
        m = y.astype(x.dtype)
        return x + (m * jax.nn.silu(z)) @ w["out_proj"], m, tail, state


def pair_queries(cfg: PhiFlashConfig, q):
    """``q`` (B, T, H h) as the attention reads it: (B, T, H, 2 h), an even
    head ``[q | 0]`` and an odd one ``[0 | q]``."""
    B, T = q.shape[:2]
    H, h = cfg.num_attention_heads, cfg.head_dim
    q = q.reshape(B, T, H // 2, 2, h)
    zero = jnp.zeros_like(q[:, :, :, 0])
    return jnp.stack([jnp.concatenate([q[:, :, :, 0], zero], -1),
                      jnp.concatenate([zero, q[:, :, :, 1]], -1)],
                     axis=3).reshape(B, T, H, 2 * h)


def diff_attention_mixer(cfg: PhiFlashConfig, w, x, lam_init, attend):
    """``x`` (B, T, d) through a differential-attention mixer. A layer that
    has ``qkv`` makes its own keys and values, one that has ``q`` alone (cross
    attention) reads another's. ``attend(q (B, T, H, 2 h), k, v) -> o (B, T,
    H, 2 h)`` reads the context, ``k``/``v`` (B, T, G/2, 2 h) the layer's own
    fresh rows or None; every head's softmax is over scores ``q . k / sqrt
    h``. Returns ``(x, k, v)``."""
    B, T = x.shape[:2]
    H, G, h = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("diff_attention"):
        u = layer_norm(x, w["norm_g"], w["norm_b"], cfg.layer_norm_eps)
        if "qkv" in w:
            qkv = u @ w["qkv"]
            q = qkv[..., :H * h]
            k = qkv[..., H * h:(H + G) * h].reshape((B, T) + cfg.kv_row)
            v = qkv[..., (H + G) * h:].reshape((B, T) + cfg.kv_row)
        else:
            q, k, v = u @ w["q"], None, None
        o = attend(pair_queries(cfg, q), k, v).astype(F32)
        lam = (jnp.exp(jnp.sum(w["lambda_q1"].astype(F32) * w["lambda_k1"].astype(F32)))
               - jnp.exp(jnp.sum(w["lambda_q2"].astype(F32) * w["lambda_k2"].astype(F32)))
               + lam_init)
        o = o.reshape(B, T, H // 2, 2, 2 * h)
        o = o[:, :, :, 0] - lam * o[:, :, :, 1]
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg.layer_norm_eps)
        o = ((1.0 - lam_init) * o).astype(x.dtype) * w["subln_g"]
        return x + o.reshape(B, T, H * h) @ w["o"], k, v


def gmu_mixer(cfg: PhiFlashConfig, w, x, m):
    """``W_2 (m * silu(W_1 u))``: ``m`` (B, T, d_i) is layer ``L/2``'s scan
    output at the same positions."""
    with jax.named_scope("gmu"):
        u = layer_norm(x, w["norm_g"], w["norm_b"], cfg.layer_norm_eps)
        return x + (m * jax.nn.silu(u @ w["in_proj"])) @ w["out_proj"]


def attend_dense(cfg: PhiFlashConfig, q, k, v, live):
    """The plain read: ``q`` (B, T, H, 2 h) against ``k``/``v`` (B, Tk, G/2,
    2 h), ``live`` (B or 1, T, Tk) the keys each query sees. Grouped: a
    key/value pair serves the ``2 H / G`` queries of its group and is never
    repeated. Scores and softmax in float32."""
    B, T, H, D = q.shape
    P = k.shape[2]
    with jax.named_scope("attention"):
        qg = q.reshape(B, T, P, H // P, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                       preferred_element_type=F32) * (cfg.head_dim ** -0.5)
        p = jax.nn.softmax(jnp.where(live[:, None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v)
        return o.reshape(B, T, H, D)


# -- a whole prompt ------------------------------------------------------------------

def prompt_conv(K: int, lens):
    """``conv`` of :func:`mamba_mixer` over whole prompts, ``K`` taps: the
    taps of position ``t`` are the inputs at ``t - K + 1 .. t`` (zeros before
    the prompt), and what it keeps is the last ``K - 1`` inputs of each row's
    TRUE length ``lens``, not of the bucket."""

    def conv(a):
        T = a.shape[1]
        ap = jnp.pad(a, ((0, 0), (K - 1, 0), (0, 0)))
        taps = jnp.stack([ap[:, k:k + T] for k in range(K)], axis=2)
        # position p lies at index p + K - 1 of ``ap``
        idx = lens[:, None] + jnp.arange(K - 1)[None]
        return taps, jnp.take_along_axis(ap, idx[:, :, None], axis=1)

    return conv


def prompt_recur(live, kernels):
    """``recur`` of :func:`mamba_mixer` over whole prompts from the zero
    state. A padded position has its ``Delta`` set to 0, which leaves the
    state as it was: what comes back is the state at each row's true length."""
    from ..ops.kernels.selective_scan import selective_scan, selective_scan_plain

    def recur(dt, c, Bm, Cm, A, D):
        dt = jnp.where(live[:, :, None], dt, 0.0)
        with jax.named_scope("selective_scan"):
            return (selective_scan if kernels else selective_scan_plain)(
                dt, c, Bm, Cm, A, D)

    return recur


def window_ring(cfg: PhiFlashConfig, rows, lens):
    """The window cache of a prompt: ``rows`` (B, T, ...) -> (B, W, ...),
    ring entry ``r`` holding the last position ``p < lens`` with ``p % W ==
    r`` (position ``p`` lives at ``p % W`` for as long as it is inside the
    window; an entry no position has reached yet is never read)."""
    W = cfg.sliding_window
    last = lens[:, None] - 1
    p = last - (last - jnp.arange(W)[None]) % W
    idx = jnp.clip(p, 0, rows.shape[1] - 1)
    return jnp.take_along_axis(
        rows, idx.reshape(idx.shape + (1,) * (rows.ndim - 2)), axis=1)


def _scan_pairs(body, carry, stacked, first, step):
    """``lax.scan`` of ``body(carry, (w, i, layer)) -> carry`` over the pairs
    of a stack: ``i`` counts the pairs, ``layer`` is the first layer of each
    (``first + step * i``, as a float for ``lambda_init``)."""
    leaves = jax.tree_util.tree_leaves(stacked)
    if not leaves:  # four layers have no [GMU, cross] pair
        return carry
    n = leaves[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return lax.scan(lambda c, xs: (body(c, xs), None), carry,
                    (stacked, idx, first + step * idx))[0]


def _lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(F32))


def prompt_stack(cfg: PhiFlashConfig, params, x, pools, lens, live, kernels,
                 keep):
    """Every layer over whole prompts ``x`` (B, T, d), ``lens`` (B,) their
    true lengths, ``live`` (B, T) the real positions. ``keep(pools, kind, i,
    *rows) -> pools`` is handed what each caching layer keeps (``i`` counts
    the layers of its kind): ``"state", i, S (B, N, d_i), tail (B, K - 1,
    d_i)``; ``"window", i, k ring, v ring (B, W, G/2, 2 h)``; ``"paged", 0, k,
    v (B, T, G/2, 2 h)``. Returns ``(x, pools)``."""
    T = x.shape[1]
    t = jnp.arange(T)
    causal = (t[None, :] <= t[:, None])[None]
    window = causal & (t[:, None] - t[None, :] < cfg.sliding_window)[None]
    conv, recur = prompt_conv(cfg.mamba_d_conv, lens), prompt_recur(live, kernels)
    dense = lambda mask: lambda q, k, v: attend_dense(cfg, q, k, v, mask)

    def mamba(w, x, i, pools):
        x, m, tail, S = mamba_mixer(cfg, w, x, conv, recur)
        return ffn(cfg, w, x), m, keep(pools, "state", i, S, tail)

    def front(carry, xs):
        x, pools = carry
        w, i, layer = xs
        x, _, pools = mamba(w["mamba"], x, i, pools)
        x, k, v = diff_attention_mixer(cfg, w["attn"], x, _lambda_init(layer + 1),
                                       dense(window))
        pools = keep(pools, "window", i, window_ring(cfg, k, lens),
                     window_ring(cfg, v, lens))
        return ffn(cfg, w["attn"], x), pools

    half = cfg.num_hidden_layers // 2
    x, pools = _scan_pairs(front, (x, pools), params["front"], 0, 2)
    mid = params["mid"]
    x, m, pools = mamba(mid["mamba"], x, cfg.front_pairs, pools)
    x, k17, v17 = diff_attention_mixer(cfg, mid["attn"], x,
                                       cfg.lambda_init(half + 1), dense(causal))
    pools = keep(pools, "paged", 0, k17, v17)
    x = ffn(cfg, mid["attn"], x)

    def back(x, xs):
        w, _, layer = xs
        x = ffn(cfg, w["gmu"], gmu_mixer(cfg, w["gmu"], x, m))
        x, _, _ = diff_attention_mixer(
            cfg, w["cross"], x, _lambda_init(layer + 1),
            lambda q, k, v: attend_dense(cfg, q, k17, v17, causal))
        return ffn(cfg, w["cross"], x)

    return _scan_pairs(back, x, params["back"], half + 2, 2), pools


def decode_stack(cfg: PhiFlashConfig, params, x, pools, state_step, window_read,
                 paged_read):
    """Every layer over one fresh token a row, ``x`` (B, 1, d), against the
    pools. The three reads are the program's: ``state_step(pools, i, a) ->
    (pools, taps, recur)`` for scan layer ``i`` (the row's cached convolution
    tail with the fresh input ``a`` behind it, the tail shifted in the pool,
    and ``recur(pools, dt, c, Bm, Cm, A, D) -> (y, pools)``, the recurrence
    against the pooled state), ``window_read(pools, i, q, k, v) -> (pools,
    o)`` and ``paged_read(pools, q, k, v) -> (pools, o)`` (``k`` None: a
    cross layer, which writes nothing). Returns ``(x, pools)``."""
    def mamba(w, x, i, pools):
        box = {}

        def conv(a):
            box["pools"], taps, box["recur"] = state_step(pools, i, a)
            return taps, None

        x, m, _, pools = mamba_mixer(
            cfg, w, x, conv, lambda *ops: box["recur"](box["pools"], *ops))
        return ffn(cfg, w, x), m, pools

    def attn(w, x, lam_init, read, pools):
        box = {}

        def attend(q, k, v):
            box["pools"], o = read(pools, q, k, v)
            return o

        x, _, _ = diff_attention_mixer(cfg, w, x, lam_init, attend)
        return ffn(cfg, w, x), box["pools"]

    def front(carry, xs):
        x, pools = carry
        w, i, layer = xs
        x, _, pools = mamba(w["mamba"], x, i, pools)
        return attn(w["attn"], x, _lambda_init(layer + 1),
                    lambda p, q, k, v: window_read(p, i, q, k, v), pools)

    half = cfg.num_hidden_layers // 2
    x, pools = _scan_pairs(front, (x, pools), params["front"], 0, 2)
    mid = params["mid"]
    x, m, pools = mamba(mid["mamba"], x, cfg.front_pairs, pools)
    x, pools = attn(mid["attn"], x, cfg.lambda_init(half + 1), paged_read, pools)

    def back(carry, xs):
        x, pools = carry
        w, _, layer = xs
        x = ffn(cfg, w["gmu"], gmu_mixer(cfg, w["gmu"], x, m))
        return attn(w["cross"], x, _lambda_init(layer + 1), paged_read, pools)

    return _scan_pairs(back, (x, pools), params["back"], half + 2, 2)


# -- the model ----------------------------------------------------------------------

def _ffn_leaves(cfg):
    d, F = cfg.hidden_size, cfg.intermediate_size
    return [("ffn_norm.weight", (d,), "gain"), ("ffn_norm.bias", (d,), "normal"),
            ("up.weight", (d, 2 * F), "normal"), ("down.weight", (F, d), "normal")]


def _mixer_leaves(cfg: PhiFlashConfig, kind: str):
    """``[(name, shape, kind)]`` of one layer of ``kind``; matrices (in,
    out), the convolution (K, d_i) and ``A_log`` (N, d_i): channels last."""
    d, H, G, h = (cfg.hidden_size, cfg.num_attention_heads,
                  cfg.num_key_value_heads, cfg.head_dim)
    di, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank
    norm = [("norm.weight", (d,), "gain"), ("norm.bias", (d,), "normal")]
    lam = [(f"lambda_{n}", (h,), "normal") for n in ("q1", "k1", "q2", "k2")] \
        + [("subln.weight", (2 * h,), "gain")]
    mixer = {
        "mamba": [("in_proj.weight", (d, 2 * di), "normal"),
                  ("conv.weight", (K, di), "gain"), ("conv.bias", (di,), "normal"),
                  ("x_proj.weight", (di, R + 2 * N), "normal"),
                  ("dt_proj.weight", (R, di), "normal"),
                  ("dt_proj.bias", (di,), "normal"),
                  ("A_log", (N, di), "normal"), ("D", (di,), "gain"),
                  ("out_proj.weight", (di, d), "normal")],
        "attn": [("qkv.weight", (d, (H + 2 * G) * h), "normal"),
                 ("o.weight", (H * h, d), "normal")] + lam,
        "gmu": [("in_proj.weight", (d, di), "normal"),
                ("out_proj.weight", (di, d), "normal")],
        "cross": [("q.weight", (d, H * h), "normal"),
                  ("o.weight", (H * h, d), "normal")] + lam,
    }[kind]
    return norm + mixer + _ffn_leaves(cfg)


def _leaf_kinds(cfg: PhiFlashConfig):
    """``[(state_dict key, shape, kind)]``: every parameter, in order. The
    layers of a stack are ONE leaf a matrix, stacked over the stack's pairs
    (``model.front.*``: L/4, ``model.back.*``: L/4 - 1), as the programs
    scan them; ``model.mid.*`` are layers L/2 and L/2 + 1."""
    d = cfg.hidden_size
    out = [("model.embed_tokens.weight", (cfg.vocab_size, d), "normal")]
    for stack, n, kinds in (("front", cfg.front_pairs, ("mamba", "attn")),
                            ("mid", None, ("mamba", "attn")),
                            ("back", cfg.back_pairs, ("gmu", "cross"))):
        lead = () if n is None else (n,)
        for kind in kinds:
            out += [(f"model.{stack}.{kind}.{name}", lead + shape, k)
                    for name, shape, k in _mixer_leaves(cfg, kind)]
    return out + [("model.final_layernorm.weight", (d,), "gain"),
                  ("model.final_layernorm.bias", (d,), "normal")]


_SHORT = {"norm.weight": "norm_g", "norm.bias": "norm_b",
          "ffn_norm.weight": "ffn_norm_g", "ffn_norm.bias": "ffn_norm_b",
          "conv.weight": "conv_w", "conv.bias": "conv_b",
          "dt_proj.bias": "dt_bias", "subln.weight": "subln_g"}


def params_tree(cfg: PhiFlashConfig, sd):
    """The weight tree the layer functions take, from ``{state_dict key:
    array}`` (arrays or their shapes)."""
    tree = {"wte": sd["model.embed_tokens.weight"],
            "lnf_g": sd["model.final_layernorm.weight"],
            "lnf_b": sd["model.final_layernorm.bias"]}
    for key, _, _ in _leaf_kinds(cfg):
        parts = key.split(".")
        if parts[1] not in ("front", "mid", "back"):
            continue
        name = ".".join(parts[3:])
        short = _SHORT.get(name, name[:-len(".weight")] if name.endswith(".weight") else name)
        tree.setdefault(parts[1], {}).setdefault(parts[2], {})[short] = sd[key]
    tree.setdefault("back", {})
    return tree


class PhiFlashForCausalLM(nn.Layer):
    """The decoder as a tree of parameters (``state_dict`` keys as
    ``parameter_specs`` lists them). ``weights``, a ``{key: array}`` of every
    parameter, is held as given, without a second copy ever made on the
    device: the model fills half the chip."""

    def __init__(self, config: PhiFlashConfig, weights: Optional[dict] = None):
        super().__init__()
        self.config = config
        hold_parameters(self, _leaf_kinds(config), weights, config.initializer_range)

    @staticmethod
    def parameter_specs(config: PhiFlashConfig):
        return _leaf_kinds(config)

    def forward(self, input_ids):
        """Logits (B, T, vocab) of whole prompts: the prefill path, no cache."""
        ids = jnp.asarray(getattr(input_ids, "_data", input_ids), jnp.int32)
        _, arch, params, _ = self.decode_state()
        lens = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
        x, _ = prompt_stack(self.config, params, arch["embed"](params, ids, None),
                            (), lens, jnp.ones(ids.shape, bool), False,
                            lambda pools, *_: pools)
        return Tensor(arch["head"](params, x))

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``forward`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.phi4flash_decode_state(self)

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "PhiFlashForCausalLM.generate: the dense decode loop and beam "
            "search are not built for this arch; serve it through serving.Engine")
