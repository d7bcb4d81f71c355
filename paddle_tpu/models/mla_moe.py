"""Decoders of the latent-attention / routed-expert lineage (DeepSeek-V2/V3 and
their siblings), built from a config whose KEYS switch the mechanisms:

- latent attention (arXiv:2405.04434): keys and values are up-projections of
  one ``kv_lora_rank``-wide normed latent a token, beside a rotary key of
  ``qk_rope_head_dim`` shared by all heads; ``q_lora_rank`` (``None``: a
  direct query projection) gives the query its low-rank path; ``rope_scaling``
  of type ``yarn`` blends the rotary frequencies and scales the scores;
- routed experts (arXiv:2412.19437): sigmoid scores, the ``num_experts_per_tok``
  largest of ``score + e_bias``, gates renormalised and scaled by
  ``routed_scaling_factor``, ``n_shared_experts`` always-on experts beside
  them, the first ``first_k_dense_replace`` layers a dense gated MLP. No
  capacity: a token is never dropped. ``held_experts`` names the experts this
  chip holds (default all): the layer routes over all ``n_routed_experts``
  and computes the part of the result its own experts give;
- manifold-constrained hyper-connections (arXiv:2512.24880 on arXiv:2409.19606):
  ``hc_mult`` residual streams a token, each sub-layer reading a learned mix
  of them and writing back through a doubly stochastic matrix (Sinkhorn,
  ``hc_sinkhorn_iters``). ``hc_mult 1`` is the plain pre-norm residual.

The layer equations are the pure functions below; ONE set, which prefill,
a prefill call against the cache, decode and ``forward`` all call
(``models/generation.py`` builds the serving programs around
``decoder_layer``). Each of the four device operations has a Pallas kernel
that the layer takes where Mosaic compiles (``kernels``) and a plain
``jax.numpy`` form, its reference, everywhere else:
``ops/kernels/mla_paged_attention.py``, ``mla_prefill_attention.py``,
``moe_experts.py``, ``mhc_mix.py``.

Attention has three forms of one mathematics: EXPANDED over a whole short
prompt (``attend_expanded``: a (T, T) product), expanded over a CALL of
positions against the latent rows the cache holds and its own
(``expand_context`` + ``attend_call``: blocked, no (queries x context)
tensor), ABSORBED for one token a row (``absorb_queries``).

Served only so far: no training step, and the multi-token-prediction module
(``num_nextn_predict_layers``) is not held (its join with the residual
streams is unpublished).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .. import nn
from ..core.tensor import Parameter, Tensor

F32 = jnp.float32
HI = lax.Precision.HIGHEST
LANES = 128  # Mosaic's lane tile: a cached row is padded to a multiple


@dataclass
class MLAMoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 1024
    num_hidden_layers: int = 4
    num_attention_heads: int = 8
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # latent attention
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    # feed-forward: dense width, expert width, routing
    intermediate_size: int = 4096
    moe_intermediate_size: int = 512
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    num_experts_per_tok: int = 1
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    held_experts: Optional[Tuple[int, ...]] = None
    # residual streams
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    @classmethod
    def from_dict(cls, d: dict) -> "MLAMoEConfig":
        """A published ``config.json`` (or a benchmark configuration): the
        keys this class has are taken, the others say nothing of the shape."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def __post_init__(self):
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)
        if self.n_routed_experts and self.scoring_func != "sigmoid":
            raise NotImplementedError(
                f"MLAMoE: scoring_func {self.scoring_func!r}; the router "
                "implemented is the sigmoid one")
        if self.n_routed_experts and (self.n_group != 1 or self.topk_group != 1):
            raise NotImplementedError(
                f"MLAMoE: group-limited routing (n_group {self.n_group}, "
                f"topk_group {self.topk_group}) is not implemented")
        if self.rope_scaling and (self.rope_scaling.get("type")
                                  or self.rope_scaling.get("rope_type")) != "yarn":
            raise NotImplementedError(
                f"MLAMoE: rope_scaling {self.rope_scaling!r}; only yarn")

    @property
    def experts_held(self) -> Tuple[int, ...]:
        return (self.held_experts if self.held_experts is not None
                else tuple(range(self.n_routed_experts)))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a cached token NEEDS a layer: the normed latent and the
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """What a cached token OCCUPIES a layer: the latent row padded to
        whole 128-lane tiles (Mosaic copies nothing narrower out of a pool)."""
        return -(-self.latent_width // LANES) * LANES

    def is_expert_layer(self, i: int) -> bool:
        return bool(self.n_routed_experts) and i >= self.first_k_dense_replace


# -- YaRN ---------------------------------------------------------------------

def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg: MLAMoEConfig):
    """``(inv_freq (rope_dim / 2,), cos/sin scale, score scale)`` as
    ``deepseek_v3`` computes them: plain rotary frequencies, or YaRN's blend
    of ``f`` and ``f / factor`` by the linear ramp between the correction
    dims of ``beta_fast`` / ``beta_slow``; the softmax scale carries
    ``mscale(factor, mscale_all_dim) ** 2``."""
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = cfg.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if not rs:
        return freq, 1.0, scale
    factor, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rs.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = freq / factor * ramp + freq * (1.0 - ramp)
    m_all = _yarn_mscale(factor, float(rs.get("mscale_all_dim", 0) or 0))
    amp = _yarn_mscale(factor, float(rs.get("mscale", 1))) / m_all
    return inv, amp, scale * m_all * m_all


def rope(x, pos, inv_freq, amp):
    """Rotary embedding, half-split pairs (``x[..., :D/2]`` with
    ``x[..., D/2:]``), at positions ``pos`` (broadcast against ``x``'s
    leading dims), computed in float32 and returned in ``x``'s dtype."""
    ang = pos.astype(F32)[..., None] * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def dot_f32(x, w):
    """``x @ w`` for float32 ``x`` at float32 accuracy (the TPU's default
    precision would round both to bfloat16)."""
    return jnp.matmul(x, w.astype(F32), precision=HI)


def rms(x, g, eps):
    var = jnp.mean(jnp.square(x.astype(F32)), -1, keepdims=True)
    y = (x.astype(F32) * lax.rsqrt(var + eps)).astype(x.dtype)
    return y if g is None else y * g


# -- residual streams -----------------------------------------------------------

def sinkhorn_plain(z, n, iters, eps, clamp):
    """The three mixing maps from their pre-activations ``z`` (..., n (2 + n))
    float32: ``H_pre = sigmoid``, ``H_post = 2 sigmoid``, ``H_res`` = the
    clipped exponential made doubly stochastic by ``iters`` rounds of column
    then row normalisation. The plain form of ``ops/kernels/mhc_mix``."""
    pre = jax.nn.sigmoid(z[..., :n])
    post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    m = jnp.exp(jnp.clip(z[..., 2 * n:], clamp[0], clamp[1]))
    m = m.reshape(z.shape[:-1] + (n, n))
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return pre, post, m


def mhc_wrap(cfg: MLAMoEConfig, w, X, norm_g, fn, kernels=False):
    """One sub-layer ``fn`` inside its hyper-connection: ``X`` (..., n, d) ->
    (..., n, d). With ``hc_mult`` 1 there is no map to learn and this is
    ``x + fn(norm(x))``. The streams and the maps are float32 (a token's
    streams are 57 KB at the published widths, and every later layer's router
    reads them); the sub-layer gets its input in the weights' dtype.
    """
    n, d = cfg.hc_mult, X.shape[-1]
    if n == 1:
        u = X[..., 0, :].astype(norm_g.dtype)
        return X + fn(rms(u, norm_g, cfg.rms_norm_eps)).astype(X.dtype)[..., None, :]
    flat = X.reshape(X.shape[:-2] + (n * d,)).astype(F32)
    xb = flat * lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True) + cfg.hc_eps)
    z = dot_f32(xb, w["phi"])
    alpha = jnp.repeat(w["alpha"].astype(F32), np.array([n, n, n * n]),
                       total_repeat_length=n * (2 + n))
    z = z * alpha + w["bias"].astype(F32)
    clamp = (float(cfg.mhc_h_res_clamp_min), float(cfg.mhc_h_res_clamp_max))
    if kernels:
        from ..ops.kernels.mhc_mix import mhc_mix

        pre, post, res = mhc_mix(z, n, cfg.hc_sinkhorn_iters, cfg.hc_eps, clamp)
    else:
        pre, post, res = sinkhorn_plain(z, n, cfg.hc_sinkhorn_iters, cfg.hc_eps, clamp)
    # the mixes are written as sums of products, not as dots: a float32 dot
    # at the TPU's default precision would round the maps to bfloat16
    Xf = X.astype(F32)
    u = jnp.sum(pre[..., None] * Xf, axis=-2).astype(norm_g.dtype)
    y = fn(rms(u, norm_g, cfg.rms_norm_eps))
    out = jnp.sum(res[..., None] * Xf[..., None, :, :], axis=-2) \
        + post[..., None] * y.astype(F32)[..., None, :]
    return out.astype(X.dtype)


# -- latent attention -----------------------------------------------------------

def latent_project(cfg: MLAMoEConfig, w, u, pos, tables):
    """Queries and the cached row of each token: ``u`` (..., d) at positions
    ``pos`` (...). Returns ``q_nope`` (..., H, nope), ``q_rope`` (..., H,
    rope) after RoPE, and ``latent`` (..., cache_row) = the normed latent,
    the rotary key after RoPE, zeros up to the row's padded width."""
    inv, amp, _ = tables
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    if "q_a" in w:
        q = rms(u @ w["q_a"], w["q_a_norm"], cfg.rms_norm_eps) @ w["q_b"]
    else:
        q = u @ w["q"]
    q = q.reshape(u.shape[:-1] + (H, cfg.qk_head_dim))
    q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    q_rope = rope(q_rope, pos[..., None], inv, amp)
    kv = u @ w["kv_a"]
    ckv = rms(kv[..., :r], w["kv_a_norm"], cfg.rms_norm_eps)
    kr = rope(kv[..., r:], pos, inv, amp)
    pad = jnp.zeros(u.shape[:-1] + (cfg.cache_row - cfg.latent_width,), u.dtype)
    return q_nope, q_rope, jnp.concatenate([ckv, kr, pad], axis=-1)


def attend_expanded(cfg: MLAMoEConfig, w, q_nope, q_rope, latent, tables):
    """Causal attention of a whole prompt in the EXPANDED form: keys and
    values of every head are up-projected from the rows the cache will hold.
    (B, T, ...) -> (B, T, H * v)."""
    B, T, H = q_nope.shape[:3]
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = (latent[..., :r] @ w["kv_b"]).reshape(B, T, H, -1)
    k_nope, v = kv[..., :cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]
    kr = latent[..., r:r + dr]
    with jax.named_scope("attention"):
        s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                        preferred_element_type=F32)
             + jnp.einsum("bqhr,bkr->bhqk", q_rope, kr,
                          preferred_element_type=F32)) * tables[2]
        live = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)
    return o.reshape(B, T, H * cfg.v_head_dim)


# rows of context a turn of ``expand_context`` up-projects (whole blocks)
EXPAND_ROWS = 2048
# the layout ``mla_prefill_attention`` reads its context in: (B, S, H D),
# features minor
ROW_MAJOR = Layout(major_to_minor=(0, 1, 2))
# the float32 scores (H, T, T) a whole prompt's ``attend_expanded`` may hold a
# row; a longer prompt is served in calls (``whole_prompt_max``)
WHOLE_SCORES_BYTES = 2 ** 30


def whole_prompt_max(cfg: "MLAMoEConfig") -> int:
    """The longest prompt ``prompt_layer`` takes whole: the power of two whose
    (H, T, T) float32 scores still fit ``WHOLE_SCORES_BYTES`` (4,096 positions
    at 16 heads)."""
    t = math.isqrt(WHOLE_SCORES_BYTES // (4 * cfg.num_attention_heads))
    return 1 << (t.bit_length() - 1)


def expand_turn(block_size, max_blocks) -> int:
    """Blocks of a row's table a turn of ``expand_context`` gathers."""
    return min(max(EXPAND_ROWS // block_size, 1), max_blocks)


def context_scratch(cfg: MLAMoEConfig, B, block_size, max_blocks, dtype):
    """``(tables' padded width, (K, V))``: the expanded keys and values of a
    call's context, ``(B, S, H (nope + pad))`` and ``(B, S, H v)`` over ``S``
    = the table's positions in whole turns of :func:`expand_context`. Made
    ONCE a program (zeros: what no layer writes has to be finite) and handed
    from layer to layer, each overwriting the rows up to its call's end."""
    turn = expand_turn(block_size, max_blocks)
    MB = -(-max_blocks // turn) * turn
    H, S = cfg.num_attention_heads, MB * block_size
    Dk = cfg.qk_nope_head_dim + cfg.cache_row - cfg.kv_lora_rank
    return MB, (jnp.zeros((B, S, H * Dk), dtype),
                jnp.zeros((B, S, H * cfg.v_head_dim), dtype))


def expand_rows(cfg: MLAMoEConfig, w, latent):
    """Keys and values of every head from cached rows ``latent`` (B, T,
    cache_row): ``(B, T, H (nope + pad))`` and ``(B, T, H v)``. A key is
    ``[k_nope | the row's lanes behind the latent]``: the rotary key all heads
    share and the row's zero padding, 256 lanes at the published widths, so
    that one dot with ``[q_nope | q_rope | 0]`` is the score. Both results are
    pinned ``ROW_MAJOR``: left to itself XLA:TPU lays the up-projection out
    positions minor, ``expand_context``'s loop carries the whole scratch so,
    and a transposing copy of the whole scratch stands before every kernel
    call (PR 49); pinned, one turn's rows are relaid in VMEM. No arithmetic,
    and nothing at all on the CPU."""
    B, T = latent.shape[:2]
    H, r, nope = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    up = (latent[..., :r] @ w["kv_b"]).reshape(B, T, H, -1)
    shared = jnp.broadcast_to(latent[:, :, None, r:], (B, T, H, latent.shape[-1] - r))
    k = jnp.concatenate([up[..., :nope], shared], axis=-1)
    return with_layout_constraint(
        (k.reshape(B, T, -1), up[..., nope:].reshape(B, T, -1)), ROW_MAJOR)


def expand_context(cfg: MLAMoEConfig, w, pool, layer, tables, ends, scratch):
    """Keys and values of every head for the positions ``0 .. ends - 1`` of
    each row, up-projected from the latent rows the pool holds (read by block
    table), into ``scratch`` (:func:`context_scratch`; ``tables`` padded to
    its width; ``expand_rows`` a turn). A ``fori_loop`` of ``EXPAND_ROWS``
    positions a turn up to the longest row's end: positions no row has reached
    cost nothing."""
    K, V = scratch
    B, S = K.shape[:2]
    BS = pool.shape[2]
    turn = expand_turn(BS, tables.shape[1])
    rows = turn * BS

    def body(c, kv):
        tb = lax.dynamic_slice_in_dim(tables, c * turn, turn, axis=1)
        k, v = expand_rows(cfg, w, pool[layer, tb].reshape(B, rows, pool.shape[-1]))
        return (lax.dynamic_update_slice_in_dim(kv[0], k, c * rows, axis=1),
                lax.dynamic_update_slice_in_dim(kv[1], v, c * rows, axis=1))

    turns = jnp.minimum(-(-jnp.max(ends) // rows), S // rows)
    return lax.fori_loop(0, turns, body, (K, V))


def attend_call_plain(q, k, v, starts, heads, scale, block=512):
    """Attention of a call's queries against a context in sequence order, in
    blocks of query rows: the plain form of
    ``ops/kernels/mla_prefill_attention`` (its docstring has the shapes).
    ``lax.scan`` over the blocks, every key masked by position; float32
    scores of ONE block at a time, never (queries x context) whole."""
    B, T, S = q.shape[0], q.shape[1], k.shape[1]
    bq = min(int(block), T)
    Tp = -(-T // bq) * bq
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0)))
    kh, vh = k.reshape(B, S, heads, -1), v.reshape(B, S, heads, -1)

    def rows(_, i):
        qb = lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1).reshape(B, bq, heads, -1)
        qpos = starts[:, None] + i * bq + jnp.arange(bq)[None]
        sees = jnp.arange(S)[None, None, :] <= qpos[:, :, None]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kh, preferred_element_type=F32) * scale
        p = jax.nn.softmax(jnp.where(sees[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vh.dtype), vh)
        return None, o.reshape(B, bq, -1)

    with jax.named_scope("attention"):
        _, o = lax.scan(rows, None, jnp.arange(Tp // bq))
    return jnp.moveaxis(o, 0, 1).reshape(B, Tp, -1)[:, :T]


def attend_call(cfg: MLAMoEConfig, q_nope, q_rope, context, starts, lens, scale,
                kernels=False):
    """Causal attention of a call (``q_nope`` / ``q_rope`` (B, T, H, ...) at
    positions ``starts + 0 .. T - 1``) in the EXPANDED form, against the
    ``context`` ``expand_context`` made: through the kernel where Mosaic takes
    the widths, else the plain form. (B, T, H v)."""
    B, T, H = q_nope.shape[:3]
    K, V = context
    Dk = K.shape[-1] // H
    pad = jnp.zeros((B, T, H, Dk - cfg.qk_head_dim), q_nope.dtype)
    q = jnp.concatenate([q_nope, q_rope, pad], axis=-1).reshape(B, T, H * Dk)
    if kernels:
        from ..ops.kernels import mla_prefill_attention as A

        if A.mla_prefill_attention_takes(K.shape[1], Dk, cfg.v_head_dim, q.dtype):
            with jax.named_scope("attention"):
                return A.mla_prefill_attention(q, K, V, starts, lens, heads=H,
                                               scale=scale)
    return attend_call_plain(q, K, V, starts, H, scale)


def absorb_queries(cfg: MLAMoEConfig, w, q_nope, q_rope):
    """The ABSORBED form's query of one token a row: ``q_nope`` through the
    key up-projection, beside ``q_rope``, zero over the row's padding, so
    that one dot with a cached row gives the score. (B, H, cache_row)."""
    qa = jnp.einsum("bhn,hnc->bhc", q_nope, w["uk"])
    pad = jnp.zeros(q_rope.shape[:-1] + (cfg.cache_row - cfg.latent_width,),
                    q_rope.dtype)
    return jnp.concatenate([qa, q_rope, pad], axis=-1)


def attend_absorbed_plain(cfg: MLAMoEConfig, q, pool, layer, block_tables, pos,
                          scale):
    """Decode attention over the paged latent pool by GATHER: the plain form
    of ``ops/kernels/mla_paged_attention`` (the row's table whole, positions
    past ``pos`` masked). ``q`` (B, H, cache_row) -> (B, H, kv_lora_rank)."""
    B = q.shape[0]
    ctx = pool[layer, block_tables].reshape(B, -1, pool.shape[-1])
    with jax.named_scope("attention"):
        s = jnp.einsum("bhc,btc->bht", q, ctx, preferred_element_type=F32) * scale
        live = jnp.arange(ctx.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(live[:, None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("bht,btc->bhc", p.astype(ctx.dtype),
                          ctx[..., :cfg.kv_lora_rank])


# -- feed-forward -----------------------------------------------------------------

def gated_mlp(x, gate, up, down):
    """``(silu(x Wg) * x Wu) Wd``, the activation in float32 (as the expert
    kernel has it), its product rounded once for the last matmul."""
    g = jnp.dot(x, gate, preferred_element_type=F32)
    u = jnp.dot(x, up, preferred_element_type=F32)
    return (jax.nn.silu(g) * u).astype(x.dtype) @ down


def route(cfg: MLAMoEConfig, w, x, live):
    """``(choice (N, k) int32, gates (N, k) float32, counts (E,) int32)`` over
    ALL ``n_routed_experts``: sigmoid scores in float32, the k largest of
    ``score + e_bias``, gates from the scores alone. A token that is not
    ``live`` (padding) chooses no expert (``choice = E``, gate 0) and is
    counted nowhere."""
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    sc = jax.nn.sigmoid(dot_f32(x.astype(F32), w["router"]))
    _, choice = lax.top_k(sc + w["e_bias"].astype(F32), k)
    g = jnp.take_along_axis(sc, choice, axis=-1)
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = g * float(cfg.routed_scaling_factor)
    choice = jnp.where(live[:, None], choice.astype(jnp.int32), E)
    g = jnp.where(live[:, None], g, 0.0)
    counts = jnp.zeros((E + 1,), jnp.int32).at[choice.reshape(-1)].add(1)[:E]
    return choice, g, counts


def experts_plain(x, slot, gates, gate_w, up_w, down_w):
    """Routed experts, dense and masked: every held expert over every token,
    kept where the token chose it. The plain form of
    ``ops/kernels/moe_experts``. ``slot`` (N, k): index into the held experts,
    or their count for "none held"."""
    n_held = gate_w.shape[0]
    combine = jnp.sum(gates[..., None] * (slot[..., None] == jnp.arange(n_held)),
                      axis=1)  # (N, held)

    def body(acc, e):
        y = gated_mlp(x, gate_w[e], up_w[e], down_w[e]).astype(F32)
        return acc + combine[:, e][:, None] * y, None

    acc, _ = lax.scan(body, jnp.zeros(x.shape, F32), jnp.arange(n_held))
    return acc.astype(x.dtype)


def moe_ffn(cfg: MLAMoEConfig, w, x, live, kernels=False):
    """Routed + shared experts of tokens ``x`` (N, d). Returns ``(y, counts)``:
    the part of the result the held experts give plus the shared expert, and
    how many live tokens chose each of ALL the experts."""
    choice, g, counts = route(cfg, w, x, live)
    held = cfg.experts_held
    if len(held) == cfg.n_routed_experts:
        slot = choice
    else:
        lookup = np.full((cfg.n_routed_experts + 1,), len(held), np.int32)
        lookup[list(held)] = np.arange(len(held))
        slot = jnp.asarray(lookup)[choice]
        g = jnp.where(slot < len(held), g, 0.0)
    stacks = [w["experts_gate"], w["experts_up"], w["experts_down"]]
    # ``experts_layer``: the stacks hold several layers' experts, (layers,
    # held, ...), and this scalar (traced inside a scan over layers) says
    # whose; the kernel reads them where they lie, the plain form slices
    layer = w.get("experts_layer")
    if kernels:
        from ..ops.kernels.moe_experts import moe_experts

        y = moe_experts(x, slot, g, *stacks, layer=layer)
    else:
        y = experts_plain(x, slot, g, *(stacks if layer is None
                                        else [s[layer] for s in stacks]))
    if "shared_gate" in w:
        y = y + gated_mlp(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    return y, counts


def decoder_layer(cfg: MLAMoEConfig, w, X, attend, live, kernels=False):
    """One layer over the residual streams ``X`` (..., n, d): the attention
    sub-layer (``attend(u) -> (..., d)``: expanded over a prompt, absorbed
    over the paged cache; the caller's, so that its side effects on the cache
    are the caller's too) and the feed-forward, each inside its own
    hyper-connection. ``live`` (...) marks real tokens. Returns ``(X, counts
    or None)``."""
    X = mhc_wrap(cfg, w.get("hc_attn"), X, w["attn_norm"], attend, kernels)
    counts = []

    def ffn(u):
        with jax.named_scope("mlp"):
            if "router" not in w:
                return gated_mlp(u, w["gate"], w["up"], w["down"])
            y, c = moe_ffn(cfg, w, u.reshape(-1, u.shape[-1]), live.reshape(-1),
                           kernels)
            counts.append(c)
            return y.reshape(u.shape)

    X = mhc_wrap(cfg, w.get("hc_ffn"), X, w["ffn_norm"], ffn, kernels)
    return X, (counts[0] if counts else None)


def embed_streams(cfg: MLAMoEConfig, params, ids):
    x = params["wte"][ids].astype(F32)
    return jnp.repeat(x[..., None, :], cfg.hc_mult, axis=-2)


def final_hidden(cfg: MLAMoEConfig, params, X):
    """Row sum of the streams, then the final norm."""
    h = jnp.sum(X, axis=-2).astype(params["norm"].dtype)
    return rms(h, params["norm"], cfg.rms_norm_eps)


def prompt_layer(cfg, tables, w, X, live, kernels=False):
    """A layer over whole prompts ``X`` (B, T, n, d), attention expanded as
    one (T, T) product (prompts up to ``whole_prompt_max``: the engine serves
    longer ones in calls, through the arch's ``tail_layer``).
    Returns ``(X, latent rows (B, T, cache_row), counts)``."""
    T = X.shape[1]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), X.shape[:2])
    kept = []

    def attend(u):
        q_nope, q_rope, latent = latent_project(cfg, w, u, pos, tables)
        kept.append(latent)
        return attend_expanded(cfg, w, q_nope, q_rope, latent, tables) @ w["o"]

    X, counts = decoder_layer(cfg, w, X, attend, live, kernels)
    return X, kept[0], counts


# -- the model ----------------------------------------------------------------------

def _leaf_kinds(cfg: MLAMoEConfig):
    """``[(state_dict key, shape, kind)]``: every parameter of the model, in
    order. ``kind`` is ``normal`` (mean 0) or ``gain`` (mean 1)."""
    d, H, n = cfg.hidden_size, cfg.num_attention_heads, cfg.hc_mult
    r, f = cfg.kv_lora_rank, cfg.moe_intermediate_size
    out = [("model.embed_tokens.weight", (cfg.vocab_size, d), "normal")]
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}."
        for sub in ("attn", "ffn"):
            if n > 1:
                out += [(p + f"{sub}_hc.phi", (n * d, n * (2 + n)), "normal"),
                        (p + f"{sub}_hc.alpha", (3,), "gain"),
                        (p + f"{sub}_hc.bias", (n * (2 + n),), "normal")]
            out.append((p + f"{sub}_norm.weight", (d,), "gain"))
        if cfg.q_lora_rank:
            out += [(p + "attn.q_a.weight", (d, cfg.q_lora_rank), "normal"),
                    (p + "attn.q_a_norm.weight", (cfg.q_lora_rank,), "gain"),
                    (p + "attn.q_b.weight", (cfg.q_lora_rank, H * cfg.qk_head_dim), "normal")]
        else:
            out.append((p + "attn.q.weight", (d, H * cfg.qk_head_dim), "normal"))
        out += [(p + "attn.kv_a.weight", (d, cfg.latent_width), "normal"),
                (p + "attn.kv_a_norm.weight", (r,), "gain"),
                (p + "attn.kv_b.weight", (r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), "normal"),
                (p + "attn.o.weight", (H * cfg.v_head_dim, d), "normal")]
        if cfg.is_expert_layer(i):
            E, held = cfg.n_routed_experts, len(cfg.experts_held)
            out += [(p + "mlp.router.weight", (d, E), "normal"),
                    (p + "mlp.router.e_bias", (E,), "normal"),
                    (p + "mlp.experts.gate", (held, d, f), "normal"),
                    (p + "mlp.experts.up", (held, d, f), "normal"),
                    (p + "mlp.experts.down", (held, f, d), "normal")]
            if cfg.n_shared_experts:
                fs = f * cfg.n_shared_experts
                out += [(p + "mlp.shared.gate.weight", (d, fs), "normal"),
                        (p + "mlp.shared.up.weight", (d, fs), "normal"),
                        (p + "mlp.shared.down.weight", (fs, d), "normal")]
        else:
            fd = cfg.intermediate_size
            out += [(p + "mlp.gate.weight", (d, fd), "normal"),
                    (p + "mlp.up.weight", (d, fd), "normal"),
                    (p + "mlp.down.weight", (fd, d), "normal")]
    out.append(("model.norm.weight", (d,), "gain"))
    if not cfg.tie_word_embeddings:
        out.append(("lm_head.weight", (d, cfg.vocab_size), "normal"))
    return out


class _Leaves(nn.Layer):
    """A node of the parameter tree: children by name, parameters by name."""


def hold_parameters(model, specs, weights, initializer_range):
    """Give ``model`` the tree of parameters that ``specs`` (``[(state_dict
    key, shape, kind)]``) lists. ``weights``, a ``{key: array}`` of every one
    of them, is held as given (no second copy on the device); without it each
    leaf is drawn ``normal(0, initializer_range)``, a ``gain`` around 1."""
    who = type(model).__name__
    if weights is not None and set(weights) != {k for k, _, _ in specs}:
        odd = sorted(set(weights) ^ {k for k, _, _ in specs})
        raise ValueError(f"{who}: weights and parameters differ at {odd[:6]}")
    rng = np.random.default_rng(0)
    for key, shape, kind in specs:
        if weights is not None:
            data = jnp.asarray(weights[key], dtype=model._dtype)
            if tuple(data.shape) != tuple(shape):
                raise ValueError(f"{who}: {key} has shape "
                                 f"{tuple(data.shape)}, the config says {shape}")
        else:
            x = rng.standard_normal(shape, np.float32) * initializer_range
            data = jnp.asarray(x + (kind == "gain"), dtype=model._dtype)
        node, names = model, key.split(".")
        for name in names[:-1]:
            if name not in node._sub_layers:
                node.add_sublayer(name, _Leaves())
            node = node._sub_layers[name]
        node.add_parameter(names[-1], Parameter(data))


class MLAMoEForCausalLM(nn.Layer):
    """The decoder as a tree of parameters (``state_dict`` keys as
    ``parameter_specs`` lists them; matrices are (in, out), experts stacked
    (held, in, out)). ``weights``, a ``{key: array}`` of every parameter, is
    held as given, without a second copy ever made on the device: at the
    sizes this lineage has, a model that first initialises itself does not
    fit beside the weights it is then handed."""

    def __init__(self, config: MLAMoEConfig, weights: Optional[dict] = None):
        super().__init__()
        self.config = config
        hold_parameters(self, _leaf_kinds(config), weights, config.initializer_range)

    @staticmethod
    def parameter_specs(config: MLAMoEConfig):
        return _leaf_kinds(config)

    def forward(self, input_ids):
        """Logits (B, T, vocab) of whole prompts: the prefill path, no cache."""
        ids = jnp.asarray(getattr(input_ids, "_data", input_ids), jnp.int32)
        _, arch, params, _ = self.decode_state()
        X = arch["embed"](params, ids, None)
        live = jnp.ones(ids.shape, bool)
        for w in params["layers"]:
            X, _, _ = arch["prompt_layer"](w, X, live)
        return Tensor(arch["head"](params, X))

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``forward`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.mla_moe_decode_state(self)

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "MLAMoEForCausalLM.generate: the dense decode loop and beam search "
            "are not built for this arch; serve it through serving.Engine")
