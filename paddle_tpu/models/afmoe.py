"""Decoders of the AFMoE lineage (arcee-ai's ``afmoe``: Trinity-Mini, -Nano):
window layers WITH rotary positions beside full layers WITHOUT any, a sigmoid
gate on the attention output, norms before AND after both sub-layers, an
embedding scaled by ``sqrt(hidden)``, and routed experts with a shared one
behind a few leading dense layers.

``x_0 = E[ids] * sqrt(d)`` (``mup_enabled``). Every layer is

    a = RMSNorm(x; g_in);  [q | k | v | z] = a W_qkvg      (H, G, G, H heads of D)
    q, k <- RMSNorm over D (gains g_q, g_k)
    layer_types[i] == "sliding_attention": q, k <- RoPE(q, k) (theta, no
      scaling) and a query at p sees p' with 0 <= p - p' < sliding_window;
    "full_attention": NO rotation, every p' <= p
    o = softmax(q k^T / sqrt(D)) v, grouped (a K/V head serves H / G query
      heads);  o <- o * sigmoid(z);  x <- x + RMSNorm(o W_o; g_post_attn)
    b = RMSNorm(x; g_pre_mlp);  x <- x + RMSNorm(F(b); g_post_mlp)

``F`` of the first ``num_dense_layers`` layers is a dense gated MLP; of the
others ``Shared(b) + sum_k g_k Expert_k(b)``, the router and the expert product
of ``models/mla_moe.py`` (``route``, ``moe_ffn``, ``ops/kernels/moe_experts``:
sigmoid scores in float32 over ALL ``num_experts``, the ``num_experts_per_tok``
largest of ``score + expert_bias``, gates from the scores alone, renormalised
and scaled; no capacity), which this config is read by through the attribute
names that module uses. ``held_experts`` says which of the routed experts
live HERE (one chip's share of a layer under expert parallelism): the router
keeps its width, and the layer gives the held experts' part of the result
plus the shared expert. ``logits = RMSNorm(x; g_f) W_head``, untied.

What a layer caches is K (after its norm and, in a window layer, its
rotation) and V, ``(G, D)`` a token: a full layer by block table, a window
layer in a RING of ``sliding_window`` entries a row (position ``p`` at entry
``p % W``: the keys are rotated BEFORE they are cached, so an entry's place
says nothing). A prompt's attention is never a ``(T, T)`` product
(:func:`prompt_attention`): blocks of query rows against the band of keys a
window layer sees, or every key up to the block's end; on the chip the
forward kernel ``ops/kernels/window_flash``.

The layers behind the dense ones repeat a short pattern (``period``) and are
held STACKED over its repetitions, as ``models/lfm2_moe.py`` holds its own;
the programs ``lax.scan`` over the repetitions. The layer is written once
(``layer``): ``models/generation.py`` gives it the two reads of the caches,
for whole prompts and for one token a row.

Served only: no training step, no dense ``generate()`` loop.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..core.tensor import Tensor
from . import mla_moe as M
from .lfm2_moe import rope_freqs
from .periodic_stack import PeriodicLayers, layer_trees, scan_stack
from .phi4flash import window_ring

F32 = jnp.float32
KINDS = ("sliding_attention", "full_attention")
# a masked score: far below any real one, and finite (a padded query that
# sees no key still has a finite softmax, and a NaN never reaches a cache)
_MASK = -0.7 * float(np.finfo(np.float32).max)


@dataclass
class AfmoeConfig(PeriodicLayers):
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 8
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    layer_types: Tuple[str, ...] = ("sliding_attention", "sliding_attention",
                                    "sliding_attention", "full_attention") * 2
    sliding_window: int = 2048
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    held_experts: Optional[Tuple[int, ...]] = None
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    @classmethod
    def from_dict(cls, d: dict) -> "AfmoeConfig":
        """A published ``config.json`` (or a benchmark configuration): the
        keys this class has are taken, the others say nothing of the shape."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def __post_init__(self):
        refuse = lambda what: NotImplementedError(f"Afmoe: {what}")
        self.layer_types = tuple(self.layer_types)
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)
        if self.rope_scaling:
            raise refuse(f"rope_scaling {self.rope_scaling!r}; only the plain rotation")
        if self.n_group != 1 or self.topk_group != 1:
            raise refuse(f"group-limited routing (n_group {self.n_group}, topk_group "
                         f"{self.topk_group}); the router chooses among all experts")
        if self.score_func != "sigmoid":
            raise refuse(f"score_func {self.score_func!r}; the router implemented is "
                         "the sigmoid one")
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - set(KINDS):
            raise refuse(f"layer_types {self.layer_types!r}: one of {KINDS} for "
                         f"each of the {self.num_hidden_layers} layers")
        if self.tie_word_embeddings or self.num_shared_experts not in (0, 1):
            raise refuse("a tied head / more than one shared expert; the "
                         "published models have neither")
        H, G = self.num_attention_heads, self.num_key_value_heads
        if H % G or self.head_dim % 2 or self.sliding_window < 1:
            raise refuse(f"{H} query heads on {G} key/value heads of {self.head_dim}, "
                         f"sliding_window {self.sliding_window}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise refuse(f"num_dense_layers {self.num_dense_layers}")
        if set(self.experts_held) - set(range(self.num_experts)):
            raise refuse(f"held_experts {self.held_experts!r} of {self.num_experts}")

    # -- what models/mla_moe.py's router and expert product read of a config --
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def experts_held(self) -> Tuple[int, ...]:
        return (self.held_experts if self.held_experts is not None
                else tuple(range(self.num_experts)))

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    # -- sizes ---------------------------------------------------------------
    @property
    def kv_row(self) -> tuple:
        """What a cache holds of a token in one layer, for K and for V."""
        return (self.num_key_value_heads, self.head_dim)


# -- a prompt's attention: never a (T, T) product ------------------------------------

def band_tokens(lens, window=None) -> int:
    """Keys inside the band, summed over the real query rows of prompts of
    true lengths ``lens``: ``min(p + 1, window)`` for the query at ``p`` (no
    ``window``: ``p + 1``). What one layer's prompt attention must score."""
    total = 0
    for n in (int(x) for x in lens):
        w = n if window is None else min(n, int(window))
        total += w * (w + 1) // 2 + (n - w) * w
    return total


def prompt_attention_plain(q, k, v, window=None, block=512):
    """Causal grouped attention of whole prompts in blocks of query rows:
    ``q`` (B, T, H, D) against ``k`` / ``v`` (B, T, G, D), a K/V head serving
    ``H / G`` query heads and never repeated; ``window``: a query at ``p``
    sees ``p'`` with ``0 <= p - p' < window``. ``lax.scan`` over the blocks;
    a window layer takes the band of keys its block can see by
    ``dynamic_slice`` (``window - 1 + block`` of them), a full layer every
    key, masked; float32 scores of ONE block at a time, never (T, T). A
    padded query sees the (finite) keys before it like any other."""
    B, T, H, D = q.shape
    G = k.shape[2]
    bq = min(int(block), T)
    Tp = -(-T // bq) * bq
    if Tp != T:
        pad = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        q, k, v = pad(q), pad(k), pad(v)
    band = Tp if window is None else min(Tp, int(window) - 1 + bq)
    scale = D ** -0.5

    def rows(_, i):
        qs = i * bq
        ks = jnp.clip(qs + bq - band, 0, Tp - band)  # the band ends with the block
        qb = lax.dynamic_slice_in_dim(q, qs, bq, axis=1).reshape(B, bq, G, H // G, D)
        kb = lax.dynamic_slice_in_dim(k, ks, band, axis=1)
        vb = lax.dynamic_slice_in_dim(v, ks, band, axis=1)
        qpos = (qs + jnp.arange(bq))[:, None]
        kpos = (ks + jnp.arange(band))[None, :]
        sees = kpos <= qpos
        if window is not None:
            sees &= qpos - kpos < window
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb, preferred_element_type=F32) * scale
        p = jax.nn.softmax(jnp.where(sees, s, _MASK), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vb.dtype), vb)
        return None, o.reshape(B, bq, H, D)

    _, o = lax.scan(rows, None, jnp.arange(Tp // bq))
    return jnp.moveaxis(o, 0, 1).reshape(B, Tp, H, D)[:, :T]


def prompt_attention(q, k, v, lens, window, kernels):
    """``o`` (B, T, H, D) of whole prompts of true lengths ``lens``: the
    forward kernel where the program takes its kernels and the kernel takes
    the shape, else the plain blocked form. Rows past ``lens`` are padding:
    what comes back for them is finite and never read."""
    with jax.named_scope("attention"):
        if kernels:
            from ..ops.kernels.window_flash import window_flash, window_flash_takes

            if window_flash_takes(q.shape[1], q.shape[3], q.dtype):
                B, T, H, D = q.shape
                o = window_flash(q.reshape(B, T, H * D), k.reshape(B, T, -1),
                                 v.reshape(B, T, -1), lens, heads=H, window=window)
                return o.reshape(B, T, H, D)
        return prompt_attention_plain(q, k, v, window)


# -- the layer equations -----------------------------------------------------------

def attention(cfg: AfmoeConfig, freqs, w, kind, u, pos, attend):
    """``u`` (B, T, d), normed, at positions ``pos`` (B, T) through the
    attention operator of a layer of ``kind``. ``attend(q (B, T, H, D), k, v
    (B, T, G, D)) -> o (B, T, H, D)`` reads the context: the prompt itself,
    or the cache with the fresh row in it."""
    B, T = u.shape[:2]
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attention"):
        qkvg = u @ w["qkvg"]
        q = qkvg[..., :H * D].reshape(B, T, H, D)
        k = qkvg[..., H * D:(H + G) * D].reshape(B, T, G, D)
        v = qkvg[..., (H + G) * D:(H + 2 * G) * D].reshape(B, T, G, D)
        z = qkvg[..., (H + 2 * G) * D:]
        q = M.rms(q, w["q_norm"], cfg.rms_norm_eps)
        k = M.rms(k, w["k_norm"], cfg.rms_norm_eps)
        if kind == "sliding_attention":  # a full layer has no positions at all
            q = M.rope(q, pos[..., None], freqs, 1.0)
            k = M.rope(k, pos[..., None], freqs, 1.0)
        o = attend(q, k, v).reshape(B, T, H * D)
        o = (o.astype(F32) * jax.nn.sigmoid(z.astype(F32))).astype(u.dtype)
        return o @ w["o"]


def layer(cfg: AfmoeConfig, freqs, w, kind, x, pos, live, attend, kernels):
    """One layer of ``kind`` over ``x`` (B, T, d); ``live`` (B, T) marks the
    real tokens (the others choose no expert). Returns ``(x, counts (held
    experts,) or None)``: the live tokens each expert HELD HERE took."""
    eps = cfg.rms_norm_eps
    u = M.rms(x, w["in_norm"], eps)
    x = x + M.rms(attention(cfg, freqs, w, kind, u, pos, attend), w["post_attn_norm"], eps)
    u = M.rms(x, w["pre_mlp_norm"], eps)
    if "router" not in w:
        with jax.named_scope("mlp"):
            y, counts = M.gated_mlp(u, w["gate"], w["up"], w["down"]), None
    else:
        with jax.named_scope("experts"):
            y, counts = M.moe_ffn(cfg, w, u.reshape(-1, u.shape[-1]), live.reshape(-1),
                                  kernels)
        y, counts = y.reshape(u.shape), counts[jnp.asarray(cfg.experts_held)]
    return x + M.rms(y, w["post_mlp_norm"], eps), counts


def stack(cfg: AfmoeConfig, params, x, pos, live, pools, reads, kernels):
    """Every layer over ``x`` (B, T, d) at positions ``pos`` (B, T).
    ``reads[kind](pools, i, q, k, v) -> (pools, o)`` is the program's read of
    the context for a layer of ``kind``, told which layer OF ITS KIND it
    serves (``i``, a traced scalar inside the scan); it may write what the
    layer caches into ``pools``, which the scan carries. Lead (the dense
    layers) and tail (a last, partial period) are unrolled, the whole periods
    scanned. Returns ``(x, pools, counts (expert layers, held experts) or
    None)``."""
    freqs = rope_freqs(cfg)
    return scan_stack(
        cfg, params, x, pools, lambda kind, w, x, read: layer(
            cfg, freqs, w, kind, x, pos, live, read(reads[kind]), kernels))


def prompt_reads(cfg: AfmoeConfig, lens, kernels):
    """``reads`` of :func:`stack` over whole prompts of true lengths ``lens``:
    nothing is read from a cache, and what each layer is to cache is STAGED
    in ``pools`` = ``prompt_staging``'s four arrays, a layer of its kind a
    row: a full layer's K and V rows, a window layer's ring (its last
    ``sliding_window`` positions under ``lens``, ``window_ring``). The
    program writes each into its pool by ONE scatter afterwards."""
    def full(pools, i, q, k, v):
        ks, vs, wks, wvs = pools
        o = prompt_attention(q, k, v, lens, None, kernels)
        return (ks.at[i].set(k), vs.at[i].set(v), wks, wvs), o

    def sliding(pools, i, q, k, v):
        ks, vs, wks, wvs = pools
        o = prompt_attention(q, k, v, lens, cfg.sliding_window, kernels)
        return (ks, vs, wks.at[i].set(window_ring(cfg, k, lens)),
                wvs.at[i].set(window_ring(cfg, v, lens))), o

    return {"full_attention": full, "sliding_attention": sliding}


def prompt_staging(cfg: AfmoeConfig, B, T, dtype):
    """Where :func:`prompt_reads` puts what a prompt's layers cache: ``(K
    rows, V rows (full layers, B, T, G, D), K rings, V rings (window layers,
    B, sliding_window, G, D))``."""
    kv = jnp.zeros((cfg.layer_types.count("full_attention"), B, T) + cfg.kv_row, dtype)
    ring = jnp.zeros((cfg.layer_types.count("sliding_attention"), B,
                      cfg.sliding_window) + cfg.kv_row, dtype)
    return kv, kv, ring, ring


def embed(cfg: AfmoeConfig, params, ids):
    x = params["wte"][ids]
    if cfg.mup_enabled:
        x = (x.astype(F32) * math.sqrt(cfg.hidden_size)).astype(x.dtype)
    return x


# -- the model ----------------------------------------------------------------------

def _layer_leaves(cfg: AfmoeConfig, experts: bool):
    d, H, G, D = (cfg.hidden_size, cfg.num_attention_heads,
                  cfg.num_key_value_heads, cfg.head_dim)
    out = [(f"{n}.weight", (d,), "gain") for n in
           ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
            "post_mlp_layernorm")]
    out += [("self_attn.qkvg.weight", (d, 2 * (H + G) * D), "normal"),
            ("self_attn.q_norm.weight", (D,), "gain"),
            ("self_attn.k_norm.weight", (D,), "gain"),
            ("self_attn.o_proj.weight", (H * D, d), "normal")]
    if not experts:
        F = cfg.intermediate_size
        return out + [("mlp.gate.weight", (d, F), "normal"),
                      ("mlp.up.weight", (d, F), "normal"),
                      ("mlp.down.weight", (F, d), "normal")]
    E, held, f = cfg.num_experts, len(cfg.experts_held), cfg.moe_intermediate_size
    out += [("mlp.router.weight", (d, E), "normal"),
            ("mlp.router.expert_bias", (E,), "normal"),
            ("mlp.experts.gate", (held, d, f), "normal"),
            ("mlp.experts.up", (held, d, f), "normal"),
            ("mlp.experts.down", (held, f, d), "normal")]
    if cfg.num_shared_experts:
        out += [("mlp.shared.gate.weight", (d, f), "normal"),
                ("mlp.shared.up.weight", (d, f), "normal"),
                ("mlp.shared.down.weight", (f, d), "normal")]
    return out


def _leaf_kinds(cfg: AfmoeConfig):
    """``[(state_dict key, shape, kind)]``: every parameter, in order;
    matrices (in, out), the four projections of a layer ONE leaf ``[q | k | v
    | gate]``, the HELD experts stacked (held, in, out).
    ``model.layers.<i>.*`` are the unrolled layers (the dense ones and a last
    partial period); ``model.body.<j>.*`` position ``j`` of the period, ONE
    leaf over its ``periods`` repetitions, as the programs scan them."""
    out = [("model.embed_tokens.weight", (cfg.vocab_size, cfg.hidden_size), "normal")]
    body = range(cfg.num_dense_layers, cfg.tail_start)
    for i in range(cfg.num_hidden_layers):
        if i not in body:
            out += [(f"model.layers.{i}.{name}", shape, k)
                    for name, shape, k in _layer_leaves(cfg, cfg.is_expert_layer(i))]
    for j in range(len(cfg.period) if cfg.periods else 0):
        out += [(f"model.body.{j}.{name}", (cfg.periods,) + shape, k)
                for name, shape, k in _layer_leaves(cfg, True)]
    return out + [("model.norm.weight", (cfg.hidden_size,), "gain"),
                  ("lm_head.weight", (cfg.hidden_size, cfg.vocab_size), "normal")]


_SHORT = {"input_layernorm": "in_norm", "post_attention_layernorm": "post_attn_norm",
          "pre_mlp_layernorm": "pre_mlp_norm", "post_mlp_layernorm": "post_mlp_norm",
          "self_attn.qkvg": "qkvg", "self_attn.q_norm": "q_norm",
          "self_attn.k_norm": "k_norm", "self_attn.o_proj": "o",
          "mlp.gate": "gate", "mlp.up": "up", "mlp.down": "down",
          "mlp.router": "router", "mlp.router.expert_bias": "e_bias",
          "mlp.experts.gate": "experts_gate", "mlp.experts.up": "experts_up",
          "mlp.experts.down": "experts_down", "mlp.shared.gate": "shared_gate",
          "mlp.shared.up": "shared_up", "mlp.shared.down": "shared_down"}
_EXPERTS = ("experts_gate", "experts_up", "experts_down")


def params_tree(cfg: AfmoeConfig, sd):
    """The weight tree the layer functions take, from ``{state_dict key:
    array}`` (arrays or their shapes): ``lead`` / ``tail`` a dict a layer,
    ``body`` a dict a position of the period with the repetitions stacked,
    which the scan slices, and beside it ``body_experts``, the experts' stacks
    of the same positions, which it does not."""
    return {"wte": sd["model.embed_tokens.weight"], "norm": sd["model.norm.weight"],
            "head_w": sd["lm_head.weight"],
            **layer_trees(cfg, _leaf_kinds(cfg), _SHORT.__getitem__, _EXPERTS, sd)}


class AfmoeForCausalLM(nn.Layer):
    """The decoder as a tree of parameters (``state_dict`` keys as
    ``parameter_specs`` lists them). ``weights``, a ``{key: array}`` of every
    parameter, is held as given, without a second copy ever made on the
    device."""

    def __init__(self, config: AfmoeConfig, weights: Optional[dict] = None):
        super().__init__()
        self.config = config
        M.hold_parameters(self, _leaf_kinds(config), weights, config.initializer_range)

    @staticmethod
    def parameter_specs(config: AfmoeConfig):
        return _leaf_kinds(config)

    def forward(self, input_ids):
        """Logits (B, T, vocab) of whole prompts: the prefill path, no cache."""
        ids = jnp.asarray(getattr(input_ids, "_data", input_ids), jnp.int32)
        _, arch, params, _ = self.decode_state()
        B, T = ids.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        x = arch["embed"](params, ids, None)
        x, _, _ = stack(self.config, params, x, pos, jnp.ones((B, T), bool),
                        prompt_staging(self.config, B, T, x.dtype),
                        prompt_reads(self.config, jnp.full((B,), T, jnp.int32), False),
                        False)
        return Tensor(arch["head"](params, x))

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``forward`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.afmoe_decode_state(self)

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "AfmoeForCausalLM.generate: the dense decode loop and beam "
            "search are not built for this arch; serve it through serving.Engine")
