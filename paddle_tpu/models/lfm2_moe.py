"""Decoders of the LFM2-MoE lineage (LiquidAI's ``lfm2_moe``): gated short
convolutions beside a few grouped-query attention layers, and routed experts
with no shared one behind a few leading dense layers.

Every layer is ``x <- x + Op(RMSNorm(x))`` then ``x <- x + FFN(RMSNorm(x))``;
RMS norms with a gain, no bias anywhere, a tied head. ``layer_types[i]`` says
what ``Op`` of layer ``i`` is:

- ``conv``: ``[B, C, u] = split3(n W_in)``, ``z = B * u``, a causal depthwise
  convolution of ``conv_L_cache`` taps over ``z`` (zeros before the first
  token), ``Op = (C * c) W_out``. What a row caches is the last
  ``conv_L_cache - 1`` values of ``z``: a fixed shape a row, whatever the
  context;
- ``full_attention``: grouped-query attention, ``q`` and ``k`` RMS-normed a
  head (gains of ``head_dim``) BEFORE the rotation, causal softmax over the
  whole context: the only cache that grows with it. Heads are 64 wide, half
  of Mosaic's 128-lane line, so the cache lays NEIGHBOURING key/value heads
  side by side, two a line (``kv_row``); a query is padded with zeros on the
  other head's half (``pair_queries``), so ``q . line`` is the score of its
  own head, and of what comes back it keeps its own half (``own_half``).
  What reads the cache is then the block-table kernel every arch has, at a
  width it takes.

``FFN`` of the first ``num_dense_layers`` layers is a dense gated MLP; of the
others the routed experts of ``models/mla_moe.py`` (``route``, ``moe_ffn``,
``ops/kernels/moe_experts``: sigmoid scores in float32, the
``num_experts_per_tok`` largest of ``score + expert_bias``, gates from the
scores alone, renormalised; no capacity), which this config is read by
through the attribute names that module uses.

The layers behind the dense ones repeat a short pattern (``period``): they
are held STACKED over its repetitions, position by position, and the programs
``lax.scan`` over the repetitions (``stack``), so a program holds one period
whatever the depth; what is left of a last, partial period (``tail``) and the
dense layers (``lead``) are unrolled. The layer is written once (``layer``):
``models/generation.py`` gives it the two reads of the caches, for whole
prompts and for one token a row.

Served only: no training step, no dense ``generate()`` loop.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..core.tensor import Tensor
from . import mla_moe as M
from .periodic_stack import PeriodicLayers, layer_trees, scan_stack
from .phi4flash import attend_dense, prompt_conv

F32 = jnp.float32
KINDS = ("conv", "full_attention")


@dataclass
class Lfm2MoeConfig(PeriodicLayers):
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 6
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv",
                                    "full_attention", "conv")
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    rope_theta: float = 1000000.0
    rope_parameters: Optional[dict] = None
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02

    @classmethod
    def from_dict(cls, d: dict) -> "Lfm2MoeConfig":
        """A published ``config.json`` (or a benchmark configuration): the
        keys this class has are taken, the others say nothing of the shape."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def __post_init__(self):
        refuse = lambda what: NotImplementedError(f"Lfm2Moe: {what}")
        self.layer_types = tuple(self.layer_types)
        if self.rope_parameters:
            kind = self.rope_parameters.get("rope_type", "default")
            if kind != "default":
                raise refuse(f"rope_parameters of type {kind!r}; only the plain rotation")
            self.rope_theta = float(self.rope_parameters.get("rope_theta", self.rope_theta))
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - set(KINDS):
            raise refuse(f"layer_types {self.layer_types!r}: one of {KINDS} for "
                         f"each of the {self.num_hidden_layers} layers")
        if self.conv_bias or not self.use_expert_bias or not self.tie_word_embeddings:
            raise refuse("conv_bias / a router without its bias / an untied head; "
                         "the published models have none of them")
        H, G = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % H or H % G or G % 2:
            raise refuse(f"{H} query heads on {G} key/value heads: the cache "
                         "lays neighbouring key/value heads two a line")
        if self.conv_L_cache < 2 or not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise refuse(f"conv_L_cache {self.conv_L_cache}, num_dense_layers "
                         f"{self.num_dense_layers}")

    # -- what models/mla_moe.py's router and expert product read of a config --
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def experts_held(self) -> Tuple[int, ...]:
        return tuple(range(self.num_experts))  # a chip holds every expert

    # -- sizes ---------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_row(self) -> tuple:
        """What the cache holds of a token in an attention layer, for K and
        for V: neighbouring heads side by side, two a line."""
        return (self.num_key_value_heads // 2, 2 * self.head_dim)


# -- the layer equations -----------------------------------------------------------

def rope_freqs(cfg: Lfm2MoeConfig):
    D = cfg.head_dim
    return 1.0 / float(cfg.rope_theta) ** (np.arange(0, D, 2, dtype=np.float64) / D)


def pair_keys(cfg: Lfm2MoeConfig, k):
    """``k`` (..., G, D) as the cache holds it: (..., G/2, 2 D)."""
    return k.reshape(k.shape[:-2] + cfg.kv_row)


def pair_queries(cfg: Lfm2MoeConfig, q):
    """``q`` (..., H, D) as the block-table read takes it: (..., H, 2 D), the
    query of a head of an even key/value group ``[q | 0]``, of an odd one
    ``[0 | q]``."""
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = q.reshape(q.shape[:-2] + (G // 2, 2, H // G, D))
    zero = jnp.zeros_like(q[..., 0, :, :])
    both = jnp.stack([jnp.concatenate([q[..., 0, :, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :, :]], -1)], axis=-3)
    return both.reshape(both.shape[:-4] + (H, 2 * D))


def own_half(cfg: Lfm2MoeConfig, o):
    """What the read gives a padded query, (..., H, 2 D), cut to the half of
    its own head's values: (..., H, D)."""
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    o = o.reshape(o.shape[:-2] + (G // 2, 2, H // G, 2 * D))
    both = jnp.stack([o[..., 0, :, :D], o[..., 1, :, D:]], axis=-3)
    return both.reshape(both.shape[:-4] + (H, D))


def gated_conv(cfg: Lfm2MoeConfig, w, u, taps):
    """``u`` (B, T, d), normed, through the gated short convolution.
    ``taps(z) -> z at t - K + 1 .. t (B, T, K, d)``: the convolution's inputs
    at each position, from the prompt itself or from the row's cached ones."""
    with jax.named_scope("gated_conv"):
        gate_b, gate_c, a = jnp.split(u @ w["in_proj"], 3, axis=-1)
        c = jnp.einsum("btkc,kc->btc", taps(gate_b * a).astype(F32),
                       w["conv_w"].astype(F32)).astype(u.dtype)
        return (gate_c * c) @ w["out_proj"]


def attention(cfg: Lfm2MoeConfig, freqs, w, u, pos, attend):
    """``u`` (B, T, d), normed, at positions ``pos`` (B, T) through the
    attention operator. ``attend(q (B, T, H, D), k, v (B, T, G, D)) -> o (B,
    T, H, D)`` reads the context: the prompt itself, or the paged cache with
    the fresh row in it."""
    B, T = u.shape[:2]
    H, G, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attention"):
        qkv = u @ w["qkv"]
        q = qkv[..., :H * D].reshape(B, T, H, D)
        k = qkv[..., H * D:(H + G) * D].reshape(B, T, G, D)
        v = qkv[..., (H + G) * D:].reshape(B, T, G, D)
        q = M.rope(M.rms(q, w["q_norm"], cfg.norm_eps), pos[..., None], freqs, 1.0)
        k = M.rope(M.rms(k, w["k_norm"], cfg.norm_eps), pos[..., None], freqs, 1.0)
        return attend(q, k, v).reshape(B, T, H * D) @ w["o"]


def layer(cfg: Lfm2MoeConfig, freqs, w, x, pos, live, taps, attend, kernels):
    """One layer over ``x`` (B, T, d); ``live`` (B, T) marks the real tokens
    (the others choose no expert). Returns ``(x, counts (experts,) or
    None)``."""
    u = M.rms(x, w["op_norm"], cfg.norm_eps)
    if "conv_w" in w:
        x = x + gated_conv(cfg, w, u, taps)
    else:
        x = x + attention(cfg, freqs, w, u, pos, attend)
    u = M.rms(x, w["ffn_norm"], cfg.norm_eps)
    if "router" not in w:
        with jax.named_scope("mlp"):
            return x + M.gated_mlp(u, w["gate"], w["up"], w["down"]), None
    with jax.named_scope("experts"):
        y, counts = M.moe_ffn(cfg, w, u.reshape(-1, u.shape[-1]), live.reshape(-1),
                              kernels)
    return x + y.reshape(u.shape), counts


def stack(cfg: Lfm2MoeConfig, params, x, pos, live, pools, conv, attend, kernels):
    """Every layer over ``x`` (B, T, d) at positions ``pos`` (B, T). The two
    reads are the program's, each told which layer OF ITS KIND it serves
    (``i``, a traced scalar inside the scan), and each may write what the
    layer caches into ``pools``, which the scan carries: ``conv(pools, i, z)
    -> (pools, taps (B, T, K, d))`` and ``attend(pools, i, q, k, v) -> (pools,
    o)``. Returns ``(x, pools, counts (expert layers, experts) or None)``."""
    freqs = rope_freqs(cfg)
    return scan_stack(
        cfg, params, x, pools, lambda kind, w, x, read: layer(
            cfg, freqs, w, x, pos, live, read(conv), read(attend), kernels))


def prompt_reads(cfg: Lfm2MoeConfig, lens, T):
    """``(conv, attend)`` of :func:`stack` over whole prompts of true lengths
    ``lens`` in a bucket of ``T``: nothing is read from a cache, and what each
    layer is to cache is STAGED in ``pools`` = ``prompt_staging``'s three
    arrays, a layer of its kind a row: K and V rows as the cache lays them,
    the convolution's last ``K - 1`` inputs under ``lens`` (zeros before a
    prompt shorter than the taps). The program writes each into its pool by
    ONE scatter afterwards: a scatter into the pool from inside the scan would
    copy the pool a turn (PERF.md section 7, 0b (ii)); the staged rows are a
    few MB."""
    taps = prompt_conv(cfg.conv_L_cache, lens)
    t = jnp.arange(T)
    causal = (t[None, :] <= t[:, None])[None]
    G, rep = cfg.num_key_value_heads, cfg.num_attention_heads // cfg.num_key_value_heads

    def conv(pools, i, z):
        ks, vs, tails = pools
        zs, tail = taps(z)
        return (ks, vs, tails.at[i].set(tail)), zs

    def attend(pools, i, q, k, v):
        # a key/value group at a time: the scores of 32 heads over a bucket
        # of 2,048 are 2 GB in float32, of one group an eighth
        def group(qkv):
            qg, kg, vg = qkv
            return attend_dense(cfg, qg, kg[:, :, None], vg[:, :, None], causal)

        ks, vs, tails = pools
        B = q.shape[0]
        qg = jnp.moveaxis(q.reshape(B, T, G, rep, cfg.head_dim), 2, 0)
        o = lax.map(group, (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
        return (ks.at[i].set(pair_keys(cfg, k)), vs.at[i].set(pair_keys(cfg, v)),
                tails), jnp.moveaxis(o, 0, 2).reshape(q.shape)

    return conv, attend


def prompt_staging(cfg: Lfm2MoeConfig, B, T, dtype):
    """Where :func:`prompt_reads` puts what a prompt's layers cache: ``(K rows,
    V rows (attention layers, B, T, G / 2, 2 D), convolution inputs (conv
    layers, B, K - 1, d))``."""
    kv = jnp.zeros((cfg.layer_types.count("full_attention"), B, T) + cfg.kv_row, dtype)
    return kv, kv, jnp.zeros((cfg.layer_types.count("conv"), B, cfg.conv_L_cache - 1,
                              cfg.hidden_size), dtype)


# -- the model ----------------------------------------------------------------------

def _op_leaves(cfg: Lfm2MoeConfig, kind: str):
    d, H, G, D = (cfg.hidden_size, cfg.num_attention_heads,
                  cfg.num_key_value_heads, cfg.head_dim)
    if kind == "conv":
        # taps about 1 (kind ``gain``): the convolution's output is then of
        # the size of its input
        return [("conv.in_proj.weight", (d, 3 * d), "normal"),
                ("conv.conv.weight", (cfg.conv_L_cache, d), "gain"),
                ("conv.out_proj.weight", (d, d), "normal")]
    return [("attn.qkv.weight", (d, (H + 2 * G) * D), "normal"),
            ("attn.q_norm.weight", (D,), "gain"),
            ("attn.k_norm.weight", (D,), "gain"),
            ("attn.o.weight", (H * D, d), "normal")]


def _ffn_leaves(cfg: Lfm2MoeConfig, experts: bool):
    d = cfg.hidden_size
    if not experts:
        F = cfg.intermediate_size
        return [("mlp.gate.weight", (d, F), "normal"), ("mlp.up.weight", (d, F), "normal"),
                ("mlp.down.weight", (F, d), "normal")]
    E, f = cfg.num_experts, cfg.moe_intermediate_size
    return [("mlp.router.weight", (d, E), "normal"),
            ("mlp.router.expert_bias", (E,), "normal"),
            ("mlp.experts.gate", (E, d, f), "normal"),
            ("mlp.experts.up", (E, d, f), "normal"),
            ("mlp.experts.down", (E, f, d), "normal")]


def _layer_leaves(cfg: Lfm2MoeConfig, kind: str, experts: bool):
    d = cfg.hidden_size
    return [("op_norm.weight", (d,), "gain"), ("ffn_norm.weight", (d,), "gain")] \
        + _op_leaves(cfg, kind) + _ffn_leaves(cfg, experts)


def _leaf_kinds(cfg: Lfm2MoeConfig):
    """``[(state_dict key, shape, kind)]``: every parameter, in order;
    matrices (in, out), the convolution (taps, d), experts stacked (experts,
    in, out). ``model.layers.<i>.*`` are the unrolled layers (the dense ones and
    a last partial period); ``model.body.<j>.*`` position ``j`` of the period,
    ONE leaf over its ``periods`` repetitions, as the programs scan them."""
    out = [("model.embed_tokens.weight", (cfg.vocab_size, cfg.hidden_size), "normal")]
    body = range(cfg.num_dense_layers, cfg.tail_start)
    for i, kind in enumerate(cfg.layer_types):
        if i not in body:
            out += [(f"model.layers.{i}.{name}", shape, k)
                    for name, shape, k in _layer_leaves(cfg, kind, cfg.is_expert_layer(i))]
    for j, kind in enumerate(cfg.period if cfg.periods else ()):
        out += [(f"model.body.{j}.{name}", (cfg.periods,) + shape, k)
                for name, shape, k in _layer_leaves(cfg, kind, True)]
    return out + [("model.norm.weight", (cfg.hidden_size,), "gain")]


_SHORT = {"conv.in_proj": "in_proj", "conv.conv": "conv_w", "conv.out_proj": "out_proj",
          "attn.qkv": "qkv", "attn.q_norm": "q_norm", "attn.k_norm": "k_norm",
          "attn.o": "o", "mlp.gate": "gate", "mlp.up": "up", "mlp.down": "down",
          "mlp.router": "router", "mlp.router.expert_bias": "e_bias",
          "mlp.experts.gate": "experts_gate", "mlp.experts.up": "experts_up",
          "mlp.experts.down": "experts_down", "op_norm": "op_norm",
          "ffn_norm": "ffn_norm"}
_EXPERTS = ("experts_gate", "experts_up", "experts_down")


def params_tree(cfg: Lfm2MoeConfig, sd):
    """The weight tree the layer functions take, from ``{state_dict key:
    array}`` (arrays or their shapes): ``lead`` / ``tail`` a dict a layer,
    ``body`` a dict a position of the period with the repetitions stacked,
    which the scan slices, and beside it ``body_experts``, the experts' stacks
    of the same positions, which it does not."""
    return {"wte": sd["model.embed_tokens.weight"], "norm": sd["model.norm.weight"],
            **layer_trees(cfg, _leaf_kinds(cfg), _SHORT.__getitem__, _EXPERTS, sd)}


class Lfm2MoeForCausalLM(nn.Layer):
    """The decoder as a tree of parameters (``state_dict`` keys as
    ``parameter_specs`` lists them). ``weights``, a ``{key: array}`` of every
    parameter, is held as given, without a second copy ever made on the
    device: ten layers fill two thirds of a chip."""

    def __init__(self, config: Lfm2MoeConfig, weights: Optional[dict] = None):
        super().__init__()
        self.config = config
        M.hold_parameters(self, _leaf_kinds(config), weights, config.initializer_range)

    @staticmethod
    def parameter_specs(config: Lfm2MoeConfig):
        return _leaf_kinds(config)

    def forward(self, input_ids):
        """Logits (B, T, vocab) of whole prompts: the prefill path, no cache."""
        ids = jnp.asarray(getattr(input_ids, "_data", input_ids), jnp.int32)
        _, arch, params, _ = self.decode_state()
        B, T = ids.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        conv, attend = prompt_reads(self.config, jnp.full((B,), T, jnp.int32), T)
        x = arch["embed"](params, ids, None)
        x, _, _ = stack(self.config, params, x, pos, jnp.ones((B, T), bool),
                        prompt_staging(self.config, B, T, x.dtype), conv, attend, False)
        return Tensor(arch["head"](params, x))

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``forward`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.lfm2_moe_decode_state(self)

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "Lfm2MoeForCausalLM.generate: the dense decode loop and beam "
            "search are not built for this arch; serve it through serving.Engine")
