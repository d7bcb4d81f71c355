"""A second model family, as a later PR would add one: the program's other
served decoder class (``paddle_tpu.models.llama``: RMS norm, rotary
positions, gated MLP, fewer K/V heads than query heads, no position table,
untied head). Tiny, served only (no ``TrainReference``, no FLOP or weight
counts), and in no ``BENCHMARK.json``: ``test_benchmark_manifest.py`` copies
this directory over a copy of ``benchmark/`` and runs the cell.

Layer equations (Touvron et al. 2023), float32, every matmul at ``HIGHEST``:
    h   = x + o(attn(rope(q(n1(x))), rope(k(n1(x))), v(n1(x))))   causal, 1/sqrt(D)
    out = h + down(silu(gate(n2(h))) * up(n2(h)))
    logits = head(n_f(x_L))
``rope`` turns the pairs (2j, 2j+1) of a head by ``pos / theta^(2j/D)``; K
and V have ``num_kv_heads`` heads, each shared by a group of query heads.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark import model
from benchmark.reference.common import F32, HI, linear

REHEARSE = {"vocab_size": 1024, "hidden_size": 128, "num_layers": 2, "num_heads": 4,
            "num_kv_heads": 2, "intermediate_size": 352,
            "max_position_embeddings": 256, "dtype": "float32"}

_LAYER = (("ln1.g", "input_layernorm"), ("q.w", "self_attn.q_proj"),
          ("k.w", "self_attn.k_proj"), ("v.w", "self_attn.v_proj"),
          ("o.w", "self_attn.o_proj"), ("ln2.g", "post_attention_layernorm"),
          ("gate.w", "mlp.gate_proj"), ("up.w", "mlp.up_proj"),
          ("down.w", "mlp.down_proj"))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_heads"]


def leaf_specs(cfg: dict) -> list:
    d, f, kv = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_kv_heads"] * head_dim(cfg)
    shapes = {"ln1.g": (d,), "q.w": (d, d), "k.w": (d, kv), "v.w": (d, kv), "o.w": (d, d),
              "ln2.g": (d,), "gate.w": (d, f), "up.w": (d, f), "down.w": (f, d)}
    specs = [("wte", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_layers"]):
        specs += [(f"h{i}.{leaf}", shapes[leaf], "gain" if leaf.endswith(".g") else "normal")
                  for leaf, _ in _LAYER]
    return specs + [("lnf.g", (d,), "gain"), ("head.w", (d, cfg["vocab_size"]), "normal")]


def state_key(leaf: str) -> str:
    if leaf in ("wte", "lnf.g", "head.w"):
        return {"wte": "model.embed_tokens.weight", "lnf.g": "model.norm.weight",
                "head.w": "lm_head.weight"}[leaf]
    layer, _, rest = leaf.partition(".")
    return f"model.layers.{int(layer[1:])}.{dict(_LAYER)[rest]}.weight"


def build(cfg: dict, weights: dict):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    with model.default_dtype(cfg["dtype"]):
        net = LlamaForCausalLM(LlamaConfig(**{k: v for k, v in cfg.items() if k in fields}))
    return net, model.hold(net, weights, state_key, cfg["name"])


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one cached token over all layers: K/V heads only."""
    return float(2 * cfg["num_layers"] * cfg["num_kv_heads"] * head_dim(cfg) * itemsize)


# -- the plain reference ------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope(x, theta):
    """(T, H, D): pairs (2j, 2j+1) turned by position / theta^(2j/D)."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=F32)[:, None] / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _attend_row(q, k, v, theta):
    """One sequence: q (T, H, D), k and v (T, KV, D) -> (T, H*D)."""
    t, h, d = q.shape
    group = h // k.shape[1]
    q, k = rope(q, theta), rope(k, theta)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI).reshape(t, -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "mode"))
def _layer(p, x, heads, kv_heads, eps, theta, mode):
    p = {k: w.astype(F32) for k, w in p.items()}
    b, t, d = x.shape
    h = rms_norm(x, p["ln1.g"], eps)
    q = linear(h, p["q.w"], 0.0, mode).reshape(b, t, heads, d // heads)
    k = linear(h, p["k.w"], 0.0, mode).reshape(b, t, kv_heads, d // heads)
    v = linear(h, p["v.w"], 0.0, mode).reshape(b, t, kv_heads, d // heads)
    a = jax.vmap(partial(_attend_row, theta=theta))(q, k, v)
    x = x + linear(a, p["o.w"], 0.0, mode)
    h = rms_norm(x, p["ln2.g"], eps)
    ff = jax.nn.silu(linear(h, p["gate.w"], 0.0, mode)) * linear(h, p["up.w"], 0.0, mode)
    return x + linear(ff, p["down.w"], 0.0, mode)


def forward_logits(cfg: dict, weights: dict, ids, mode: str = "f32"):
    """Logits (B, T, V), float32, layer by layer."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = weights["wte"].astype(F32)[jnp.asarray(ids, jnp.int32)]
    for i in range(cfg["num_layers"]):
        x = _layer({leaf: weights[f"h{i}.{leaf}"] for leaf, _ in _LAYER}, x,
                   int(cfg["num_heads"]), int(cfg["num_kv_heads"]), eps, theta, mode)
    h = rms_norm(x, weights["lnf.g"].astype(F32), eps)
    return linear(h, weights["head.w"].astype(F32), 0.0, mode)
