"""Llama model family (BASELINE: Llama-7B TP×PP hybrid).

TPU-first: RMSNorm + SwiGLU + RoPE with Megatron-shardable weights; uniform
decoder stack (pipeline-stageable); rotary embedding computed inside the
traced step (no host-side caches).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import as_tensor, eager_call
from ..core.tensor import Tensor
from ..nn import functional as F
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding, products_of,
)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02

    @property
    def ffn_size(self):
        if self.intermediate_size is not None:
            return self.intermediate_size
        return int(2 * (4 * self.hidden_size) / 3 + 255) // 256 * 256

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads


class RMSNorm(nn.Layer):
    def __init__(self, hidden_size, eps=1e-6):
        super().__init__()
        self.weight = self.create_parameter([hidden_size], default_initializer=nn.initializer.Constant(1.0))
        self.eps = eps

    def forward(self, x):
        return eager_call(
            "rms_norm",
            lambda a, w, eps: (a * jax.lax.rsqrt(jnp.mean(jnp.square(a.astype(jnp.float32)), -1, keepdims=True) + eps)).astype(a.dtype) * w,
            [as_tensor(x), self.weight],
            {"eps": self.eps},
        )


def apply_rope(q, k, theta=10000.0):
    """Rotary embedding as one traced op over (B, T, H, D) q/k."""

    def fn(qa, ka, theta):
        B, T, H, D = qa.shape
        pos = jnp.arange(T, dtype=jnp.float32)
        inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
        ang = pos[:, None] * inv[None, :]  # (T, D/2)
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]

        def rot(x):
            x1, x2 = x[..., ::2], x[..., 1::2]
            o1 = x1 * cos - x2 * sin
            o2 = x2 * cos + x1 * sin
            # angles are f32: cast back so bf16 q/k stay bf16 (a silent f32
            # upcast here forced the whole attention out of the MXU-native
            # dtype and crashed the Pallas path on mixed-dtype operands)
            return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)

        return rot(qa), rot(ka)

    out = eager_call("rope", fn, [as_tensor(q), as_tensor(k)], {"theta": theta})
    return out[0], out[1]


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.kv_heads = config.kv_heads
        self.head_dim = h // config.num_heads
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.kv_heads * self.head_dim, has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.kv_heads * self.head_dim, has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)
        self.theta = config.rope_theta

    def forward(self, x, attn_mask=None):
        B, T = x.shape[0], x.shape[1]
        # one input, three products: under 'mp' their input cotangents are
        # added before they cross the axis once
        q, k, v = products_of(x, self.q_proj, self.k_proj, self.v_proj)
        lh = q.shape[-1] // self.head_dim
        lkv = k.shape[-1] // self.head_dim
        q = q.reshape([B, T, lh, self.head_dim])
        k = k.reshape([B, T, lkv, self.head_dim])
        v = v.reshape([B, T, lkv, self.head_dim])
        q, k = apply_rope(q, k, self.theta)
        if lkv != lh:  # grouped-query attention: repeat kv heads
            from ..ops.manipulation import repeat_interleave

            k = repeat_interleave(k, lh // lkv, axis=2)
            v = repeat_interleave(v, lh // lkv, axis=2)
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None, training=self.training)
        return self.o_proj(out.reshape([B, T, lh * self.head_dim]))


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, f = config.hidden_size, config.ffn_size
        self.gate_proj = ColumnParallelLinear(h, f, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, f, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(f, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        gate, up = products_of(x, self.gate_proj, self.up_proj)
        return self.down_proj(F.silu(gate) * up)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, attn_mask=None):
        x = x + self.self_attn(self.input_layernorm(x), attn_mask)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([LlamaDecoderLayer(config) for _ in range(config.num_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, attn_mask)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.model = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size, has_bias=False, gather_output=True)

    def forward(self, input_ids, attn_mask=None):
        return self.lm_head(self.model(input_ids, attn_mask))

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``generate()`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.llama_decode_state(self)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 top_p=1.0, eos_token_id=None, do_sample=True):
        """KV-cached compiled decode (models/generation.py Llama path: RoPE
        at absolute cache positions, GQA caches only KV heads)."""
        from .generation import generate_llama

        return generate_llama(
            self, input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, do_sample=do_sample,
        )


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=4, num_heads=4, max_position_embeddings=256, **kw)


def llama_7b(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, **kw)
