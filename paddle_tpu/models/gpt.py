"""GPT model family — the flagship training config (BASELINE: GPT-3 1.3B).

Parity: the reference trains GPT via PaddleNLP on Fleet hybrid parallel
(BASELINE.md); the in-tree building blocks are the fused transformer ops
(``paddle/fluid/operators/fused/fused_attention_op.cc``) and the Megatron
layers (``fleet/meta_parallel/parallel_layers/mp_layers.py``). This model is
built TPU-first:

 * every matmul is a Megatron-shardable layer — weights carry PartitionSpecs
   ("mp" column/row sharding) that GSPMD partitions when compiled on a mesh;
 * sequence-parallel activations: hidden states carry ("dp", "sp") sharding
   constraints so long sequences shard over the 'sp' axis;
 * attention runs through the fused scaled_dot_product_attention functional
   (Pallas flash kernel on TPU) or ring attention under explicit shard_map;
 * the decoder stack is uniform — pipeline-stageable by construction
   (pp_layers.PipelineLayer segments it; the spmd pipeline stacks it).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding, ParallelCrossEntropy,
)
from ..distributed.sharding_api import shard_tensor

try:
    from jax.sharding import PartitionSpec as P
except Exception:  # pragma: no cover
    P = None


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    use_mp_layers: bool = True  # Megatron-shardable weights (GSPMD specs)
    fused_lm_loss: bool = True  # blockwise head+CE, no (B·T,V) logits tensor
    remat: bool = False  # jax.checkpoint each decoder layer (1.3B-on-a-chip)
    sequence_parallel: bool = False  # annotate activations with 'sp'
    # "auto": ring attention whenever sequence_parallel and the mesh has an
    # 'sp' axis >1 (the long-context path — O(T/sp) memory per device, K/V
    # blocks rotate the ICI ring); "exact"/"flash" force those kernels.
    attention_impl: str = "auto"

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size


def _sp_constrain(x, config):
    """Sequence-parallel activation sharding: (B, T, H) → P('dp','sp',None)."""
    if config.sequence_parallel and P is not None:
        try:
            return shard_tensor(x, placement=P("dp", "sp", None))
        except Exception:
            return x
    return x


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        self.qkv = ColumnParallelLinear(h, 3 * h, has_bias=True, gather_output=False)
        self.proj = RowParallelLinear(h, h, has_bias=True, input_is_parallel=True)
        self.attn_dropout = config.attention_dropout
        if config.attention_impl not in ("auto", "ring", "exact", "flash"):
            raise ValueError(
                f"attention_impl must be auto|ring|exact|flash, got {config.attention_impl!r}"
            )
        self.config = config

    def _ring_mesh(self):
        """The global mesh iff ring attention should run: sequence_parallel
        on, causal, an 'sp' axis of size >1 present, and no attention dropout
        in play (ring, like flash, never materializes the score matrix a
        dropout mask would apply to)."""
        if not self.config.sequence_parallel or self.config.attention_impl not in ("auto", "ring"):
            return None
        if self.attn_dropout and self.training:
            if self.config.attention_impl == "ring":
                raise ValueError(
                    "attention_impl='ring' does not support attention_dropout>0 "
                    "while training; set attention_dropout=0.0"
                )
            return None  # auto: fall back to sdpa so dropout semantics hold
        try:
            from ..distributed.mesh import global_mesh

            mesh = global_mesh()
        except Exception:
            return None
        if mesh is None:
            return None
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        return mesh if sizes.get("sp", 1) > 1 else None

    def _ring_attention(self, q, k, v, mesh):
        """shard_map island inside the GSPMD program: q/k/v (B,T,heads,D) get
        sequence-sharded over 'sp' (batch over 'dp', heads over 'mp' when
        present) and K/V blocks rotate via ppermute — the long-context path
        the reference lacks. Attention dropout is skipped on this path (as in
        flash kernels)."""
        from jax.sharding import PartitionSpec as P

        from ..distributed.mesh import shard_map_compat

        _shard_map, _check = shard_map_compat()
        from ..core.dispatch import eager_call
        from ..distributed.fleet.meta_parallel.sequence_parallel import ring_attention

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = "dp" if sizes.get("dp", 1) > 1 else None
        hp = "mp" if sizes.get("mp", 1) > 1 else None
        spec = P(dp, "sp", hp, None)
        fn = _shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **_check,
        )
        return eager_call("ring_attention_spmd", fn, [q, k, v])

    def forward(self, x, attn_mask=None):
        B, T = x.shape[0], x.shape[1]
        # each (B, T, heads, D), GLOBAL shapes under GSPMD whatever the mesh
        q, k, v = self.qkv.fused_heads(x, 3, self.head_dim)
        ring_mesh = self._ring_mesh() if attn_mask is None else None
        if ring_mesh is not None:
            out = self._ring_attention(q, k, v, ring_mesh)
        else:
            impl = self.config.attention_impl
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                dropout_p=self.attn_dropout, training=self.training,
                impl=impl if impl in ("exact", "flash") else None,
            )
        out = out.reshape([B, T, q.shape[2] * self.head_dim])
        return self.proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.up = ColumnParallelLinear(h, config.ffn_size, has_bias=True, gather_output=False)
        self.down = RowParallelLinear(config.ffn_size, h, has_bias=True, input_is_parallel=True)

    def forward(self, x):
        return self.down(F.gelu(self.up(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    """Pre-LN decoder block — the uniform pipeline stage unit."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size, epsilon=1e-5)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size, epsilon=1e-5)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.hidden_dropout)
        self.config = config

    def forward(self, x, attn_mask=None):
        x = x + self.dropout(self.attn(self.ln1(x), attn_mask))
        x = _sp_constrain(x, self.config)
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return _sp_constrain(x, self.config)


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = nn.initializer.Normal(std=config.initializer_range)
        if config.use_mp_layers:
            self.word_embeddings = VocabParallelEmbedding(config.vocab_size, config.hidden_size, weight_attr=init)
        else:
            self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size, weight_attr=init)
        self.position_embeddings = nn.Embedding(config.max_position_embeddings, config.hidden_size, weight_attr=init)
        self.dropout = nn.Dropout(config.hidden_dropout)
        self.config = config

    def forward(self, input_ids, position_ids=None):
        from ..ops.creation import arange

        T = input_ids.shape[1]
        if position_ids is None:
            position_ids = arange(T, dtype="int64").unsqueeze(0)
        x = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return _sp_constrain(self.dropout(x), self.config)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList([GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.final_ln = nn.LayerNorm(config.hidden_size, epsilon=1e-5)

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        x = self.embeddings(input_ids, position_ids)
        if self.config.remat:
            # activation checkpointing: drop per-layer residuals, XLA
            # rematerializes them in the backward (HBM for FLOPs — the
            # single-chip 1.3B training config needs this)
            from ..distributed.fleet.utils import recompute

            for layer in self.layers:
                if attn_mask is None:
                    x = recompute(lambda h, _l=layer: _l(h, None), x)
                else:
                    # mask travels as a tensor ARG (a closed-over tensor would
                    # change the flush-cache key every step and a pending
                    # LazyArray cannot cross the jax.checkpoint boundary)
                    x = recompute(lambda h, m, _l=layer: _l(h, m), x, attn_mask)
        else:
            for layer in self.layers:
                x = layer(x, attn_mask)
        return self.final_ln(x)


class GPTForPretraining(nn.Layer):
    """LM head tied to the word embedding (reference: SharedLayerDesc tying)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        x = self.gpt(input_ids, position_ids, attn_mask)
        w = self.gpt.embeddings.word_embeddings.weight
        logits = F.linear(x, _transpose(w))
        return logits

    def loss(self, input_ids, labels):
        if getattr(self.config, "fused_lm_loss", True):
            # blockwise fused projection+CE: never materializes the
            # (B·T, vocab) fp32 logits (ops/fused_ce.py) — this is what
            # bounds trainable batch size at V≈50k
            x = self.gpt(input_ids)
            w = self.gpt.embeddings.word_embeddings.weight
            return F.fused_linear_cross_entropy(x, w, labels)
        logits = self(input_ids)
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1])
        )

    def decode_state(self):
        """``(arch_key, arch, params, max_positions)``: the arch plug and the
        weight tree that ``generate()`` and ``serving.Engine`` run this model
        through (``models/generation.py``)."""
        from . import generation

        return generation.gpt_decode_state(self)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 top_p=1.0, eos_token_id=None, do_sample=True, num_beams=1,
                 length_penalty=0.0):
        """KV-cached compiled autoregressive decoding (see
        models/generation.py — prefill + lax.fori_loop sampling in ONE jitted
        program; the reference's top_k/multinomial/beam_search op roles).
        ``num_beams>1`` runs stacked-beam search (beam_search_op role)."""
        from .generation import generate as _generate

        return _generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_token_id=eos_token_id, do_sample=do_sample,
            num_beams=num_beams, length_penalty=length_penalty,
        )


def _transpose(w):
    from ..ops.manipulation import transpose

    return transpose(w, [1, 0])


# -- standard configs --------------------------------------------------------
def gpt_tiny(**kw):
    return GPTConfig(
        vocab_size=1024, hidden_size=128, num_layers=4, num_heads=4,
        max_position_embeddings=256, **kw,
    )


def gpt3_1p3b(**kw):
    """GPT-3 1.3B (BASELINE north-star config)."""
    return GPTConfig(
        vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
        max_position_embeddings=2048, **kw,
    )


def gpt3_13b(**kw):
    return GPTConfig(
        vocab_size=50304, hidden_size=5120, num_layers=40, num_heads=40,
        max_position_embeddings=2048, **kw,
    )
