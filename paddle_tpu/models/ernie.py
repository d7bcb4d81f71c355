"""ERNIE (BERT-style bidirectional encoder) — BASELINE config ERNIE-3.0.

Encoder with token/position/segment embeddings, MLM + NSP-style heads;
Megatron-shardable like GPT.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)


@dataclass
class ErnieConfig:
    vocab_size: int = 40000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 2048
    type_vocab_size: int = 4
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02


class ErnieSelfAttention(nn.Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_heads
        self.head_dim = h // config.num_heads
        self.qkv = ColumnParallelLinear(h, 3 * h, has_bias=True, gather_output=False)
        self.out = RowParallelLinear(h, h, has_bias=True, input_is_parallel=True)
        self.dropout = config.attention_dropout

    def forward(self, x, attn_mask=None):
        B, T = x.shape[0], x.shape[1]
        # each (B, T, heads, D), GLOBAL shapes under GSPMD whatever the mesh
        q, k, v = self.qkv.fused_heads(x, 3, self.head_dim)
        o = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=False, dropout_p=self.dropout, training=self.training
        )
        return self.out(o.reshape([B, T, q.shape[2] * self.head_dim]))


class ErnieLayer(nn.Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        h = config.hidden_size
        self.attn = ErnieSelfAttention(config)
        self.ln1 = nn.LayerNorm(h)
        self.up = ColumnParallelLinear(h, config.intermediate_size, has_bias=True, gather_output=False)
        self.down = RowParallelLinear(config.intermediate_size, h, has_bias=True, input_is_parallel=True)
        self.ln2 = nn.LayerNorm(h)
        self.dropout = nn.Dropout(config.hidden_dropout)

    def forward(self, x, attn_mask=None):
        x = self.ln1(x + self.dropout(self.attn(x, attn_mask)))
        x = self.ln2(x + self.dropout(self.down(F.gelu(self.up(x)))))
        return x


class ErnieModel(nn.Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(std=config.initializer_range)
        self.word_emb = VocabParallelEmbedding(config.vocab_size, config.hidden_size, weight_attr=init)
        self.pos_emb = nn.Embedding(config.max_position_embeddings, config.hidden_size, weight_attr=init)
        self.type_emb = nn.Embedding(config.type_vocab_size, config.hidden_size, weight_attr=init)
        self.emb_ln = nn.LayerNorm(config.hidden_size)
        self.dropout = nn.Dropout(config.hidden_dropout)
        self.layers = nn.LayerList([ErnieLayer(config) for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, position_ids=None, attn_mask=None):
        from ..ops.creation import arange, zeros_like

        T = input_ids.shape[1]
        if position_ids is None:
            position_ids = arange(T, dtype="int64").unsqueeze(0)
        if token_type_ids is None:
            token_type_ids = zeros_like(input_ids)
        x = self.word_emb(input_ids) + self.pos_emb(position_ids) + self.type_emb(token_type_ids)
        x = self.dropout(self.emb_ln(x))
        for layer in self.layers:
            x = layer(x, attn_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForPretraining(nn.Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.ernie = ErnieModel(config)
        self.mlm_transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.mlm_ln = nn.LayerNorm(config.hidden_size)
        self.nsp_head = nn.Linear(config.hidden_size, 2)
        self.config = config

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        seq, pooled = self.ernie(input_ids, token_type_ids, attn_mask=attn_mask)
        from ..ops.manipulation import transpose

        h = self.mlm_ln(F.gelu(self.mlm_transform(seq)))
        mlm_logits = F.linear(h, transpose(self.ernie.word_emb.weight, [1, 0]))
        nsp_logits = self.nsp_head(pooled)
        return mlm_logits, nsp_logits

    def loss(self, input_ids, mlm_labels, nsp_labels=None):
        mlm_logits, nsp_logits = self(input_ids)
        loss = F.cross_entropy(
            mlm_logits.reshape([-1, mlm_logits.shape[-1]]), mlm_labels.reshape([-1]), ignore_index=-100
        )
        if nsp_labels is not None:
            loss = loss + F.cross_entropy(nsp_logits, nsp_labels)
        return loss


def ernie_3_base(**kw):
    return ErnieConfig(**kw)
