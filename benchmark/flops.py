"""Operations and bytes the ALGORITHM needs, from shapes. Nothing padded,
nothing recomputed: a share worked out from these can reach 100% only if the
program wastes nothing, and a share over 100% is a bug in a count or in the
time it is divided by (``share`` raises).

Hand-worked values for GPT-3 XL are in tests/benchmark/test_benchmark_flops.py.
"""
from __future__ import annotations


def matmul_params_per_layer(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 3 * d * d + d * d + 2 * d * f  # qkv, proj, up, down


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3 x forward), causal attention counted once: a
    token at position t attends t+1 keys, so QK^T and PV cost 4*d*(t+1)
    and the mean over a sequence is 2*d*(seq+1). Biases, norms, GELU and the
    softmax are left out (under 1% at these widths)."""
    d, layers, vocab = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    fwd = layers * (2 * matmul_params_per_layer(cfg) + 2 * d * (seq + 1)) \
        + 2 * d * vocab
    return 3.0 * fwd


def flash_flops(batch: int, heads: int, head_dim: int, seq: int, kind: str) -> float:
    """Causal attention of one call: the forward has two matmuls over the
    seq*(seq+1)/2 live (query, key) pairs, the backward four (dV, dP, dQ,
    dK); the backward's recomputation of the scores is not counted."""
    pairs = batch * heads * seq * (seq + 1) / 2
    per_pair = {"fwd": 4, "bwd": 8, "dq": 4, "dkv": 4}[kind] * head_dim
    return pairs * per_pair


def flash_bytes(batch: int, heads: int, head_dim: int, seq: int, kind: str,
                itemsize: int = 2) -> float:
    """Each operand read or written once: fwd q,k,v -> o; dq reads q,k,v,do
    and writes dq; dkv reads q,k,v,do and writes dk,dv."""
    tensors = {"fwd": 4, "dq": 5, "dkv": 6, "bwd": 8}[kind]
    return float(tensors * batch * seq * heads * head_dim * itemsize)


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of weights one decode step must read: every layer's matrices,
    biases and norms, and the tied embedding once (the head reads all of it;
    the token and position lookups read rows of what is already counted)."""
    d, f, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_layers"]
    per_layer = matmul_params_per_layer(cfg) + (3 * d + d + f + d) + 4 * d
    return float(itemsize * (layers * per_layer + cfg["vocab_size"] * d + 2 * d))


def kv_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one cached token over all layers."""
    return float(2 * cfg["num_layers"] * cfg["hidden_size"] * itemsize)


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """Least time the chip could take, and which bound sets it."""
    tc, tm = flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def share(needed: float, spent: float, what: str) -> float:
    """``needed / spent`` as a percentage. Over 100% is refused."""
    if spent <= 0:
        raise ValueError(f"{what}: no time or capacity to divide by ({spent})")
    pct = 100.0 * needed / spent
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.2f}% of the peak: the operations or bytes are "
            "counted too high, or the time leaves out part of the work")
    return pct
