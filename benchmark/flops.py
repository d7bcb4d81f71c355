"""Operations and bytes the ALGORITHM needs, from shapes. Nothing padded,
nothing recomputed: a share worked out from these can reach 100% only if the
program wastes nothing, and a share over 100% is a bug in a count or in the
time it is divided by (``share`` raises).

Here is what belongs to no model family: the flash-attention kernels' counts,
the roofline and the share. A model's own counts (parameters, FLOPs per
trained token, bytes a decode step reads, cache bytes per context token) are
its family's, ``families/<family>.py``; a family that gives none has no such
metric, never another family's formula.

Hand-worked values are in tests/benchmark/test_benchmark_flops.py.
"""
from __future__ import annotations


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
