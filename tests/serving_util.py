"""Shared helpers for the serving test files (test_serving.py,
test_serving_resilience.py, test_serving_chaos.py): ONE tiny-GPT config,
one prompt generator, one engine-kwargs base — change the model here and
all three suites move together instead of silently diverging."""
import contextlib

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.models.generation as G
from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

# 64 usable blocks of 8 tokens, 8-wide decode, 128-token sequences — small
# enough that pool pressure is easy to provoke, big enough for real batching
ENGINE_KW = dict(block_size=8, num_blocks=64, max_batch=8, max_seq_len=128)


def tiny_gpt(seed=0):
    paddle.seed(seed)
    cfg = GPTConfig(
        vocab_size=211, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    m = GPTForPretraining(cfg)
    m.eval()
    return m


def make_prompts(n, rng, lo=3, hi=24):
    return [rng.randint(0, 211, (int(rng.randint(lo, hi)),)).tolist()
            for _ in range(n)]


@contextlib.contextmanager
def paged_kernel(on):
    """Engines BUILT inside read decode K/V through the block-table kernel
    (``True``: at any head width, which the interpreter runs), through the
    gather (``False``), or as the chip would choose for their arch
    (``"mosaic"``: the kernel where Mosaic takes the head width, run here by
    the interpreter). Patches the one function that chooses
    (``generation.paged_kernel_default``; on this tier it says gather)."""
    real = G.paged_kernel_default
    if on == "mosaic":
        G.paged_kernel_default = lambda arch, mosaic=None: real(arch, True)
    else:
        G.paged_kernel_default = lambda arch, mosaic=None: bool(on)
    try:
        yield
    finally:
        G.paged_kernel_default = real
