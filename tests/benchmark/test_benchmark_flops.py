"""FLOP and byte functions against values worked by hand for GPT-3 XL
(24 layers, d 2048, 16 heads of 128, vocab 50,304, s 2048): the model's own
counts through its family's module, as the harness finds it."""
import pytest

from benchmark import flops
from benchmark.manifest import Manifest

CFG = Manifest().config("gpt3-xl-1p3b")
GPT = Manifest().family(CFG["family"])
PEAKS = Manifest().peaks("TPU v5 lite")


def test_train_flops_per_token_xl():
    # per layer: qkv 3d^2 + proj d^2 + up 4d^2 + down 4d^2 = 12 d^2 = 50,331,648
    assert GPT.matmul_params_per_layer(CFG) == 12 * 2048 * 2048 == 50_331_648
    layer_matmul = 2 * 50_331_648                 # 100,663,296 per token
    attention = 2 * 2048 * 2049                   # 8,392,704: causal, counted once
    head = 2 * 2048 * 50_304                      # 206,045,184
    fwd = 24 * (layer_matmul + attention) + head  # 2,823,389,184
    assert fwd == 2_823_389_184
    assert GPT.train_flops_per_token(CFG, 2048) == 3 * fwd == 8_470_167_552


def test_flash_counts_xl_layer():
    pairs = 2 * 16 * 2048 * 2049 // 2             # 67,141,632 live (q, k) pairs
    assert flops.flash_flops(2, 16, 128, 2048, "fwd") == pairs * 4 * 128
    assert flops.flash_flops(2, 16, 128, 2048, "dq") \
        + flops.flash_flops(2, 16, 128, 2048, "dkv") \
        == flops.flash_flops(2, 16, 128, 2048, "bwd") == pairs * 8 * 128
    one = 2 * 2048 * 16 * 128 * 2                 # one (B,T,H,D) bf16 operand: 16 MiB
    assert one == 16 * 2**20
    assert flops.flash_bytes(2, 16, 128, 2048, "fwd") == 4 * one
    assert flops.flash_bytes(2, 16, 128, 2048, "dq") == 5 * one
    assert flops.flash_bytes(2, 16, 128, 2048, "dkv") == 6 * one
    sec, bound = flops.roofline_seconds(
        flops.flash_flops(2, 16, 128, 2048, "fwd"),
        flops.flash_bytes(2, 16, 128, 2048, "fwd"), PEAKS)
    assert bound == "compute"                     # 34.4 GFLOP / 197 T = 174 us > 82 us
    assert sec == pytest.approx(pairs * 512 / 197e12)


def test_decode_bytes_xl():
    d = 2048
    per_layer = 12 * d * d + (3 * d + d + 4 * d + d) + 4 * d
    want = 2 * (24 * per_layer + 50_304 * d + 2 * d)
    assert GPT.weight_bytes(CFG) == want
    assert 2.62e9 < want < 2.63e9                 # 1.31 G weights in bf16
    assert GPT.cache_bytes_per_context_token(CFG) == 2 * 24 * 2048 * 2 == 196_608
    assert GPT.head_dim(CFG) == 128


def test_share_over_100_raises():
    assert flops.share(70.0, 100.0, "x") == 70.0
    with pytest.raises(ValueError, match="counted too high"):
        flops.share(101.0, 100.0, "x")
    with pytest.raises(ValueError):
        flops.share(1.0, 0.0, "x")
