"""Every Pallas entry point must compile for the TPU — checked from the CPU.

The sandbox's libtpu compiles for a chip it does not have: a topology
description stands in for the devices, and ``jit(f).trace(shapes).lower(
lowering_platforms=("tpu",)).compile()`` runs Mosaic and XLA:TPU. The rest of
the suite runs these kernels under the Pallas interpreter, which accepts
programs Mosaic refuses (a bf16 matmul accumulator, a block shape off the
(8, 128) tile, a kernel GSPMD is asked to partition), so this file is the only
place tier-1 meets the TPU compiler. No kernel executes here; numbers come
from the chip (``chip_smoke.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import partitioned_over
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.kernels import int8_matmul, paged_attention_rows
from paddle_tpu.ops.pallas import flash_attention as flash_mod

pytest.importorskip("libtpu")


@pytest.fixture(scope="module")
def v5e():
    """Four ``TPU v5 lite`` device descriptions (one 2x2 host)."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


def _compile_for_tpu(fn, *args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    n_kernels = lowered.as_text().count("tpu_custom_call")
    # an executable for a chip that is not here cannot be loaded back from
    # the persistent cache ("DeserializeLoadedExecutable not implemented"):
    # keep these out of it, or every later run warns and recompiles anyway
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float("inf"))
    try:
        lowered.compile()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", threshold)
    return n_kernels


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the three training shapes the repo has chip history for (ROADMAP S1):
# GPT-355M b8xs1024, GPT-1.3B b2xs2048, GPT-211M b2xs8192
@pytest.mark.parametrize("b,t,h,d", [(8, 1024, 16, 64), (2, 2048, 16, 128),
                                     (2, 8192, 16, 64)])
def test_flash_fwd_bwd_compiles(v5e, b, t, h, d):
    def loss(q, k, v):
        out = flash_mod.flash_attention_array(q, k, v, causal=True,
                                              interpret=False)
        return out.astype(jnp.float32).sum()

    x = _on(SingleDeviceSharding(v5e[0]), (b, t, h, d), jnp.bfloat16)
    n = _compile_for_tpu(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert n == 3  # forward, dq, dk/dv


def test_flash_under_dp_mp_sharding_compiles(v5e, monkeypatch):
    """GSPMD refuses to partition a Mosaic call; the functional maps it over
    the mesh of the enclosing compiled step (``partitioned_over``) by hand."""
    monkeypatch.setattr(flash_mod, "interpret_default", lambda: False)
    mesh = Mesh(np.asarray(v5e).reshape(2, 2), ("dp", "mp"))
    x = _on(NamedSharding(mesh, P("dp", None, "mp", None)),
            (4, 2048, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = F.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=True, impl="flash")
        return out._data.astype(jnp.float32).sum()

    def sharded_loss(q, k, v):
        with partitioned_over(mesh):
            return loss(q, k, v)

    grad = jax.value_and_grad(sharded_loss, argnums=(0, 1, 2))
    assert _compile_for_tpu(grad, x, x, x) == 3
    # and the failure the wrap exists for is still the compiler's answer
    # without it — the day GSPMD learns to partition Mosaic, drop the wrap
    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _compile_for_tpu(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)


def test_int8_matmul_compiles_at_the_1p3b_head(v5e):
    s = SingleDeviceSharding(v5e[0])
    n = _compile_for_tpu(
        lambda x, q, scale: int8_matmul(x, q, scale, transpose_w=True,
                                        interpret=False),
        _on(s, (8, 2048), jnp.bfloat16), _on(s, (50304, 2048), jnp.int8),
        _on(s, (), jnp.float32))
    assert n == 1


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic refuses the kernel as written (handed to ROADMAP S2a): "
           "'The Pallas TPU lowering currently requires that the last two "
           "dimensions of your block shape are divisible by 8 and 128 "
           "respectively, or be equal to the respective dimensions of the "
           "overall array' — the (R, MB) = (1, 16) SMEM block of the (8, 16) "
           "block table")
def test_paged_attention_compiles_at_b8_mb16(v5e):
    B, H, D, KV, BS, MB, NB = 8, 16, 128, 16, 16, 16, 512
    s = SingleDeviceSharding(v5e[0])
    pool = _on(s, (NB, BS, KV, D), jnp.bfloat16)
    n = _compile_for_tpu(
        lambda q, k, v, tables, pos: paged_attention_rows(
            q, k, v, tables, pos, interpret=False),
        _on(s, (B, H, D), jnp.bfloat16), pool, pool,
        _on(s, (B, MB), jnp.int32), _on(s, (B,), jnp.int32))
    assert n == 1
