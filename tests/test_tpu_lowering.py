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


def _compile_uncached(lowered):
    # an executable for a chip that is not here cannot be loaded back from
    # the persistent cache ("DeserializeLoadedExecutable not implemented"):
    # keep these out of it, or every later run warns and recompiles anyway
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float("inf"))
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", threshold)


def _compile_for_tpu(fn, *args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    _compile_uncached(lowered)
    return lowered.as_text().count("tpu_custom_call")


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


# the paged decode kernel: a small shape, the serving cell's (64 rows, a table
# 128 wide, the pool that fills the chip beside GPT-3 XL) and grouped heads
@pytest.mark.parametrize(
    "B,MB,H,KV,NB,L",
    [pytest.param(8, 16, 16, 16, 512, 2, id="b8_mb16"),
     pytest.param(64, 128, 16, 16, 3679, 24, id="cell_b64_mb128"),
     pytest.param(8, 16, 32, 8, 512, 2, id="gqa_rep4")])
def test_paged_attention_compiles(v5e, B, MB, H, KV, NB, L):
    D, BS = 128, 16
    s = SingleDeviceSharding(v5e[0])
    pool = _on(s, (L, NB, BS, KV, D), jnp.bfloat16)
    n = _compile_for_tpu(
        lambda q, k, v, tables, pos: paged_attention_rows(
            q, k, v, L - 1, tables, pos, interpret=False),
        _on(s, (B, H, D), jnp.bfloat16), pool, pool,
        _on(s, (B, MB), jnp.int32), _on(s, (B,), jnp.int32))
    assert n == 1


def _gpt_step_operands(s, L, d, H, D, BS, B, MB, NB, vocab=50304):
    """``ShapeDtypeStruct`` operands of a bfloat16 GPT decode step."""
    bf = jnp.bfloat16
    layer = {"ln1_w": (d,), "ln1_b": (d,), "qkv_w": (d, 3 * d),
             "qkv_b": (3 * d,), "proj_w": (d, d), "proj_b": (d,),
             "ln2_w": (d,), "ln2_b": (d,), "up_w": (d, 4 * d),
             "up_b": (4 * d,), "down_w": (4 * d, d), "down_b": (d,)}
    params = {"wte": _on(s, (vocab, d), bf), "wpe": _on(s, (2048, d), bf),
              "lnf_w": _on(s, (d,), bf), "lnf_b": _on(s, (d,), bf),
              "layers": [{k: _on(s, v, bf) for k, v in layer.items()}
                         for _ in range(L)]}
    pool = _on(s, (L, NB, BS, H, D), bf)
    return (params, pool, pool, _on(s, (B, MB), jnp.int32),
            _on(s, (B,), jnp.int32), _on(s, (B,), jnp.int32),
            _on(s, (B,), jnp.float32), _on(s, (2,), jnp.uint32))


@pytest.mark.parametrize("d,H,kernel_calls", [
    pytest.param(768, 12, 0, id="gpt2_d64_gathers"),
    pytest.param(2560, 32, 0, id="gpt3_2p7b_d80_gathers"),
    pytest.param(1024, 8, 2, id="d128_kernel")])
def test_decode_step_the_chip_chooses_compiles(v5e, monkeypatch, d, H,
                                               kernel_calls):
    """The decode program the engine builds on the chip for an arch
    (``paged_kernel_default``, by head width) compiles whole: Mosaic refuses
    the kernel at a head width off the 128-lane tile, and there the step is
    the gather's."""
    import paddle_tpu.models.generation as G
    from paddle_tpu.ops.kernels import paged_attention as pa

    monkeypatch.setattr(pa, "interpret_default", lambda: False)
    L, BS, B, MB, NB = 2, 16, 8, 16, 512
    D = d // H
    arch = G._gpt_arch(H, D)
    on_chip = G.paged_kernel_default(arch, mosaic=True)
    assert on_chip is (kernel_calls > 0)
    build = G.build_paged_decode_kernel if on_chip else G.build_paged_decode
    s = SingleDeviceSharding(v5e[0])
    lowered = jax.jit(build(arch, B, BS, MB), donate_argnums=(1, 2)).trace(
        *_gpt_step_operands(s, L, d, H, D, BS, B, MB, NB, vocab=1024),
    ).lower(lowering_platforms=("tpu",))
    compiled = _compile_uncached(lowered)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == kernel_calls
    if not on_chip:
        # and the refusal the rule stands for is still the compiler's answer
        monkeypatch.setattr(pa, "mosaic_takes", lambda head_dim: True)
        with pytest.raises(Exception, match="Mosaic failed to compile"):
            _compile_uncached(jax.jit(
                G.build_paged_decode_kernel(arch, B, BS, MB)).trace(
                *_gpt_step_operands(s, L, d, H, D, BS, B, MB, NB, vocab=1024),
            ).lower(lowering_platforms=("tpu",)))


def test_kernel_decode_step_at_the_cell_shape(v5e, monkeypatch):
    """The whole B64 decode program of GPT-3 XL beside the 3,679-block pool:
    one kernel call a layer, and temporaries that do not grow with the pool
    (the gather step has 1.57 GB there; XLA updates the donated pool in
    place between the kernel reads)."""
    import paddle_tpu.models.generation as G
    from paddle_tpu.ops.kernels import paged_attention as pa

    monkeypatch.setattr(pa, "interpret_default", lambda: False)
    L, d, H, D, BS, B, MB, NB = 24, 2048, 16, 128, 16, 64, 128, 3679
    s = SingleDeviceSharding(v5e[0])
    step = jax.jit(G.build_paged_decode_kernel(G._gpt_arch(H, D), B, BS, MB),
                   donate_argnums=(1, 2))
    lowered = step.trace(
        *_gpt_step_operands(s, L, d, H, D, BS, B, MB, NB),
    ).lower(lowering_platforms=("tpu",))
    # the 24 layers share one lowered kernel ...
    assert lowered.as_text().count("tpu_custom_call") == 1
    compiled = _compile_uncached(lowered)
    # ... which the program calls once a layer
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == L
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 1e9, f"decode step temporaries {temps / 1e9:.2f} GB"
