"""How the routed experts' per-pair rows get back to their tokens
(``ops/kernels/moe_experts.py``, PR 48).

At 256-row tiles (``pairs >= 512``: the prefill programs) each row the expert
kernel produced leaves it by a copy of its own to its (choice, token) place and
``moe_combine`` reads the k planes once; a pair no held expert took starts no
copy and counts as an exact zero whatever its place holds. At 16-row tiles
(the decode programs) the tail is the parent's, bit for bit and jaxpr for
jaxpr: a copy of the parent's ``_experts_call`` is kept here as the plain
form.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.mla_moe import experts_plain
from paddle_tpu.ops.kernels import moe_experts as K
from paddle_tpu.ops.pallas import kernel_x64_off

pl, pltpu, I32 = K.pl, K.pltpu, jnp.int32


def tol(ref):
    return 2e-5 * np.abs(np.asarray(ref)).max() + 2e-6


@pytest.fixture(autouse=True, scope="module")
def _drop_the_executables():
    """Every case compiles interpreted kernels (a wide tile's 256 row copies
    unrolled); a test process that keeps some hundred XLA:CPU executables
    alive runs out of room for code (PERF.md, PR 45 (6)), so they go when
    the module is done."""
    yield
    jax.clear_caches()


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def parent_call(x, slot, gates, wg, wu, wd, layer=None, *, tm, tf, interpret):
    """``_experts_call`` as it stood at ``1afa004`` (PR 47), line for line."""
    N, d = x.shape
    k = slot.shape[1]
    E, _, f = wg.shape[-3:]
    nf = f // tf
    A = N * k
    flat = slot.reshape(A).astype(I32)
    dest, te, nt = K.tile_layout(flat, E, tm)
    if layer is not None:
        te = te + layer.astype(I32) * E
        wg, wu, wd = (w.reshape((-1,) + w.shape[-2:]) for w in (wg, wu, wd))
    NT = te.shape[0]
    src = jnp.full((NT * tm + 1,), A, I32).at[dest].set(jnp.arange(A, dtype=I32))
    xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[
        jnp.where(src < A, src // k, N)[:NT * tm]]

    def used(i, nt):
        return jnp.maximum(jnp.minimum(i, nt[0] - 1), 0)

    def f_at(i, j, nt):
        return jnp.where(i < nt[0], j, nf - 1)

    with kernel_x64_off(interpret):
        ys = pl.pallas_call(
            functools.partial(K._experts_kernel, nf=nf),
            name=f"moe_experts_t{tm}",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(NT, nf),
                in_specs=[
                    pl.BlockSpec((tm, d), lambda i, j, te, nt: (used(i, nt), 0)),
                    pl.BlockSpec((None, d, tf),
                                 lambda i, j, te, nt: (te[i], 0, f_at(i, j, nt))),
                    pl.BlockSpec((None, d, tf),
                                 lambda i, j, te, nt: (te[i], 0, f_at(i, j, nt))),
                    pl.BlockSpec((None, tf, d),
                                 lambda i, j, te, nt: (te[i], f_at(i, j, nt), 0)),
                ],
                out_specs=pl.BlockSpec((tm, d),
                                       lambda i, j, te, nt: (used(i, nt), 0)),
                scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((NT * tm, d), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=96 * 2 ** 20),
            interpret=interpret,
        )(te, nt, xs, wg, wu, wd)
    dest = dest.reshape(N, k)
    took = (slot < E)[..., None]
    y = jnp.where(took, ys[jnp.minimum(dest, NT * tm - 1)].astype(jnp.float32), 0.0)
    return jnp.sum(y * gates[..., None], axis=1).astype(x.dtype)


def case(name, dtype):
    """``(x, slot, gates, (wg, wu, wd), layer)`` of a named case; every one
    has 512 pairs or more, so the rule gives 256-row tiles."""
    rng = np.random.default_rng(sum(map(ord, name)))
    d, f = 256, 128
    N, k, routed, held, layers = {
        "all_held_k6": (128, 6, 8, 8, 0),
        "16_of_128_held_k8": (96, 8, 128, 16, 0),
        "padding_between_live_rows": (160, 4, 8, 8, 0),
        "layer_1_of_a_stack_of_2": (128, 6, 8, 8, 2),
        "one_expert_takes_every_pair": (300, 2, 8, 8, 0),
    }[name]
    choice = np.stack([rng.permutation(routed)[:k] for _ in range(N)])
    if name == "one_expert_takes_every_pair":  # full tiles, then a partial one
        choice[:, 0] = 3
    slot = np.where(choice < held, choice, held)
    if name == "padding_between_live_rows":
        slot[rng.random(N) < 0.4] = held
    gates = np.where(slot < held, rng.random((N, k)), 0.0)
    lead = (layers,) if layers else ()
    ws = [jnp.asarray(rng.normal(size=lead + s) * 0.2, dtype)
          for s in ((held, d, f), (held, d, f), (held, f, d))]
    return (jnp.asarray(rng.normal(size=(N, d)), dtype), jnp.asarray(slot, I32),
            jnp.asarray(gates, jnp.float32), ws, jnp.int32(1) if layers else None)


CASES = ["all_held_k6", "16_of_128_held_k8", "padding_between_live_rows",
         "layer_1_of_a_stack_of_2", "one_expert_takes_every_pair"]


@pytest.fixture
def nan_where_nothing_was_written(monkeypatch):
    """The interpreter's memory that no kernel wrote reads NaN, also as the
    32-bit words two bfloat16 share (it gives 0 for an unsigned integer)."""
    from jax._src.pallas import primitives

    real = primitives.uninitialized_value

    def value(shape, dtype):
        if jnp.issubdtype(dtype, jnp.unsignedinteger):
            return jnp.full(shape, jnp.iinfo(dtype).max, dtype)
        return real(shape, dtype)

    monkeypatch.setattr(primitives, "uninitialized_value", value)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", CASES)
def test_wide_tiles_equal_the_plain_form(name, dtype, nan_where_nothing_was_written):
    """Pairs no held expert took land nowhere; their places hold NaN and the
    result has none: they were masked, not multiplied by a zero gate."""
    x, slot, gates, ws, layer = case(name, dtype)
    E = ws[0].shape[-3]
    assert x.shape[0] * slot.shape[1] >= 512
    got = np.asarray(K.moe_experts(x, slot, gates, *ws, layer=layer, interpret=True),
                     np.float64)
    assert np.isfinite(got).all()
    plain = [w if layer is None else w[layer] for w in ws]
    if dtype == jnp.float32:
        ref = np.asarray(experts_plain(x, slot, gates, *plain), np.float64)
        assert np.abs(got - ref).max() <= tol(ref), name
    else:
        # the parent's tail rounds at the same points (a pair's row once,
        # gates and sum in float32, the sum once): the order of the k terms
        # is all that may differ, one rounding of the result
        ref = np.asarray(parent_call(x, slot, gates, *ws, layer, tm=256,
                                     tf=ws[0].shape[-1], interpret=True), np.float64)
        assert np.abs(got - ref).max() <= 2.0 ** -7 * np.abs(ref).max(), name
    # a row whose every pair went unheld is an exact zero
    lost = np.asarray((slot == E).all(axis=1))
    assert not got[lost].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_pair_row_is_rounded_once_and_packed_losslessly(dtype):
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(16, 512)) * 100, jnp.float32)
    halves = K._unpack(K._pack(y, dtype), dtype)
    back = np.concatenate([np.asarray(h) for h in halves], axis=-1)
    assert back.dtype == np.float32
    assert np.array_equal(back, np.asarray(y.astype(dtype).astype(jnp.float32)))


@pytest.mark.parametrize("d,dtype,ok", [(2048, jnp.bfloat16, True), (3584, jnp.bfloat16, True),
                                        (256, jnp.float32, True), (64, jnp.float32, False),
                                        (128, jnp.bfloat16, False), (2048, jnp.float16, False)])
def test_which_rows_leave_by_a_copy_of_their_own(d, dtype, ok):
    assert K._rows_copy(d, dtype) is ok


def test_a_width_whose_rows_cannot_be_copied_keeps_the_gathered_tail():
    """32 columns at 256-row tiles: the parent's result, bit for bit."""
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    x, ws = f(160, 32), [f(6, 32, 64), f(6, 32, 64), f(6, 64, 32)]
    slot = jnp.asarray(rng.integers(0, 7, (160, 4)), I32)
    gates = jnp.asarray(rng.random((160, 4)), jnp.float32)
    got = K.moe_experts(x, slot, gates, *ws, interpret=True)
    ref = parent_call(x, slot, gates, *ws, tm=256, tf=64, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


DECODE = {  # rows, choices, experts routed, held, layers
    "8_rows_k4": (8, 4, 8, 8, 0),
    "32_rows_k8_4_of_16_held": (32, 8, 16, 4, 0),
    "5_rows_k2_layer_of_a_stack": (5, 2, 6, 6, 2),
    "63_rows_k8_just_under_512_pairs": (63, 8, 8, 8, 0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(DECODE))
def test_narrow_tiles_are_the_parent_bit_for_bit(name, dtype):
    N, k, routed, held, layers = DECODE[name]
    assert N * k < 512
    rng = np.random.default_rng(N)
    d, f = 256, 128
    choice = np.stack([rng.permutation(routed)[:k] for _ in range(N)])
    slot = jnp.asarray(np.where(choice < held, choice, held), I32)
    gates = jnp.asarray(rng.random((N, k)), jnp.float32)
    lead = (layers,) if layers else ()
    ws = [jnp.asarray(rng.normal(size=lead + s) * 0.2, dtype)
          for s in ((held, d, f), (held, d, f), (held, f, d))]
    x = jnp.asarray(rng.normal(size=(N, d)), dtype)
    layer = jnp.int32(1) if layers else None
    got = K.moe_experts(x, slot, gates, *ws, layer=layer, interpret=True)
    ref = parent_call(x, slot, gates, *ws, layer, tm=16, tf=f, interpret=True)
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(ref.astype(jnp.float32)))


@pytest.mark.parametrize("name", list(DECODE))
def test_narrow_tiles_trace_to_the_parents_jaxpr(name):
    """A decode program's expert product is the parent's jaxpr for jaxpr."""
    N, k, _, held, layers = DECODE[name]
    d, f, bf = 256, 128, jnp.bfloat16
    lead = (layers,) if layers else ()
    args = (jax.ShapeDtypeStruct((N, d), bf), jax.ShapeDtypeStruct((N, k), I32),
            jax.ShapeDtypeStruct((N, k), jnp.float32),
            *(jax.ShapeDtypeStruct(lead + s, bf)
              for s in ((held, d, f), (held, d, f), (held, f, d))))
    layer = (jax.ShapeDtypeStruct((), I32),) if layers else ()

    def text(call):
        fn = lambda *a: call(*a[:6], *(a[6:] or (None,)), tm=16, tf=f, interpret=False)
        return str(jax.make_jaxpr(fn)(*args, *layer))

    ours = text(K._experts_call).replace("_experts_call", "call")
    assert ours == text(parent_call).replace("parent_call", "call")


@pytest.mark.parametrize("arch,pairs,held", [
    pytest.param({}, 14, 14, id="every_expert_held_and_counted"),
    pytest.param({"experts_held": (0, 2)}, 14, 9, id="every_expert_counted_two_held"),
    pytest.param({"experts_per_token": 3}, 5 * 3 * 2, 14, id="only_the_held_counted"),
])
def test_a_span_says_how_many_pairs_the_combine_moves(arch, pairs, held):
    """``Engine._note_experts``: ``expert_pairs`` of the live tokens and
    ``expert_pairs_held`` from the counts the program returned anyway."""
    from paddle_tpu.serving.engine import Engine

    class Eng:
        _arch = arch
        _expert_tokens = np.zeros((2, 4), np.int64)

    class Span(dict):
        set = dict.update

    counts = np.asarray([[3, 1, 2, 0], [1, 4, 3, 0]])
    sp = Span()
    Engine._note_experts(Eng, sp, counts, 5)
    assert (sp["expert_pairs"], sp["expert_pairs_held"]) == (pairs, held)
    assert sp["experts_touched"] == 6 and sp["expert_assignments"] == 14
    assert np.array_equal(Eng._expert_tokens, counts)
