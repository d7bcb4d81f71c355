"""The fused QKV product under 'mp' (PR 36): where the step is compiled over a
mesh that splits the heads, the weight is exchanged onto head boundaries by
``ppermute`` and the product taken against that
(``mp_layers.linear_on_groups``), so no activation is gathered. Held against
the same model with the exchange disabled (the contiguous split and GSPMD's
gather, the program before PR 36), on the same seed, on the virtual CPU mesh:
the mathematics is the same contraction for the same columns, data moved and
nothing else. The parameter, its shape, its column order, its ``pspec`` and
its optimizer state are untouched."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed.engine import HybridParallelEngine, collectives
from paddle_tpu.distributed.fleet.meta_parallel import mp_layers
from paddle_tpu.distributed.mesh import partitioned_over
from paddle_tpu.profiler import spans as _spans

from test_dp_exchange_step import _gap, _mesh

pytestmark = pytest.mark.multichip

STEPS = 3


def _model(arch, hidden, heads):
    if arch == "gpt":
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

        return GPTForPretraining(GPTConfig(
            vocab_size=256, hidden_size=hidden, num_layers=2, num_heads=heads,
            max_position_embeddings=32, hidden_dropout=0.0, attention_dropout=0.0))
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining

    return ErnieForPretraining(ErnieConfig(
        vocab_size=256, hidden_size=hidden, num_layers=2, num_heads=heads,
        intermediate_size=2 * hidden, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0))


def _run(mesh, arch, hidden, heads):
    """Three steps under AdamW of a two-layer model whose fused QKV leaves are
    seeded from outside through ``set_value``, as the benchmark holds them:
    (d, 3d) in Q | K | V column order. Returns losses, parameters and first
    moments by position, the engine, the spans and the state dict's layout."""
    paddle.seed(36)
    model = _model(arch, hidden, heads)
    rng = np.random.default_rng(36)
    state = model.state_dict()
    seeded = [k for k in state if k.endswith("qkv.weight")]
    assert len(seeded) == 2
    for k in seeded:
        assert tuple(state[k].shape) == (hidden, 3 * hidden)
        state[k].set_value(rng.normal(0, 0.05, (hidden, 3 * hidden)).astype("float32"))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    eng = HybridParallelEngine(model, opt, lambda m, i, l: m.loss(i, l), mesh=mesh)
    seen = []
    _spans.add_span_observer(seen.append)
    try:
        losses = []
        for _ in range(STEPS):
            ids = rng.integers(0, 256, (8, 17))
            losses.append(eng.train_step(paddle.to_tensor(ids[:, :-1]),
                                         paddle.to_tensor(ids[:, 1:])))
        losses = [np.asarray(l._data, np.float32) for l in losses]
    finally:
        _spans.remove_span_observer(seen.append)
    moments = opt.state_dict()
    params = {i: np.asarray(p._data) for i, p in enumerate(eng.params)}
    first = {i: np.asarray(moments[f"{p.name}.moment1"]._data)
             for i, p in enumerate(eng.params)}
    layout = {k: (tuple(v.shape), getattr(v, "pspec", None))
              for k, v in model.state_dict().items()}
    return losses, params, first, eng, [s for s in seen if s.name == "train_step"], layout


def _exchanges(eng):
    """The collective-permutes of the engine's compiled step that were traced
    under the exchange's scope (on the CPU none is asynchronous)."""
    (exe,) = eng._compiled.values()
    return sum(1 for c in collectives(exe.as_text())
               if c.op == "collective-permute" and c.under(mp_layers.MP_EXCHANGE_SCOPE))


# transfers an exchange makes: of the three chunks a rank holds, those whose
# rank -> (3 x rank + i) % mp is no identity (one of three at mp 2, all at 4)
@pytest.mark.parametrize("arch,dp,mp,hidden,heads,sends", [
    pytest.param("gpt", 2, 2, 64, 4, 1, id="gpt_dp2_mp2"),
    pytest.param("gpt", 2, 4, 64, 4, 3, id="gpt_dp2_mp4"),
    pytest.param("gpt", 2, 4, 48, 6, 0, id="gpt_heads_not_divided"),
    pytest.param("ernie", 2, 2, 64, 4, 1, id="ernie_dp2_mp2"),
])
def test_exchanged_product_is_the_gathered_product(arch, dp, mp, hidden, heads,
                                                   sends, monkeypatch):
    if len(jax.devices()) < dp * mp:
        pytest.skip(f"needs {dp * mp} devices")
    mesh = _mesh(dp, mp)
    new = _run(mesh, arch, hidden, heads)
    monkeypatch.setattr(mp_layers, "groups_axis", lambda *a, **k: None)
    ref = _run(mesh, arch, hidden, heads)
    # the leaves the benchmark holds, as the parent lays them
    assert new[5] == ref[5]
    qkv = [k for k in new[5] if k.endswith("qkv.weight")]
    assert [new[5][k] for k in qkv] == [((hidden, 3 * hidden), P(None, "mp"))] * 2
    assert [new[5][k.replace("weight", "bias")] for k in qkv] == [((3 * hidden,), P("mp"))] * 2
    # forward, again for the backward pass, and the weight's cotangent, a
    # layer; XLA:CPU drops the barrier before its last CSE and merges the
    # second with the first (XLA:TPU keeps them apart: test_tpu_lowering.py)
    assert _exchanges(ref[3]) == 0
    assert _exchanges(new[3]) in (2 * 2 * sends, 2 * 3 * sends)
    # the reshard the exchange removes is there in the step without it
    # (where mp does not divide the heads both steps are that one)
    gathers = ref[4][0].attrs["mp_activation_gathers"]
    assert gathers > 0
    for s in new[4]:
        assert s.attrs["mp_activation_gathers"] == (0 if sends else gathers)
        assert 0 <= s.attrs["mp_weight_exchanges"] <= 2 * 3 * sends
    # Two programs, two compilations: the sums over 3H of the input's
    # cotangent are taken in another order, so the runs agree to rounding
    # (float32 read 1e-07 to 3e-06; one element under Adam's epsilon is its
    # gradient's sign, so held over the whole model as test_dp_exchange_step)
    for a, b in zip(ref[0], new[0]):
        np.testing.assert_allclose(a, b, rtol=3e-6)
    for which in (1, 2):
        assert ref[which].keys() == new[which].keys() and ref[which]
        assert _gap(ref[which], new[which]) < 3e-5


@pytest.mark.parametrize("mp,groups", [(2, 3), (4, 3), (2, 5), (4, 1), (8, 3)])
def test_split_on_groups_moves_the_blocks_and_nothing_else(mp, groups):
    """``split_on_groups`` alone on a counted array: block g's part on a chip
    is that chip's columns of projection g; its transpose lays a cotangent
    back as the leaf lies; the product against it is ``x @ w + b``."""
    if len(jax.devices()) < mp:
        pytest.skip(f"needs {mp} devices")
    mesh = Mesh(np.asarray(jax.devices()[:mp]).reshape(1, mp), ("dp", "mp"))
    d, h = 8, 4 * mp
    w = jnp.arange(d * groups * h, dtype=jnp.float32).reshape(d, groups * h)
    b = jnp.arange(groups * h, dtype=jnp.float32) * 100
    lie = (NamedSharding(mesh, P(None, "mp")), NamedSharding(mesh, P("mp")))

    def split(w, b):
        with partitioned_over(mesh):
            return mp_layers.split_on_groups((w, b), groups, "mp")

    ws, bs = jax.jit(split, in_shardings=lie)(w, b)
    np.testing.assert_array_equal(np.asarray(ws), np.asarray(w).reshape(d, groups, h))
    np.testing.assert_array_equal(np.asarray(bs), np.asarray(b).reshape(groups, h))
    assert ws.sharding.spec == P(None, None, "mp") and bs.sharding.spec == P(None, "mp")

    weights = jnp.arange(w.size, dtype=jnp.float32).reshape(d, groups, h)

    def weighed(w, b):
        ws, bs = split(w, b)
        return (ws * weights).sum() + (bs * bs).sum()

    gw, gb = jax.jit(jax.grad(weighed, argnums=(0, 1)), in_shardings=lie)(w, b)
    np.testing.assert_array_equal(np.asarray(gw), np.asarray(weights).reshape(d, -1))
    np.testing.assert_array_equal(np.asarray(gb), 2 * np.asarray(b))
    assert gw.sharding.spec == P(None, "mp")

    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 6, d)), jnp.float32)
    w = w / w.size

    def product(x, w, b):
        with partitioned_over(mesh):
            return (mp_layers.linear_on_groups(x, w, b, groups, "mp") ** 2).sum()

    got = jax.jit(jax.value_and_grad(product, argnums=(0, 1, 2)),
                  in_shardings=(NamedSharding(mesh, P()), *lie))(x, w, b)
    want = jax.value_and_grad(lambda x, w, b: ((x @ w + b) ** 2).sum(),
                              argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5)


@pytest.mark.parametrize("heads,mp,groups,serves", [
    (32, 2, 3, True), (32, 4, 3, True), (32, 8, 3, True),
    (32, 3, 3, False),   # rank -> (3 x rank + i) % 3 is no permutation
    (6, 4, 3, False),    # mp does not divide the heads
    (32, 1, 3, False),   # nothing to split
    (32, 2, 2, False),   # two projections over two ranks: both on one
])
def test_groups_axis_reads_the_mesh_and_the_shapes(heads, mp, groups, serves):
    if len(jax.devices()) < mp:
        pytest.skip(f"needs {mp} devices")
    assert mp_layers.groups_axis(heads, groups) is None  # no step being traced
    mesh = Mesh(np.asarray(jax.devices()[:mp]).reshape(1, mp), ("dp", "mp"))
    with partitioned_over(mesh):
        assert mp_layers.groups_axis(heads, groups) == ("mp" if serves else None)
    with partitioned_over(Mesh(np.asarray(jax.devices()[:mp]), ("dp",))):
        assert mp_layers.groups_axis(heads, groups) is None


# sha256 of the lowered ``compile_train_step`` program of the tiny GPT below
# (``.lower(...).as_text()``), recorded on commit e810753, the parent of the
# PR that brought the exchange: with no 'mp' axis the attention traces to the
# program it was
PARENT_STEP = "e549417edeecac9cee33a859a69149db46e62e7551fb5097e58ed58c7e1c9951"


def test_without_an_mp_axis_the_step_is_the_parents_program():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(36)
    model = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=32, hidden_dropout=0.0, attention_dropout=0.0))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, lambda m, i, l: m.loss(i, l), opt)
    ids = np.random.default_rng(36).integers(0, 256, (4, 17))
    lowered = step.lower(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == PARENT_STEP
