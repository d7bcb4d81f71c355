"""The sums of partial products under 'mp' (PR 39): where the step is compiled
over a mesh whose 'mp' axis joins two chips, a row-parallel product, and the
input cotangent of a column-parallel one, is summed over the axis by an
exchange of the two partials in blocks of tokens and a local add
(``mp_layers.product_summed``) where GSPMD's all-reduce stood. Held against
the same model with the mechanism declined (``reduce_axis`` says None: the
program before PR 39), on the same seed, on the virtual CPU mesh: the
mathematics is the same sum of the same two values, data moved and nothing
else. Parameters, their shapes, ``pspec``s and optimizer state are untouched."""
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed.engine import HybridParallelEngine, collectives
from paddle_tpu.distributed.fleet.meta_parallel import mp_layers
from paddle_tpu.distributed.mesh import partitioned_over, shard_map_compat
from paddle_tpu.profiler import spans as _spans

from test_dp_exchange_step import _gap, _mesh

pytestmark = pytest.mark.multichip

STEPS = 3
LAYERS = 2


def _model(arch, hidden=64, heads=4):
    common = dict(vocab_size=256, hidden_size=hidden, num_layers=LAYERS,
                  num_heads=heads, max_position_embeddings=32)
    if arch == "gpt":
        from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

        return GPTForPretraining(GPTConfig(
            **common, hidden_dropout=0.0, attention_dropout=0.0))
    if arch == "ernie":
        from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining

        return ErnieForPretraining(ErnieConfig(
            **common, intermediate_size=2 * hidden, hidden_dropout=0.0,
            attention_dropout=0.0))
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(**common, num_kv_heads=heads // 2,
                                        intermediate_size=2 * hidden))


def _run(mesh, arch, batch=8, seq=16, exchange=True):
    """Three steps under AdamW of a two-layer model. Returns losses,
    parameters and first moments by position, the engine, the ``train_step``
    spans and the state dict's layout."""
    paddle.set_flags({"FLAGS_shard_weight_update": exchange})
    paddle.seed(39)
    model = _model(arch)
    rng = np.random.default_rng(39)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    eng = HybridParallelEngine(model, opt, lambda m, i, l: m.loss(i, l), mesh=mesh)
    seen = []
    _spans.add_span_observer(seen.append)
    try:
        losses = []
        for _ in range(STEPS):
            ids = rng.integers(0, 256, (batch, seq + 1))
            losses.append(eng.train_step(paddle.to_tensor(ids[:, :-1]),
                                         paddle.to_tensor(ids[:, 1:])))
        losses = [np.asarray(l._data, np.float32) for l in losses]
    finally:
        _spans.remove_span_observer(seen.append)
        paddle.set_flags({"FLAGS_shard_weight_update": True})
    moments = opt.state_dict()
    params = {i: np.asarray(p._data) for i, p in enumerate(eng.params)}
    first = {i: np.asarray(moments[f"{p.name}.moment1"]._data)
             for i, p in enumerate(eng.params)}
    layout = {k: (tuple(v.shape), getattr(v, "pspec", None))
              for k, v in model.state_dict().items()}
    return losses, params, first, eng, [s for s in seen if s.name == "train_step"], layout


def _text(eng, mesh, batch, seq):
    """The scheduled text of the step the engine ran: the dp step's own
    executable, or the replicated GSPMD step compiled again for its text."""
    if eng._compiled:
        (exe,) = eng._compiled.values()
        return exe.as_text()
    ids = paddle.to_tensor(np.zeros((batch, seq), np.int64))
    return eng.lower(ids, ids).compile().as_text()


def _pairs(mesh):
    ids = np.arange(mesh.size).reshape(mesh.devices.shape)
    ids = np.moveaxis(ids, mesh.axis_names.index("mp"), -1)
    return {frozenset(row.tolist()) for row in ids.reshape(-1, mesh.shape["mp"])}


# the column-parallel products whose input cotangent crosses 'mp' each layer
# (llama's three and two share ONE exchange each), beside two row-parallel
SITES = {"gpt": 4, "ernie": 4, "llama": 4}


@pytest.mark.parametrize("arch,dp,mp,chunks,batch,seq,exchange,blocks", [
    pytest.param("gpt", 2, 2, 4, 8, 16, True, 4, id="gpt_dp2_mp2"),
    pytest.param("gpt", 2, 2, 2, 8, 16, True, 2, id="gpt_dp2_mp2_two_blocks"),
    pytest.param("gpt", 2, 2, 1, 8, 16, True, 1, id="gpt_dp2_mp2_whole"),
    # 2 rows x 15 tokens a replica: the rows in two, 15 tokens in no two
    pytest.param("gpt", 2, 2, 4, 4, 15, True, 2, id="gpt_tokens_not_divided"),
    pytest.param("gpt", 1, 2, 4, 8, 16, True, 4, id="gpt_dp1_mp2"),
    # the replicated GSPMD step: 'dp' splits the rows there, so no block of
    # them is cut; the partials cross whole
    pytest.param("gpt", 2, 2, 4, 8, 16, False, 1, id="gpt_dp2_mp2_replicated_step"),
    # more than two chips along 'mp': declined, GSPMD's all-reduce
    pytest.param("gpt", 1, 4, 4, 8, 16, True, 0, id="gpt_dp1_mp4_declines"),
    pytest.param("ernie", 2, 2, 4, 8, 16, True, 4, id="ernie_dp2_mp2"),
    pytest.param("llama", 2, 2, 4, 8, 16, True, 4, id="llama_dp2_mp2"),
    pytest.param("llama", 1, 2, 2, 8, 16, True, 2, id="llama_dp1_mp2"),
])
def test_exchanged_sum_is_the_all_reduced_sum(arch, dp, mp, chunks, batch, seq,
                                              exchange, blocks, monkeypatch):
    if len(jax.devices()) < dp * mp:
        pytest.skip(f"needs {dp * mp} devices")
    mesh = _mesh(dp, mp)
    monkeypatch.setattr(mp_layers, "MP_REDUCE_CHUNKS", chunks)
    new = _run(mesh, arch, batch, seq, exchange)
    monkeypatch.setattr(mp_layers, "reduce_axis", lambda *a, **k: None)
    ref = _run(mesh, arch, batch, seq, exchange)
    assert new[5] == ref[5]  # keys, shapes and pspecs of the state dict
    assert (new[3]._wus is not None) == (exchange and dp > 1)

    def counts(run):
        found = collectives(_text(run[3], mesh, batch, seq))
        sent = [c for c in found if c.op == "collective-permute"
                and c.under(mp_layers.MP_REDUCE_SCOPE)]
        assert all(c.over() == _pairs(mesh) for c in sent)
        lead = f"[{batch // dp},{seq},"  # a compiled text's shapes are a chip's
        reduces = [c for c in found if c.op == "all-reduce" and lead in c.shape
                   and c.over() == _pairs(mesh)]
        return len(sent), len(reduces)

    sent, left = counts(new)
    assert counts(ref)[0] == 0
    assert sent == SITES[arch] * LAYERS * blocks
    # what the mechanism removes: four token-shaped all-reduces a layer (the
    # embedding's one a step is not its to take)
    if blocks:
        assert counts(ref)[1] - left == SITES[arch] * LAYERS
    if new[3]._wus is not None:
        for s in new[4]:
            assert s.attrs["mp_reduce_exchanges"] == sent
            assert 0 <= s.attrs["mp_reduce_async"] <= sent
            assert s.attrs["mp_activation_reduces"] == left
        assert ref[4][0].attrs["mp_reduce_exchanges"] == 0
    # a + b where the all-reduce took a + b: the same values in float32 on
    # this backend, and rounding between two compilations at most
    for a, b in zip(ref[0], new[0]):
        np.testing.assert_allclose(a, b, rtol=3e-6)
    for which in (1, 2):
        assert ref[which].keys() == new[which].keys() and ref[which]
        assert _gap(ref[which], new[which]) < 3e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks,shape", [(4, (2, 8)), (4, (3, 5)), (2, (6,)), (1, (2, 8))])
def test_both_chips_hold_the_same_bits(chunks, shape, dtype, monkeypatch):
    """``product_summed`` alone: each chip of the 'mp' pair holds the sum of
    the two partial products, bit for bit the same on both (they keep
    replicas of every norm and bias), and in float32 equal to the whole
    product."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setattr(mp_layers, "MP_REDUCE_CHUNKS", chunks)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    rng = np.random.default_rng(39)
    k, n = 16, 12
    x = jnp.asarray(rng.normal(size=shape + (k,)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), dtype)
    shard_map, check = shard_map_compat()

    def both(x, w):
        with partitioned_over(mesh):
            where = mp_layers._where("mp")
            assert where.local and mp_layers.reduce_axis(k) == "mp"
            out = mp_layers.row_parallel(x, w, where, chunks)
        # what each chip holds of the replicated result, side by side
        return shard_map(lambda o: o[None], mesh=mesh, in_specs=P(),
                         out_specs=P("mp"), **check)(out)

    got = np.asarray(jax.jit(both, in_shardings=(
        NamedSharding(mesh, P(*(None,) * len(shape), "mp")),
        NamedSharding(mesh, P("mp", None))))(x, w).astype(jnp.float32))
    assert got.shape == (2,) + shape + (n,)
    np.testing.assert_array_equal(got[0], got[1])
    halves = [np.asarray((x[..., i * 8:(i + 1) * 8] @ w[i * 8:(i + 1) * 8])
                         .astype(jnp.float32)) for i in range(2)]
    want = (jnp.asarray(halves[0], dtype) + jnp.asarray(halves[1], dtype))
    np.testing.assert_allclose(got[0], np.asarray(want.astype(jnp.float32)),
                               rtol=2e-2 if dtype == "bfloat16" else 1e-6)


@pytest.mark.parametrize("lead,chunks,cuts", [
    ((2, 2048), 4, (2, 2)), ((4, 2048), 4, (4, 1)), ((1, 2048), 4, (1, 4)),
    ((2, 15), 4, (2, 1)), ((3, 5), 4, (1, 1)), ((6, 16), 4, (2, 2)),
    ((4096,), 4, (4,)), ((2, 2048), 1, (1, 1)), ((2, 2048), 2, (2, 1)),
])
def test_cuts_follow_the_shape(lead, chunks, cuts):
    assert mp_layers._cuts(lead, chunks) == cuts


@pytest.mark.parametrize("mp,split,serves", [
    (2, (64,), True), (2, (64, 32), True),
    (2, (63,), False),   # the axis does not divide the contracted dimension
    (4, (64,), False),   # a ring over more than two chips is not written
    (1, (64,), False),   # nothing to sum
])
def test_reduce_axis_reads_the_mesh_and_the_shapes(mp, split, serves):
    if len(jax.devices()) < mp:
        pytest.skip(f"needs {mp} devices")
    assert mp_layers.reduce_axis(*split) is None  # no step being traced
    mesh = Mesh(np.asarray(jax.devices()[:mp]).reshape(1, mp), ("dp", "mp"))
    with partitioned_over(mesh):
        assert mp_layers.reduce_axis(*split) == ("mp" if serves else None)
    with partitioned_over(Mesh(np.asarray(jax.devices()[:mp]), ("dp",))):
        assert mp_layers.reduce_axis(*split) is None


def test_inside_a_map_that_holds_mp_by_hand_the_psum_stays():
    """Megatron's per-rank view: an enclosing map holds 'mp', shapes are the
    rank's own and ``_mp_allreduce``'s ``psum`` is the sum. The mechanism
    reads that (``_axis_bound``) and declines: the traced program holds a
    ``psum`` over 'mp' and no ``ppermute``."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = _mesh(1, 2)
    from paddle_tpu.distributed.fleet.meta_parallel import RowParallelLinear

    paddle.seed(39)
    layer = RowParallelLinear(16, 8, has_bias=True, input_is_parallel=True)
    shard_map, check = shard_map_compat()

    def by_hand(x, w, b):
        saved = layer.weight._data, layer.bias._data
        layer.weight._data, layer.bias._data = w, b
        try:
            with partitioned_over(mesh), paddle.no_grad():
                assert mp_layers.reduce_axis(16) is None
                return layer(paddle.to_tensor(x))._data
        finally:
            layer.weight._data, layer.bias._data = saved

    fn = shard_map(by_hand, mesh=mesh, in_specs=(P(None, "mp"), P("mp", None), P()),
                   out_specs=P(), axis_names=frozenset(mesh.axis_names), **check)
    x = jnp.ones((4, 16), jnp.float32)
    jaxpr = str(jax.make_jaxpr(fn)(x, layer.weight._data, layer.bias._data))
    assert "psum" in jaxpr and "ppermute" not in jaxpr
    out = jax.jit(fn)(x, layer.weight._data, layer.bias._data)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x @ layer.weight._data + layer.bias._data), rtol=1e-5)


# sha256 of the lowered ``compile_train_step`` programs of the tiny models
# below (``.lower(...).as_text()``), recorded on commit 4a82862, the parent of
# the PR that brought the exchange: with no 'mp' axis every linear traces to
# the program it was. Llama's is the parent's lines in another ORDER
# (``products_of`` takes ``up_proj`` before ``silu(gate)``, two calls that do
# not depend on each other, where the parent took it after): its digest is of
# the sorted lines, value and function numbers taken out
PARENT_STEP = {
    "gpt": "e549417edeecac9cee33a859a69149db46e62e7551fb5097e58ed58c7e1c9951",
    "ernie": "452bc4c75c5be0e7bca928c1d8c0d5c8062258a78dcc1e022973de6c17854a70",
    "llama": "ec4ad8ba46d06966873bdc460b4f620b5e438871dfc99720f09859cfb562c37c",
}


def _digest(text, ordered):
    if not ordered:
        text = re.sub(r"%\w+(#\d+)?(:\d+)?", "%", text)
        text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
        text = "\n".join(sorted(text.split("\n")))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("arch", sorted(PARENT_STEP))
def test_without_an_mp_axis_the_step_is_the_parents_program(arch):
    paddle.seed(36)
    model = _model(arch)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, lambda m, i, l: m.loss(i, l), opt)
    ids = np.random.default_rng(36).integers(0, 256, (4, 17))
    lowered = step.lower(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    assert _digest(lowered.as_text(), ordered=arch != "llama") == PARENT_STEP[arch]
