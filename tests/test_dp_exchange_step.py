"""The engine's data-parallel step beside 'mp' (PR 30): one shard_map manual
over 'dp' alone, each gradient leaf exchanged with ``ppermute`` and updated
whole. Held against the replicated GSPMD step of the same engine
(``FLAGS_shard_weight_update`` off: the partitioner's all-reduce), on the
same seed, on the virtual CPU mesh. The mathematics is the same: the exchange
gives the bits of the partitioner's sum (at dp 2 a sum of two is the same in
either order) and the same bits on every replica at any dp."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.engine import HybridParallelEngine, dp_reduce_counts
from paddle_tpu.profiler import spans as _spans

pytestmark = pytest.mark.multichip

STEPS = 3


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    paddle.set_flags({"FLAGS_shard_weight_update": True,
                      "FLAGS_dp_bucket_bytes": 25 * 1024 * 1024})


def _mesh(dp, mp):
    """The mesh as ``fleet.init`` makes it (the mp layers read their degree
    from the hybrid group), or 'dp' alone."""
    if mp == 1:
        return Mesh(np.asarray(jax.devices()[:dp]), ("dp",))
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp, "pp_degree": 1,
                               "sharding_degree": 1, "sp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group().mesh


def _run(mesh, dtype, accumulate, exchange):
    """Three steps of a two-layer GPT under AdamW; returns losses, parameters,
    both moments (after ``sync_optimizer_state``), the engine and the spans."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    # a bucket cap between this model's vectors (at most 1 KiB) and its
    # matrices (8-64 KiB), as 25 MiB lies in a model of size
    paddle.set_flags({"FLAGS_shard_weight_update": exchange,
                      "FLAGS_dp_bucket_bytes": 4 * 1024})
    paddle.seed(30)
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                        max_position_embeddings=32, hidden_dropout=0.0,
                        attention_dropout=0.0)
        model = GPTForPretraining(cfg)
    finally:
        paddle.set_default_dtype(before)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                                 parameters=model.parameters())
    eng = HybridParallelEngine(model, opt, lambda m, i, l: m.loss(i, l), mesh=mesh,
                               grad_accumulate=accumulate)
    rng = np.random.default_rng(30)
    seen = []
    _spans.add_span_observer(seen.append)
    try:
        losses = []
        for _ in range(STEPS):
            ids = rng.integers(0, cfg.vocab_size, (8, 17))
            losses.append(eng.train_step(paddle.to_tensor(ids[:, :-1]),
                                         paddle.to_tensor(ids[:, 1:])))
        losses = [np.asarray(l._data, np.float32) for l in losses]
    finally:
        _spans.remove_span_observer(seen.append)
    eng.sync_optimizer_state()
    state = opt.state_dict()
    # by position: a second model's parameters get new names
    params = {i: np.asarray(p._data.astype("float32"))
              for i, p in enumerate(eng.params)}
    moments = {(i, m): np.asarray(state[f"{p.name}.{m}"]._data.astype("float32"))
               for i, p in enumerate(eng.params) for m in ("moment1", "moment2")}
    steps = [s for s in seen if s.name == "train_step"]
    return losses, params, moments, eng, steps


def _gap(ref, new):
    """Norm of the difference over the norm, all leaves as one vector."""
    return float(np.sqrt(sum(np.sum((ref[k] - new[k]) ** 2) for k in ref))
                 / np.sqrt(sum(np.sum(ref[k] ** 2) for k in ref)))


@pytest.mark.parametrize("dp,mp,dtype,accumulate", [
    pytest.param(2, 2, "float32", 1, id="dp2_mp2"),
    pytest.param(2, 2, "bfloat16", 1, id="dp2_mp2_bf16"),
    pytest.param(4, 2, "float32", 1, id="dp4_mp2"),
    pytest.param(2, 1, "float32", 1, id="dp2_alone"),
    pytest.param(2, 2, "float32", 2, id="dp2_mp2_accumulate2"),
])
def test_dp_step_is_the_replicated_step(dp, mp, dtype, accumulate):
    if len(jax.devices()) < dp * mp:
        pytest.skip(f"needs {dp * mp} devices")
    mesh = _mesh(dp, mp)
    ref = _run(mesh, dtype, accumulate, exchange=False)
    new = _run(mesh, dtype, accumulate, exchange=True)
    assert ref[3]._wus is None
    eng, steps = new[3], new[4]
    if accumulate > 1:
        # accumulation is declined by the arguments: the scan step, reduced
        # once after the scan, as configured from the start
        assert eng._wus is None
        assert all("dp_reduce_leaves" not in s.attrs for s in steps)
    else:
        assert eng._wus is not None and eng._wus.flat == (mp == 1)
        assert len(steps) == STEPS
        for s in steps:
            assert s.attrs["dp_reduce_leaves"] > 0
            assert 0 <= s.attrs["dp_reduce_async"] <= s.attrs["dp_reduce_leaves"]
        if mp > 1:
            # a matrix over the bucket cap travels alone, the leaves under it
            # stacked by shape and layout: one transfer each, once a round
            rounds = dp.bit_length() - 1
            alone = sum(1 for b in eng._wus.plan.buckets if len(b.indices) == 1
                        and b.size * b.itemsize > eng._wus.bucket_bytes)
            # four matrices a layer, the embedding, in float32 the positions
            assert alone in (9, 10)
            sent = steps[0].attrs["dp_reduce_leaves"]
            assert rounds * alone < sent < rounds * len(eng.params)
        have = profiler.counters()
        assert have["dp_reduce_leaves"] == steps[0].attrs["dp_reduce_leaves"]
        assert have["wus_enabled"] == 1
    # Two programs, two compilations: XLA fuses and orders the float sums of
    # the layers themselves differently, so the runs agree to rounding (the
    # exchange alone is the same bits: test_exchange_is_the_sum below). Held
    # over the whole model, since one element under Adam's epsilon is its
    # gradient's sign: float32 read 1.1e-06 to 2.8e-06, bfloat16 0.017 / 0.026
    tol = 0.08 if dtype == "bfloat16" else 3e-5
    for a, b in zip(ref[0], new[0]):
        np.testing.assert_allclose(a, b, rtol=tol / 10)
    for which in (1, 2):
        assert ref[which].keys() == new[which].keys() and ref[which]
        assert _gap(ref[which], new[which]) < tol
    # the optimizer's accumulators are whole again after the sync: a second
    # sync changes nothing, and a state dropped and repacked trains on
    eng.sync_optimizer_state()
    eng.invalidate_dp_state()
    # and a parameter put back whole on every device, as a restore may leave
    # it, is laid as the compiled step wants it (jit compiles a second step)
    first = eng.params[0]
    first._set_data(jax.device_put(np.asarray(first._data), NamedSharding(mesh, P())))
    ids = np.random.default_rng(31).integers(0, 256, (8, 17))
    loss = eng.train_step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    assert np.isfinite(float(loss.item()))
    assert eng.optimizer._step_count == STEPS + 1


@pytest.mark.parametrize("dp", [2, 4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_exchange_is_the_sum(dp, dtype):
    """``_exchange_mean`` against ``lax.psum`` inside one map over 'dp': the
    same bits at dp 2, and at any dp the same bits on EVERY replica (both
    partners of a round add the same two arrays), which is what keeps
    replicated parameters from drifting apart."""
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.core.compat import shard_map
    from paddle_tpu.distributed.fleet.meta_optimizers.hybrid_parallel_optimizer import (
        ShardedWeightUpdate,
    )

    if len(jax.devices()) < dp:
        pytest.skip(f"needs {dp} devices")
    mesh = Mesh(np.asarray(jax.devices()[:dp]), ("dp",))
    model = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(parameters=model.parameters())
    wus = ShardedWeightUpdate(opt, list(model.parameters()), "dp", dp, flat=False)
    g = jnp.asarray(np.random.default_rng(dp).normal(size=(dp, 64, 48)), dtype)

    def body(x):
        return wus._exchange_mean(x), lax.psum(x, "dp") / dp

    mine, psum = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                                   out_specs=(P("dp"), P("dp")), check_vma=False))(g)
    mine = np.asarray(mine.astype(jnp.float32))
    for r in range(1, dp):
        np.testing.assert_array_equal(mine[0], mine[r])
    psum = np.asarray(psum.astype(jnp.float32))
    if dp == 2:
        np.testing.assert_array_equal(mine, psum)
    else:
        np.testing.assert_allclose(mine, psum, rtol=2e-2 if dtype == "bfloat16" else 1e-6,
                                   atol=1e-2 if dtype == "bfloat16" else 1e-6)


def test_counts_read_start_done_pairs_from_scheduled_text():
    """The reader on a hand-written schedule: a pair with compute between
    hides, a pair with none does not, a synchronous reduce never does, and a
    collective outside the scope is not a gradient reduce."""
    meta = 'metadata={op_name="jit(step_fn)/shard_map/optimizer_update/dp_reduce/ppermute"}'
    text = "\n".join([
        "HloModule jit_step_fn, is_scheduled=true",
        "%fused (p: f32[4]) -> f32[4] {",
        "  %p = f32[4] parameter(0)",
        "}",
        "ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {",
        "  %a = bf16[8,8] parameter(0)",
        f"  %s.1 = (bf16[8,8], bf16[8,8]) collective-permute-start(%a), channel_id=1, {meta}",
        "  %f.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fused",
        "  %d.1 = bf16[8,8] collective-permute-done(%s.1)",
        f"  %s.2 = (bf16[8,8], bf16[8,8]) collective-permute-start(%f.1), channel_id=2, {meta}",
        "  %d.2 = bf16[8,8] collective-permute-done(%s.2)",
        f"  %r.3 = (bf16[8,8], bf16[8]) all-reduce(%d.1, %d.2), channel_id=3, {meta}",
        '  %r.4 = bf16[8,8] all-reduce(%d.2), channel_id=4, metadata={op_name="jit(step_fn)/jvp(loss)/dot"}',
        "  ROOT %o = bf16[8,8] add(%d.1, %d.2)",
        "}",
    ])
    assert dp_reduce_counts(text) == {"dp_reduce_leaves": 4, "dp_reduce_async": 1}


def test_step_text_row_carries_the_text_and_its_counts():
    """The ahead-of-time compile of the dp step is three spans the set-up
    account tells apart, and ``step_text`` (kept by name) says what reading
    the scheduled text cost and yielded: its size and the seven counts."""
    from paddle_tpu import profiler

    before = list(_spans._kept)
    _spans._reset_account()
    try:
        *_, eng, steps = _run(_mesh(2, 2), "float32", 1, True)
        rows = profiler.setup_account()
    finally:
        _spans._kept[:] = before
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    (text,), (lower,), (compile_,) = (
        by_name["step_text"], by_name["step_lower"], by_name["step_compile"])
    counts = {"dp_reduce_leaves", "dp_reduce_async", "mp_weight_exchanges",
              "mp_activation_gathers", "mp_reduce_exchanges", "mp_reduce_async",
              "mp_activation_reduces"}
    assert text["site"] and counts <= set(text)
    assert {k: text[k] for k in counts} == eng._dp_reduce
    assert text["dp_reduce_leaves"] > 0 and text["text_bytes"] > 10_000
    assert lower["trace_s"] > 0 and lower["lower_s"] > 0 and lower["backend_s"] == 0
    assert compile_["backend_s"] > 0 and compile_["trace_s"] == 0
    assert lower["t1_ns"] <= compile_["t0_ns"] <= compile_["t1_ns"] <= text["t0_ns"]
    # all three inside the first train_step, once: the later steps compile nothing
    assert steps[0].t0 <= lower["t0_ns"] and text["t1_ns"] <= steps[0].t1
    assert [r["kind"] for r in by_name["program_build"]] == ["train_step"]
