"""Multi-chip serving — tensor-parallel paged decode + chunked prefill.

Pins the ISSUE-19 acceptance surface:

- ``FLAGS_serve_tp``/``EngineConfig(tp=...)`` shards attention heads, FFN
  columns, the LM head, and the KV ``PagePool`` over a ``tp`` mesh axis via
  shard_map, with every tp boundary a CONCAT-style all_gather of
  column-partitioned outputs — greedy decode must be **bit-identical** to
  the single-chip engine (GPT and Llama/GQA, the decode program built
  with the block-table kernel and with the gather, prefix cache on and off,
  engine int8 on).
- ``FLAGS_serve_prefill_chunk`` splits prompt prefill into block-multiple
  chunks interleaved one per scheduler step with the live decode batch;
  the chunked path must be bit-identical to monolithic prefill (prefix
  cache composing through the same tail program).
- ``Engine.snapshot()``'s compat key carries the tp degree + KV shard
  layout: cross-mesh adoption is a structured ``SnapshotError`` with the
  re-prefill fallback, never a silent re-shard of live KV.
- The unconfigured engine (tp unset, chunking off) takes the EXACT prior
  code path: tp builders and the chunk splitter are monkeypatch-exploded
  and never called.

Cross-feature gap (same ISSUE): preemption (evict + re-prefill) and
snapshot/adopt pinned to equal greedy tokens with the kernel decode step
(``serving_util.paged_kernel`` patches the one function that chooses it).
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.models.generation as G
from paddle_tpu import profiler
from paddle_tpu.framework import flags
from paddle_tpu.serving import Engine, ServeError, SnapshotError
from serving_util import ENGINE_KW, make_prompts, paged_kernel, tiny_gpt

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

needs2 = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="tensor-parallel serving tests need >= 2 devices")


@pytest.fixture(scope="module")
def model():
    return tiny_gpt()


def _llama_gqa():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny(num_kv_heads=2))
    m.eval()
    return m


def _run_engine(model, prompt_seed=3, n=4, max_new=8, vocab=211,
                prompts=None, flag_overrides=None, kernel=False, **kw):
    """Greedy token outputs of a fresh engine under flag + config
    overrides, its decode program the kernel step or the gather step."""
    fl = dict(flag_overrides or {})
    old = {k: flags._FLAGS.get(k) for k in fl}
    flags._FLAGS.update(fl)
    try:
        with paged_kernel(kernel), \
                Engine(model, **dict(ENGINE_KW, **kw)) as eng:
            if prompts is None:
                rng = np.random.RandomState(prompt_seed)
                prompts = [rng.randint(0, vocab, (int(rng.randint(3, 24)),))
                           .tolist() for _ in range(n)]
            handles = [eng.submit(p, max_new_tokens=max_new, temperature=0.0)
                       for p in prompts]
            return [h.result(timeout=600) for h in handles]
    finally:
        for k, v in old.items():
            if v is None:
                flags._FLAGS.pop(k, None)
            else:
                flags._FLAGS[k] = v


# ------------------------------------------------------------- tp bit-identity
@needs2
class TestTpBitIdentity:
    # tier-1 runs the two ends of the grid (plain gather and the deepest
    # compose, prefix+kernel); the mixed combos are slow-marked — same
    # contract, kept out of the tier-1 time budget
    @pytest.mark.parametrize(
        "prefix, kernel",
        [pytest.param(False, False, id="plain-gather"),
         pytest.param(False, True, id="plain-paged_kernel",
                      marks=pytest.mark.slow),
         pytest.param(True, False, id="prefix_cache-gather",
                      marks=pytest.mark.slow),
         pytest.param(True, True, id="prefix_cache-paged_kernel")])
    def test_gpt_tokens_identical(self, model, kernel, prefix):
        fl = {"FLAGS_serve_prefix_cache": prefix}
        base = _run_engine(model, flag_overrides=fl, kernel=kernel)
        tp2 = _run_engine(model, flag_overrides=fl, kernel=kernel, tp=2)
        assert base == tp2

    @pytest.mark.slow
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["gather", "paged_kernel"])
    def test_llama_gqa_tokens_identical(self, kernel):
        m = _llama_gqa()
        fl = {"FLAGS_serve_prefix_cache": True}
        base = _run_engine(m, vocab=1024, flag_overrides=fl, kernel=kernel)
        tp2 = _run_engine(m, vocab=1024, flag_overrides=fl, kernel=kernel,
                          tp=2)
        assert base == tp2

    @pytest.mark.slow
    def test_tp_composes_with_engine_int8(self, model):
        """The int8-tagged weight tree shards on its int8 bytes (per-tensor
        scales make slice-then-dequantize bitwise exact), so a quantized
        engine's tokens must not change with tp."""
        base = _run_engine(model, int8=True)
        tp2 = _run_engine(model, int8=True, tp=2)
        assert base == tp2

    def test_flag_configures_tp(self, model, monkeypatch):
        """FLAGS_serve_tp must really route to the shard_map builders."""
        called = {"n": 0}
        real = G.build_tp_paged_decode

        def spy(*a, **k):
            called["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(G, "build_tp_paged_decode", spy)
        out = _run_engine(model, flag_overrides={"FLAGS_serve_tp": 2})
        assert called["n"] >= 1
        assert out == _run_engine(model)

    def test_tp_int8_wire_is_lossy_but_serves(self, model):
        """EQuARX-style quantized collectives are opt-in and LOSSY: the
        engine must complete every stream (right lengths), with no
        bit-identity promise."""
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 211, (int(rng.randint(3, 24)),)).tolist()
                   for _ in range(4)]
        outs = _run_engine(model, prompts=prompts, tp=2, tp_int8=True)
        assert [len(o) for o in outs] == [len(p) + 8 for p in prompts]

    def test_tp_validation(self, model):
        with pytest.raises(ValueError, match="divide"):
            Engine(model, **dict(ENGINE_KW, tp=8))  # 8 does not divide H=2
        ndev = len(jax.devices())
        with pytest.raises(ValueError, match="exceeds"):
            Engine(model, **dict(ENGINE_KW, tp=2 * ndev))
        with pytest.raises(ValueError, match="speculative"):
            Engine(model, **dict(ENGINE_KW, tp=2, spec_k=2))


# ------------------------------------------------------------ chunked prefill
class TestChunkedPrefill:
    def test_chunked_bitwise_vs_monolithic(self, model):
        """Long prompts through FLAGS_serve_prefill_chunk-sized chunks land
        the same first token and the same greedy continuation as one
        monolithic prefill pass."""
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 211, (int(n),)).tolist()
                   for n in (40, 61, 17, 33, 7, 64)]
        base = _run_engine(model, prompts=prompts)
        assert _run_engine(model, prompts=prompts, prefill_chunk=8) == base

    @pytest.mark.slow
    def test_chunked_bitwise_at_wider_chunk(self, model):
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, 211, (int(n),)).tolist()
                   for n in (40, 61, 17, 33, 7, 64)]
        base = _run_engine(model, prompts=prompts)
        assert _run_engine(model, prompts=prompts, prefill_chunk=16) == base

    @pytest.mark.slow
    def test_chunked_composes_with_prefix_cache(self, model):
        """A prefix-cached tail is itself chunked (the cursor starts at the
        cached-block boundary) and must stay bit-identical."""
        rng = np.random.RandomState(12)
        stem = rng.randint(0, 211, (32,)).tolist()
        prompts = [stem + rng.randint(0, 211, (int(n),)).tolist()
                   for n in (24, 30, 5)]
        fl = {"FLAGS_serve_prefix_cache": True}
        base = _run_engine(model, prompts=prompts, flag_overrides=fl)
        chunked = _run_engine(model, prompts=prompts, flag_overrides=fl,
                              prefill_chunk=8)
        assert chunked == base
        assert profiler.counters().get("serve_prefill_chunks", 0) > 0

    @needs2
    @pytest.mark.slow
    def test_chunked_composes_with_tp(self, model):
        rng = np.random.RandomState(13)
        prompts = [rng.randint(0, 211, (int(n),)).tolist()
                   for n in (48, 9, 25)]
        base = _run_engine(model, prompts=prompts)
        assert _run_engine(model, prompts=prompts, tp=2,
                           prefill_chunk=16) == base

    def test_chunk_must_be_block_multiple(self, model):
        with pytest.raises(ValueError, match="multiple of block_size"):
            Engine(model, **dict(ENGINE_KW, prefill_chunk=12))

    def test_decode_interleaves_with_chunked_prefill(self, model):
        """The scheduler-step interleave: while a long prompt prefills
        chunk by chunk, an already-running short stream keeps producing
        tokens — its output matches an unconcurrent run (determinism), and
        the chunk counter proves the long admit really took the
        incremental path."""
        rng = np.random.RandomState(14)
        short = rng.randint(0, 211, (5,)).tolist()
        long_p = rng.randint(0, 211, (64,)).tolist()
        alone = _run_engine(model, prompts=[short], max_new=16)
        c0 = profiler.counters().get("serve_prefill_chunks", 0)
        with Engine(model, **dict(ENGINE_KW, prefill_chunk=8,
                                  prefill_batch=1)) as eng:
            h_short = eng.submit(short, max_new_tokens=16, temperature=0.0)
            # wait for the short stream to be decoding, then admit the long
            deadline = time.monotonic() + 30
            while eng.stats()["decode_steps"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            h_long = eng.submit(long_p, max_new_tokens=4, temperature=0.0)
            outs = [h_short.result(timeout=600), h_long.result(timeout=600)]
        assert outs[0] == alone[0]
        assert len(outs[1]) == len(long_p) + 4
        assert profiler.counters().get("serve_prefill_chunks", 0) >= c0 + 8


# ------------------------------------------------------- snapshot geometry
@needs2
class TestSnapshotMeshGeometry:
    def test_cross_mesh_adopt_is_structured_refusal(self, model):
        """A tp=2 snapshot's KV pool is sharded state: adopting it on a
        different mesh shape must be a SnapshotError (raise mode) or the
        whole-capture re-prefill fallback — never a silent re-shard."""
        rng = np.random.RandomState(21)
        prompts = [rng.randint(0, 211, (int(rng.randint(3, 24)),)).tolist()
                   for _ in range(4)]
        baseline = _run_engine(model, prompts=prompts, max_new=10)
        old = Engine(model, **dict(ENGINE_KW, tp=2))
        try:
            hs = [old.submit(p, max_new_tokens=10, temperature=0.0)
                  for p in prompts]
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = old.handoff()
            with Engine(model, **ENGINE_KW) as single:
                with pytest.raises(SnapshotError, match="geometry"):
                    single.adopt(snap, fallback="raise")
            with Engine(model, **ENGINE_KW) as single:
                info = single.adopt(snap)  # default: re-prefill fallback
                assert info["mode"] == "reprefill"
                assert "reject_reason" in info
                outs = [h.result(timeout=600) for h in hs]
            assert outs == baseline
        finally:
            old.close()

    @pytest.mark.slow
    def test_same_mesh_adopt_reattaches(self, model):
        """tp=2 -> tp=2 handoff stays the zero-re-prefill reattach path,
        and the sharded KV survives the move bit-identically."""
        rng = np.random.RandomState(22)
        prompts = [rng.randint(0, 211, (int(rng.randint(3, 24)),)).tolist()
                   for _ in range(4)]
        baseline = _run_engine(model, prompts=prompts, max_new=10)
        old = Engine(model, **dict(ENGINE_KW, tp=2))
        try:
            hs = [old.submit(p, max_new_tokens=10, temperature=0.0)
                  for p in prompts]
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = old.handoff()
            with Engine(model, **dict(ENGINE_KW, tp=2)) as new:
                info = new.adopt(snap)
                assert info["mode"] == "reattach"
                outs = [h.result(timeout=600) for h in hs]
            assert outs == baseline
        finally:
            old.close()


# ---------------------------------------------- paged kernel cross-feature
class TestPagedKernelCrossFeature:
    """ISSUE-19 satellite: preemption and snapshot/adopt with the kernel
    decode step."""

    PREEMPT_KW = dict(block_size=8, num_blocks=10, max_batch=4,
                      max_seq_len=72)

    def _preempt_run(self, model, kernel):
        rng = np.random.RandomState(7)
        with paged_kernel(kernel), Engine(model, **self.PREEMPT_KW) as eng:
            hs = [eng.submit(rng.randint(0, 211, (8,)).tolist(),
                             max_new_tokens=24, temperature=0.0)
                  for _ in range(4)]
            return [h.result(timeout=600) for h in hs]

    @pytest.mark.slow
    def test_preemption_bit_identical_with_kernel(self, model):
        """A pool too small for the batch forces evict + re-prefill; the
        kernel path must ride it to the same greedy tokens."""
        c0 = profiler.counters().get("serve_preempted", 0)
        base = self._preempt_run(model, False)
        assert profiler.counters().get("serve_preempted", 0) > c0, \
            "config did not actually preempt"
        kern = self._preempt_run(model, True)
        assert base == kern
        assert all(len(o) == 32 for o in base)

    @pytest.mark.slow
    def test_handoff_adopt_bit_identical_with_kernel(self, model):
        with paged_kernel(True):
            rng = np.random.RandomState(23)
            prompts = [rng.randint(0, 211,
                                   (int(rng.randint(3, 24)),)).tolist()
                       for _ in range(4)]
            with Engine(model, **ENGINE_KW) as eng:
                baseline = [eng.submit(p, max_new_tokens=10,
                                       temperature=0.0).result(timeout=600)
                            for p in prompts]
            old = Engine(model, **ENGINE_KW)
            try:
                hs = [old.submit(p, max_new_tokens=10, temperature=0.0)
                      for p in prompts]
                deadline = time.monotonic() + 30
                while old.stats()["decode_steps"] < 2 \
                        and time.monotonic() < deadline:
                    time.sleep(0.005)
                snap = old.handoff()
                with Engine(model, **ENGINE_KW) as new:
                    info = new.adopt(snap)
                    assert info["mode"] == "reattach"
                    outs = [h.result(timeout=600) for h in hs]
                assert outs == baseline
            finally:
                old.close()


# ------------------------------------------------------------ inert tripwire
class TestInertTripwire:
    def test_unconfigured_engine_never_touches_tp_or_chunking(
            self, model, monkeypatch):
        """tp unset + chunking off => the exact PR 18 code path: every
        shard_map builder and both chunk-scheduler hooks explode if
        reached, and plain traffic (prefix cache + paged kernel armed, the
        busiest prior configuration) never reaches them."""
        import paddle_tpu.serving.engine as E

        def boom(*a, **k):
            raise AssertionError(
                "tp/chunked-prefill machinery ran on the unconfigured path")

        for name in ("build_tp_paged_decode", "build_tp_paged_prefill",
                     "build_tp_paged_tail_prefill", "tp_pack_params"):
            monkeypatch.setattr(G, name, boom)
        monkeypatch.setattr(E.Engine, "_chunk_divert", boom)
        monkeypatch.setattr(E.Engine, "_chunk_step", boom)
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 211, (int(rng.randint(3, 24)),)).tolist()
                   for _ in range(4)]
        out = _run_engine(model, prompts=prompts, kernel=True,
                          flag_overrides={"FLAGS_serve_prefix_cache": True})
        assert [len(o) for o in out] == [len(p) + 8 for p in prompts]
        eng = Engine(model, **ENGINE_KW)
        try:
            assert eng.config.tp == 0
            assert eng.config.prefill_chunk == 0
            assert eng._tp == 0 and eng._chunk == 0
        finally:
            eng.close()
