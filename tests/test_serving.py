"""Serving engine — continuous batching + paged KV cache over compiled decode.

Pins the ISSUE-11 acceptance surface: continuous-batched greedy outputs
bit-identical to sequential per-request decode (and to the dense
``generate()`` path), page-pool alloc/free invariants (no leak, no
double-free, OOM → backpressure/preemption not crash), mid-stream cancel,
compile-count ≤ bucket count on a warm cache, the int8 serving path, and the
batched-decode EOS satellite in ``models/generation.py``.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.serving import (
    Engine, PagePool, RequestCancelled, ServeError,
)
from serving_util import ENGINE_KW as _ENGINE_KW
from serving_util import make_prompts as _prompts, tiny_gpt as _tiny_gpt


@pytest.fixture(scope="module")
def model():
    return _tiny_gpt()


class TestContinuousBatching:
    def test_batched_bit_identical_to_sequential(self, model):
        rng = np.random.RandomState(0)
        prompts = _prompts(6, rng)
        with Engine(model, **_ENGINE_KW) as eng:
            handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
            batched = [h.result(timeout=300) for h in handles]
            assert eng.stats()["running"] == 0
        with Engine(model, **_ENGINE_KW) as eng:
            sequential = [
                eng.submit(p, max_new_tokens=8).result(timeout=300)
                for p in prompts
            ]
        # THE acceptance pin: continuous batching must not change a single
        # token vs serving each request alone (greedy)
        assert batched == sequential
        for p, out in zip(prompts, batched):
            assert out[:len(p)] == p and len(out) == len(p) + 8

    def test_matches_dense_generate_greedy(self, model):
        rng = np.random.RandomState(1)
        p = rng.randint(0, 211, (11,)).tolist()
        with Engine(model, **_ENGINE_KW) as eng:
            got = eng.submit(p, max_new_tokens=6).result(timeout=300)
        ref = model.generate(
            paddle.to_tensor(np.asarray([p], np.int64)),
            max_new_tokens=6, do_sample=False,
        )
        assert got == np.asarray(ref._data)[0].tolist()

    def test_eos_retires_early_and_is_respected(self, model):
        rng = np.random.RandomState(2)
        p = rng.randint(0, 211, (7,)).tolist()
        with Engine(model, **_ENGINE_KW) as eng:
            full = eng.submit(p, max_new_tokens=8).result(timeout=300)
            eos = full[len(p) + 2]  # third generated token
            out = eng.submit(p, max_new_tokens=8, eos_token_id=eos).result(
                timeout=300)
        # stops AT the eos token's FIRST occurrence, no tail beyond it
        first = full.index(eos, len(p))
        assert out == full[:first + 1]

    def test_sixty_four_concurrent_streams(self, model):
        """The load-shape acceptance floor: >= 64 in-flight streams through
        one engine, all correct prefixes, batch occupancy accounted."""
        rng = np.random.RandomState(3)
        prompts = _prompts(64, rng, lo=3, hi=16)
        with Engine(model, block_size=8, num_blocks=512, max_batch=64,
                    max_seq_len=128) as eng:
            handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
            outs = [h.result(timeout=600) for h in handles]
            st = eng.stats()
        for p, out in zip(prompts, outs):
            assert out[:len(p)] == p and len(out) == len(p) + 6
        assert st["batch_occupancy_mean"] > 0.3
        assert st["pages_used"] == 0

    def test_streaming_and_cancel_midstream(self, model):
        rng = np.random.RandomState(4)
        with Engine(model, **_ENGINE_KW) as eng:
            h = eng.submit(rng.randint(0, 211, (5,)).tolist(),
                           max_new_tokens=100, stream=True)
            got = []
            for tok in h:  # ends cleanly when the cancel lands
                got.append(tok)
                if len(got) == 3:
                    h.cancel()
            assert 3 <= len(got) < 100
            with pytest.raises(RequestCancelled):
                h.result(timeout=60)
            deadline = time.monotonic() + 30
            while eng.stats()["pages_used"] and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.stats()["pages_used"] == 0  # blocks came back
            # the engine is still healthy after the cancel
            p = rng.randint(0, 211, (4,)).tolist()
            out = eng.submit(p, max_new_tokens=3).result(timeout=300)
            assert out[:4] == p

    def test_compile_count_bounded_by_buckets_and_warm(self, model):
        rng = np.random.RandomState(5)
        # lengths spanning exactly two prefill buckets (<=8 and <=16)
        prompts = [rng.randint(0, 211, (L,)).tolist()
                   for L in (3, 5, 7, 9, 12, 15, 4, 11)]
        with Engine(model, **_ENGINE_KW) as eng:
            outs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            [h.result(timeout=300) for h in outs]
            compiles = eng.stats()["compiles"]
            t_buckets = {8, 16}
            # decode buckets possibly touched: every width <= max_batch
            max_decode_buckets = len(eng.config.decode_buckets)
            assert compiles <= len(t_buckets) + max_decode_buckets
            # warm cache: a second identical wave must compile NOTHING new
            outs = [eng.submit(p, max_new_tokens=5) for p in prompts]
            [h.result(timeout=300) for h in outs]
            assert eng.stats()["compiles"] == compiles

    def test_submit_validation(self, model):
        with Engine(model, **_ENGINE_KW) as eng:
            with pytest.raises(ValueError, match="empty"):
                eng.submit([], max_new_tokens=4)
            with pytest.raises(ValueError, match="max_seq_len"):
                eng.submit([1] * 100, max_new_tokens=100)
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit([1, 2], max_new_tokens=0)
        with pytest.raises(ServeError):
            eng.submit([1, 2], max_new_tokens=2)  # closed engine

    def test_cancel_while_queued_unblocks_immediately(self, model):
        """A cancel must not wait for a batch slot: with the engine
        saturated by long streams, a queued request's cancel resolves at the
        next scheduler step, not when admission reaches it."""
        rng = np.random.RandomState(15)
        with Engine(model, block_size=8, num_blocks=64, max_batch=2,
                    max_seq_len=128) as eng:
            hogs = [eng.submit(rng.randint(0, 211, (4,)).tolist(),
                               max_new_tokens=100) for _ in range(2)]
            queued = eng.submit(rng.randint(0, 211, (4,)).tolist(),
                                max_new_tokens=100)
            queued.cancel()
            with pytest.raises(RequestCancelled):
                queued.result(timeout=30)  # well before any hog finishes
            [h.result(timeout=600) for h in hogs]

    def test_config_object_not_mutated_and_buckets_clamped(self, model):
        from paddle_tpu.serving import EngineConfig

        cfg = EngineConfig(block_size=8, num_blocks=64, max_batch=4,
                           max_seq_len=128, decode_buckets=(128,))
        with Engine(model, config=cfg) as eng:
            # oversized bucket clamped away; ceiling always present
            assert eng.config.decode_buckets == (4,)
            out = eng.submit([1, 2, 3], max_new_tokens=3).result(timeout=300)
            assert len(out) == 6
        # the caller's config object is untouched (reusable across engines)
        assert cfg.decode_buckets == (128,) and cfg.num_blocks == 64
        with pytest.raises(ValueError, match="not both"):
            Engine(model, config=cfg, block_size=16)


class TestPagedPool:
    def test_alloc_free_invariants(self):
        pool = PagePool(8)
        ids = pool.alloc(3)
        assert len(ids) == 3 and pool.used_blocks == 3
        assert 0 not in ids  # trash block never circulates
        assert pool.alloc(5) is None  # 4 free: backpressure, not partial
        pool.free(ids)
        assert pool.free_blocks == 7
        with pytest.raises(RuntimeError, match="double-free"):
            pool.free([ids[0]])
        pool.check()

    def test_oom_is_backpressure_then_completes(self, model):
        rng = np.random.RandomState(6)
        c0 = profiler.counters().get("serve_backpressure", 0)
        # 11 usable blocks of 8 = 88 cache slots; 6 requests of 16+24=40
        # slots each can never fit together → queueing + preemption
        with Engine(model, block_size=8, num_blocks=12, max_batch=8,
                    max_seq_len=88) as eng:
            hs = [eng.submit(rng.randint(0, 211, (16,)).tolist(),
                             max_new_tokens=24) for _ in range(6)]
            outs = [h.result(timeout=600) for h in hs]
            eng._pool.check()
            assert eng.stats()["pages_used"] == 0
        assert all(len(o) == 40 for o in outs)
        assert profiler.counters().get("serve_backpressure", 0) > c0

    def test_preempted_sequence_completes_full_length(self, model):
        """Eviction requeues accumulated state for re-prefill — the stream
        survives preemption end to end."""
        rng = np.random.RandomState(7)
        c0 = profiler.counters().get("serve_preempted", 0)
        with Engine(model, block_size=8, num_blocks=10, max_batch=4,
                    max_seq_len=72) as eng:
            hs = [eng.submit(rng.randint(0, 211, (8,)).tolist(),
                             max_new_tokens=24) for _ in range(4)]
            outs = [h.result(timeout=600) for h in hs]
        assert all(len(o) == 32 for o in outs)
        assert profiler.counters().get("serve_preempted", 0) >= c0


class TestInt8Serving:
    def test_int8_batched_bit_identical_to_sequential(self, model):
        rng = np.random.RandomState(8)
        prompts = _prompts(4, rng)
        kw = dict(_ENGINE_KW, int8=True)
        with Engine(model, **kw) as eng:
            batched = [h.result(timeout=300) for h in
                       [eng.submit(p, max_new_tokens=6) for p in prompts]]
        with Engine(model, **kw) as eng:
            sequential = [eng.submit(p, max_new_tokens=6).result(timeout=300)
                          for p in prompts]
        assert batched == sequential

    def test_int8_logits_within_ptq_tolerance(self, model):
        rng = np.random.RandomState(9)
        p = rng.randint(0, 211, (9,)).tolist()
        with Engine(model, **dict(_ENGINE_KW, int8=True)) as eng:
            l8 = eng._debug_prefill_logits(p)
        with Engine(model, **_ENGINE_KW) as eng:
            lf = eng._debug_prefill_logits(p)
        rel = float(np.abs(l8 - lf).max() / (np.abs(lf).max() + 1e-6))
        assert rel < 0.12, f"int8 serving drift {rel:.3f}"


class TestServingTelemetry:
    def test_spans_and_counters(self, model):
        rng = np.random.RandomState(10)
        c0 = profiler.counters()
        with profiler.Profiler() as prof:
            with Engine(model, **_ENGINE_KW) as eng:
                hs = [eng.submit(p, max_new_tokens=4)
                      for p in _prompts(3, rng)]
                [h.result(timeout=300) for h in hs]
            names = {s["name"] for s in profiler.span_events()}
        del prof
        assert {"schedule", "admit", "prefill", "decode_step", "decode_build",
                "decode_readback", "decode_land", "prefill_readback",
                "prefill_land"} <= names
        c1 = profiler.counters()
        for k in ("serve_requests", "serve_admitted", "serve_retired",
                  "serve_prefills", "serve_decode_steps", "serve_tokens",
                  "serve_compiles", "serve_pages_allocated",
                  "serve_pages_freed", "serve_occupancy_live",
                  "serve_occupancy_slots"):
            assert c1.get(k, 0) > c0.get(k, 0), k
        assert c1.get("serve_pages_allocated") is not None

    @staticmethod
    def _spans_of(model, n_requests=3, max_new=6, seed=12, **kw):
        """Every span the program finished while one engine served a few
        requests (the public observer hook; Span objects, attrs final)."""
        from paddle_tpu.profiler import spans

        rng = np.random.RandomState(seed)
        rows = []
        spans.add_span_observer(rows.append)
        try:
            with Engine(model, **dict(_ENGINE_KW, **kw)) as eng:
                hs = [eng.submit(p, max_new_tokens=max_new)
                      for p in _prompts(n_requests, rng)]
                outs = [h.result(timeout=300) for h in hs]
        finally:
            spans.remove_span_observer(rows.append)
        assert all(len(o) for o in outs)
        return rows

    @pytest.mark.parametrize("kw", [{}, {"spec_k": 2, "drafter": "ngram"},
                                    {"prefix_cache": True},
                                    {"prefill_chunk": 8}],
                             ids=["plain", "spec", "prefix", "chunked"])
    def test_phase_spans_nest_where_the_work_happens(self, model, kw):
        rows = self._spans_of(model, **kw)
        by_id = {sp.span_id: sp for sp in rows}
        parents = {}
        for sp in rows:
            up = by_id.get(sp.parent_id)
            parents.setdefault(sp.name, set()).add(up.name if up else None)
        assert parents["decode_build"] == {"schedule"}
        assert parents["decode_step"] == {"schedule"}
        assert parents["decode_readback"] == {"decode_step"}
        assert parents["decode_land"] == {"decode_step"}
        assert parents["prefill"] == {"schedule"}
        assert parents["prefill_readback"] == {"prefill"}
        assert parents["prefill_land"] == {"prefill"}
        assert "page_alloc" not in parents  # its count rides decode_build
        n = {name: sum(sp.name == name for sp in rows) for name in parents}
        # the plain loop runs one step ahead: the iteration that finds
        # nothing in flight only enqueues, and its decode_step lands nothing
        landed = {sp.parent_id for sp in rows if sp.name == "decode_readback"}
        starts = [sp for sp in rows if sp.name == "decode_step"
                  and sp.span_id not in landed]
        assert all(sp.attrs["ahead"] == 0 for sp in starts)
        assert len(starts) == (0 if "spec_k" in kw else 1)
        assert n["decode_build"] == n["decode_step"]
        assert n["decode_readback"] == n["decode_land"] \
            == n["decode_step"] - len(starts) > 0
        assert n["prefill_readback"] == n["prefill_land"] <= n["prefill"]
        for sp in rows:
            if sp.name == "decode_build":
                # (a build that finds every row ending with the step in
                # flight has no step to size)
                assert {"rows", "blocks_grown"} <= set(sp.attrs)
                assert ("bucket" in sp.attrs) == (sp.attrs["rows"] > 0)
            elif sp.name == "decode_step":
                assert {"rows", "bucket", "step"} <= set(sp.attrs)
            elif sp.name == "decode_land":
                assert sp.attrs["tokens"] >= 1 and sp.attrs["retired"] >= 0
        # every block the steps mapped and every token they landed is counted
        lands = [sp for sp in rows if sp.name == "decode_land"]
        assert sum(sp.attrs["retired"] for sp in lands) <= 3
        assert sum(sp.attrs["tokens"] for sp in lands) == 3 * (6 - 1)
        assert sum(sp.attrs["blocks_grown"] for sp in rows
                   if sp.name == "decode_build") >= 1

    def test_build_and_step_cover_the_scheduler_step(self, model):
        """``decode_build`` and ``decode_step`` (with ``admit`` and
        ``prefill``) leave of ``schedule`` a small unnamed rest, and
        readback + land + the step's own time ARE the step."""
        rows = self._spans_of(model, n_requests=4, max_new=24)
        kids = {}
        for sp in rows:
            kids.setdefault(sp.parent_id, []).append(sp)
        rest, steps = [], 0
        for sched in (sp for sp in rows if sp.name == "schedule"):
            mine = kids.get(sched.span_id, [])
            if not any(k.name == "decode_step" for k in mine):
                continue
            if any("compile_backend_s" in k.attrs for k in mine):
                continue  # a step that compiled says nothing of a warm one
            steps += 1
            assert all(sched.t0 <= k.t0 and k.t1 <= sched.t1 for k in mine)
            rest.append((sched.dur_ns - sum(k.dur_ns for k in mine))
                        / sched.dur_ns)
        assert steps >= 10
        assert sorted(rest)[len(rest) // 2] < 0.25, sorted(rest)
        for step in (sp for sp in rows if sp.name == "decode_step"):
            # (a step that builds a program also holds its ``program_build``)
            inner = [k for k in kids.get(step.span_id, [])
                     if k.name != "program_build"]
            if not inner:  # nothing was in flight: the step is only enqueued
                assert step.attrs["ahead"] == 0
                continue
            assert [k.name for k in sorted(inner, key=lambda k: k.t0)] \
                == ["decode_readback", "decode_land"]
            assert 0 <= step.dur_ns - sum(k.dur_ns for k in inner)

    def test_first_step_of_a_program_carries_its_compile(self, model):
        """The ``prefill`` / ``decode_step`` span a program was built under
        carries the compile stages (named with its bucket); the warm steps
        of the same program carry none."""
        rows = self._spans_of(model, n_requests=1, max_new=12, seed=13)
        stages = ("compile_trace_s", "compile_lower_s", "compile_backend_s")
        for name in ("decode_step", "prefill"):
            mine = [sp for sp in rows if sp.name == name]
            built = [sp for sp in mine if any(a in sp.attrs for a in stages)]
            # the first of a kind builds its program; a later one only when
            # its bucket (or the bucket's gather width) is new; and a
            # compile is whole: all three stages or none
            assert mine[0] in built
            assert all(sp.attrs.get(a, 0) > 0 for sp in built for a in stages)
            assert len(built) <= 3, [sp.attrs for sp in built]
            if name == "decode_step":
                # the program built is the one the span ENQUEUES, a step
                # ahead of the one it lands: named by its own bucket
                assert all((sp in built) == ("compiled_bucket" in sp.attrs)
                           for sp in mine)
        assert len([sp for sp in rows if sp.name == "decode_step"]) >= 8
        # nothing of a compile lands on a span that only waits or lands
        for sp in rows:
            if sp.name in ("decode_land", "prefill_land", "admit"):
                assert not any(a in sp.attrs for a in stages), sp

    def test_stats_programs_is_the_cold_start_report(self, model):
        """``stats()["programs"]``: one row a (kind, bucket) this engine
        built, with its build, the stages of its first call and whether the
        persistent cache served it; the set-up account holds ``engine_init``
        with the pool it made and a ``program_build`` a program."""
        from paddle_tpu import profiler
        from paddle_tpu.profiler import spans

        before = list(spans._kept)
        spans._reset_account()
        rng = np.random.RandomState(14)
        try:
            with Engine(model, **_ENGINE_KW) as eng:
                for p in _prompts(3, rng):
                    eng.submit(p, max_new_tokens=6).result(timeout=300)
                st = eng.stats()
                compiles = profiler.counters()["serve_compiles"]
                for p in _prompts(3, rng):  # a warm wave: nothing new
                    eng.submit(p, max_new_tokens=6).result(timeout=300)
                assert eng.stats()["programs"] == st["programs"]
                assert profiler.counters()["serve_compiles"] == compiles
            account = profiler.setup_account()
        finally:
            spans._kept[:] = before
        rows = st["programs"]
        keys = [(r["kind"], *r["bucket"]) for r in rows]
        # (a gather-width upgrade REPLACES a bucket's program in the engine;
        # the report keeps the one it replaced: it was paid for)
        assert len(set(keys)) == len(keys) >= st["compiles"] >= 2
        assert set(eng._fns) <= set(keys)
        assert {"prefill", "decode"} == {r["kind"] for r in rows}
        for r in rows:
            assert r["span"] == ("prefill" if r["kind"] == "prefill"
                                 else "decode_step")
            assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
            assert r["build_s"] >= 0 and r["first_run_s"] >= 0
            assert r["cache_hits"] + r["cache_misses"] <= 1
        # the account: the engine's own row first, then a build a program
        # under the step that compiled it
        init = next(r for r in account if r["name"] == "engine_init")
        assert init["site"] and init["pool_blocks"] == _ENGINE_KW["num_blocks"]
        assert init["pool_bytes"] > 0 and init["params_bytes"] > 0
        assert init["row_slots"] == 0
        assert any(r["name"] == "pool_alloc" and r["t0_ns"] >= init["t0_ns"]
                   and r["t1_ns"] <= init["t1_ns"] for r in account)
        builds = [r for r in account if r["name"] == "program_build"]
        assert sorted((b["kind"], b.get("bucket_b", b.get("bucket")),
                       b.get("bucket_t", b.get("width"))) for b in builds) \
            == sorted(keys)
        compiled = [r for r in account if r["backend_s"] > 0 and not r["site"]]
        assert len(compiled) == len(rows)

    def test_flight_context_provider_carries_request_table(self, model):
        from paddle_tpu.profiler import flight

        rng = np.random.RandomState(11)
        with Engine(model, **_ENGINE_KW) as eng:
            h = eng.submit(rng.randint(0, 211, (5,)).tolist(),
                           max_new_tokens=64)
            path = flight.dump("serving_test_probe")
            h.result(timeout=300)
        assert path is not None
        import json

        doc = json.load(open(path))
        serving = [v for k, v in doc["context"].items()
                   if k.startswith("serving_")]
        assert serving, "no serving context provider in the dump"
        assert "queue_depth" in serving[0] and "pages" in serving[0]
        # provider unregistered at close: a fresh dump carries no live table
        path2 = flight.dump("serving_test_probe2")
        doc2 = json.load(open(path2))
        assert all(not k.startswith(f"serving_{eng._provider}")
                   for k in doc2["context"])


class TestLlamaServing:
    def test_llama_paged_matches_sequential_and_generate(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        paddle.seed(0)
        cfg = llama_tiny(num_kv_heads=2)  # GQA through the paged read
        m = LlamaForCausalLM(cfg)
        m.eval()
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, cfg.vocab_size, (L,)).tolist()
                   for L in (4, 9, 6)]
        kw = dict(block_size=8, num_blocks=64, max_batch=4,
                  max_seq_len=min(64, cfg.max_position_embeddings))
        with Engine(m, **kw) as eng:
            batched = [h.result(timeout=300) for h in
                       [eng.submit(p, max_new_tokens=4) for p in prompts]]
        with Engine(m, **kw) as eng:
            sequential = [eng.submit(p, max_new_tokens=4).result(timeout=300)
                          for p in prompts]
        assert batched == sequential
        from paddle_tpu.models.generation import generate_llama

        ref = generate_llama(
            m, paddle.to_tensor(np.asarray([prompts[1]], np.int64)),
            max_new_tokens=4, do_sample=False,
        )
        assert batched[1] == np.asarray(ref._data)[0].tolist()


def _seam_model(which):
    if which == "gpt":
        return _tiny_gpt(seed=0), 211
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    cfg = llama_tiny(num_kv_heads=2)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg.vocab_size


class TestLayerSeam:
    """An arch states its layer once (``qkv`` / ``finish``) and the programs
    differ only in how they read the context between the two: the equalities
    that five copies of a layer used to hold by hand."""

    @pytest.mark.parametrize("which", ["gpt", "llama_gqa"])
    def test_reads_agree_across_programs(self, which):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.models.generation as G

        m, vocab = _seam_model(which)
        _, arch, params, _ = m.decode_state()
        B, BS, MB, NB, T = 3, 8, 4, 32, 16
        L = len(params["layers"])
        rng = np.random.RandomState(4)
        ids = jnp.asarray(rng.randint(0, vocab, (B, T)), jnp.int32)
        lens = jnp.asarray([16, 13, 9], jnp.int32)
        tables = jnp.asarray(1 + np.arange(B * MB).reshape(B, MB), jnp.int32)
        empty = tuple(jnp.zeros((L, NB, BS) + row, jnp.float32)
                      for row in G.cache_row_shapes(arch))
        whole = jax.jit(G.build_paged_prefill(arch, B, T, BS, MB))
        kpool, vpool, logits = whole(params, ids, lens, tables, *empty)

        # tail prefill of the second half over a prefilled first half: the
        # context read at T = 8 against the causal read of the whole prompt
        half = jax.jit(G.build_paged_prefill(arch, B, BS, BS, MB))
        tail = jax.jit(G.build_paged_tail_prefill(arch, B, T - BS, BS, MB))
        first = jnp.full((B,), BS, jnp.int32)
        k1, v1, _ = half(params, ids[:, :BS], first, tables, *empty)
        k2, v2, tail_logits = tail(params, ids[:, BS:], first, lens - BS,
                                   tables, k1, v1)
        assert np.abs(np.asarray(tail_logits) - np.asarray(logits)).max() <= 1e-5
        blk = int(tables[0, 1])  # row 0's second block: all tail, all real
        for got, want in ((k2, kpool), (v2, vpool)):
            assert np.abs(np.asarray(got)[:, blk]).max() > 0
            assert np.abs(np.asarray(got)[:, blk]
                          - np.asarray(want)[:, blk]).max() <= 1e-5

        # one token a row: the gather decode step against the verify step
        # fed no draft (k = 0), the context read at T = 1 through another
        # builder. Tokens and pools bit for bit, 8 steps on.
        step = jax.jit(G.build_paged_decode(arch, B, BS, MB))
        verify = jax.jit(G.build_paged_spec_decode(arch, B, 0, BS, MB))
        temps, key = jnp.zeros((B,), jnp.float32), jax.random.PRNGKey(0)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pools_a = pools_b = (kpool, vpool)
        for i in range(8):
            pos = lens + i
            *pools_a, nxt = step(params, *pools_a, tables, pos, toks, temps, key)
            *pools_b, greedy, _ = verify(params, *pools_b, tables, pos,
                                         toks[:, None], temps, key)
            assert np.array_equal(np.asarray(nxt), np.asarray(greedy)[:, 0]), i
            for a, b in zip(pools_a, pools_b):
                assert np.array_equal(np.asarray(a), np.asarray(b)), i
            toks = nxt

    def test_the_model_says_what_serves_it(self):
        from paddle_tpu.models.gpt import GPTForPretraining
        from paddle_tpu.models.llama import LlamaForCausalLM
        from paddle_tpu.models.mla_moe import MLAMoEForCausalLM

        with pytest.raises(TypeError) as err:
            Engine(object())
        for cls in (GPTForPretraining, LlamaForCausalLM, MLAMoEForCausalLM):
            assert cls.__name__ in str(err.value)
            assert callable(cls.decode_state)


class TestGenerateEosSatellite:
    """models/generation.py satellite: per-sequence EOS handling in batched
    decode — frozen finished rows, eos-padded tails, early loop exit —
    pinned bit-for-bit against single-sequence decode."""

    def _model(self):
        return _tiny_gpt(seed=3)

    def test_batched_rows_bitwise_equal_single_sequence(self):
        from paddle_tpu.models import generation as G

        m = self._model()
        rng = np.random.RandomState(13)
        prompt = rng.randint(0, 211, (3, 6))
        # an eos one row actually emits, so the batch mixes finished+live
        probe = m.generate(paddle.to_tensor(prompt[:1]), max_new_tokens=6,
                           do_sample=False)
        eos = int(np.asarray(probe._data)[0, 8])
        batched = m.generate(paddle.to_tensor(prompt), max_new_tokens=6,
                             do_sample=False, eos_token_id=eos)
        for r in range(3):
            single = m.generate(paddle.to_tensor(prompt[r:r + 1]),
                                max_new_tokens=6, do_sample=False,
                                eos_token_id=eos)
            np.testing.assert_array_equal(
                np.asarray(batched._data)[r], np.asarray(single._data)[0],
            )
        assert G.last_decode_steps() <= 6

    def test_early_exit_stops_burning_steps(self):
        from paddle_tpu.models import generation as G

        m = self._model()
        rng = np.random.RandomState(14)
        prompt = paddle.to_tensor(rng.randint(0, 211, (1, 6)))
        probe = m.generate(prompt, max_new_tokens=40, do_sample=False)
        first = int(np.asarray(probe._data)[0, 6])
        assert G.last_decode_steps() == 40  # no eos: full budget
        out = m.generate(prompt, max_new_tokens=40, do_sample=False,
                         eos_token_id=first)
        # the very first generated token is eos → ONE step, not 40
        assert G.last_decode_steps() == 1
        row = np.asarray(out._data)[0]
        assert (row[6:] == first).all()  # tail is eos-padded, never garbage
