"""Serving state durability — snapshot/restore, crash re-attach, handoff.

Pins the ISSUE-17 acceptance surface: ``PagePool.snapshot()/restore()`` is
a validated O(blocks) capture (CRC torn-detection + the conservation
``check()`` — a tampered capture is a structured ``SnapshotError``, never a
wrong pool); a supervised crash with ``snapshot=True`` RE-ATTACHES the
survivors' live KV blocks so they resume mid-decode with ZERO re-prefilled
tokens, bit-identical to an uninterrupted run (GPT and Llama/GQA, prefix
cache armed and not); a torn/corrupt capture (``serve.snapshot_corrupt``)
falls back whole to the PR 12 re-prefill path with the same bit-identity;
``Engine.handoff()`` quiesces at a step boundary and a successor adopts
queue + in-flight handles with zero downtime; and the whole layer is INERT
when unconfigured — snapshot/restore/adopt monkeypatch-exploded and never
called on the default path. Chaos-grade multi-round drives live in
tests/test_serving_chaos.py.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.fault import inject
from paddle_tpu.serving import (
    Engine, PagePool, ServeError, ServingSupervisor, SnapshotError,
    TRASH_BLOCK,
)
from serving_util import ENGINE_KW, make_prompts as _prompts, tiny_gpt

_KW = dict(ENGINE_KW)


@pytest.fixture(scope="module")
def model():
    return tiny_gpt()


@pytest.fixture(autouse=True)
def _disarm():
    yield
    inject.disarm()


def _delta(c0, name):
    return profiler.counters().get(name, 0) - c0.get(name, 0)


# ---------------------------------------------------------------- pool unit
class TestPoolSnapshot:
    def _busy_pool(self):
        pool = PagePool(16)
        a = pool.alloc(3)
        b = pool.alloc(2)
        pool.share(b)          # refcount 2: a shared prefix block pattern
        pool.park(4)
        return pool, a, b

    def test_roundtrip_preserves_every_field(self):
        pool, a, b = self._busy_pool()
        snap = pool.snapshot()
        clone = PagePool.restore(snap)
        clone.check()
        assert clone.num_blocks == pool.num_blocks
        assert clone.free_blocks == pool.free_blocks
        assert clone.parked_blocks == pool.parked_blocks
        for bid in a:
            assert clone.refcount(bid) == 1
        for bid in b:
            assert clone.refcount(bid) == 2
        # the clone is live: the shared blocks need BOTH frees
        clone.free(b)
        for bid in b:
            assert clone.refcount(bid) == 1

    def test_snapshot_is_a_capture_not_a_view(self):
        pool, a, _b = self._busy_pool()
        snap = pool.snapshot()
        pool.free(a)  # mutate the source after the capture
        clone = PagePool.restore(snap)
        for bid in a:
            assert clone.refcount(bid) == 1  # capture kept the old truth

    def test_torn_capture_rejected_by_crc(self):
        pool, _a, _b = self._busy_pool()
        snap = pool.snapshot()
        snap["free"].pop()  # tear: a field mutated after the CRC was taken
        with pytest.raises(SnapshotError, match="torn"):
            PagePool.restore(snap)

    def test_consistent_tamper_rejected_by_conservation(self):
        """A tamper that RECOMPUTES the CRC still cannot pass: the restored
        pool must satisfy the conservation check()."""
        from paddle_tpu.serving.pool import _pool_crc

        pool, a, _b = self._busy_pool()
        snap = pool.snapshot()
        snap["free"].append(a[0])  # block now both free and owned
        snap["crc"] = _pool_crc(snap["num_blocks"], snap["free"],
                                snap["ref"], snap["parked"])
        with pytest.raises(SnapshotError):
            PagePool.restore(snap)

    def test_zero_refcount_and_bad_ids_rejected(self):
        from paddle_tpu.serving.pool import _pool_crc

        pool, a, _b = self._busy_pool()
        for mutate in (
            lambda s: s["ref"].__setitem__(a[0], 0),
            lambda s: s["ref"].__setitem__(TRASH_BLOCK, 1),
            lambda s: s["ref"].__setitem__(s["num_blocks"] + 3, 1),
        ):
            snap = pool.snapshot()
            mutate(snap)
            snap["crc"] = _pool_crc(snap["num_blocks"], snap["free"],
                                    snap["ref"], snap["parked"])
            with pytest.raises(SnapshotError):
                PagePool.restore(snap)

    def test_version_and_malformed_rejected(self):
        pool, _a, _b = self._busy_pool()
        snap = pool.snapshot()
        bad = dict(snap, version=99)
        with pytest.raises(SnapshotError, match="version"):
            PagePool.restore(bad)
        with pytest.raises(SnapshotError, match="malformed"):
            PagePool.restore({"version": snap["version"], "free": object()})


# ------------------------------------------------------- crash → re-attach
class TestCrashReattach:
    def test_reattach_zero_reprefill_bit_identical(self, model):
        """THE acceptance pin: supervised crash mid-decode with snapshot
        armed — every survivor RE-ATTACHES its live KV blocks (zero tokens
        re-prefilled, zero requeues) and every greedy stream completes
        bit-identical to an uninterrupted run."""
        rng = np.random.RandomState(20)
        prompts = _prompts(6, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=300)
                        for p in prompts]
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=4")
        with ServingSupervisor(model, watchdog_s=4.0, snapshot=True,
                               **_KW) as sup:
            hs = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            last = sup.health()["last_recovery"]
            assert last["mode"] == "reattach"
            assert last["reattached"] == len(prompts)
            assert last["blocks_reattached"] > 0
            assert last["requeued"] == 0
            assert last["duration_s"] > 0.0
            assert sup.health()["ok"] and sup.ready()
            assert sup.stats()["pages_used"] == 0  # restored pool drained
        assert outs == baseline
        assert _delta(c0, "serve_reprefill_tokens") == 0, \
            "re-attach must re-prefill ZERO tokens"
        assert _delta(c0, "serve_requeued") == 0
        assert _delta(c0, "serve_reattached") == len(prompts)
        assert _delta(c0, "serve_reattached_blocks") > 0
        assert _delta(c0, "serve_reprefill_tokens_saved") > 0
        assert _delta(c0, "serve_snapshots") == 1
        assert _delta(c0, "serve_pool_restores") >= 1
        assert _delta(c0, "serve_restart_mttr_ms") > 0

    def test_reattach_llama_gqa_bit_identical(self):
        """Same pin over the Llama/GQA paged path — grouped KV heads change
        the pool geometry and the decode program, not the durability
        contract."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(3)
        cfg = LlamaConfig(vocab_size=193, hidden_size=32, num_layers=2,
                          num_heads=4, num_kv_heads=2, intermediate_size=64,
                          max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        rng = np.random.RandomState(21)
        prompts = [rng.randint(0, 193, (int(rng.randint(3, 20)),)).tolist()
                   for _ in range(6)]
        kw = dict(block_size=8, num_blocks=64, max_batch=8, max_seq_len=128)
        with Engine(m, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=600)
                        for p in prompts]
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=4")
        with ServingSupervisor(m, watchdog_s=4.0, snapshot=True,
                               **kw) as sup:
            hs = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            assert sup.health()["last_recovery"]["mode"] == "reattach"
        assert outs == baseline
        assert _delta(c0, "serve_reprefill_tokens") == 0
        assert _delta(c0, "serve_reattached_blocks") > 0

    def test_reattach_with_prefix_cache_armed(self, model):
        """Crash while streams share cached prefix blocks: the restored
        pool carries the index's own references, CoW guards, and LRU order
        — conservation holds post-restore (pages_used == pages_cached once
        drained) and the successor still serves cache hits."""
        rng = np.random.RandomState(22)
        shared = rng.randint(0, 211, (40,)).tolist()
        prompts = [shared + rng.randint(0, 211,
                                        (int(rng.randint(3, 10)),)).tolist()
                   for _ in range(8)]
        kw = dict(_KW, num_blocks=128)
        with Engine(model, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=600)
                        for p in prompts]
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=5")
        with ServingSupervisor(model, watchdog_s=4.0, snapshot=True,
                               prefix_cache=True, **kw) as sup:
            hs = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            assert sup.health()["last_recovery"]["mode"] == "reattach"
            # restored index holds its own refs; nothing else is resident
            st = sup.stats()
            assert st["pages_used"] == st["pages_cached"] > 0
            with sup._lock:
                sup._engine._pool.check()  # conservation post-restore
            # the restored chain still SERVES: a fresh wave hits the cache
            h0 = profiler.counters().get("serve_prefix_hits", 0)
            hs2 = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs2 = [h.result(timeout=600) for h in hs2]
            assert profiler.counters().get("serve_prefix_hits", 0) > h0
        assert outs == baseline and outs2 == baseline
        assert _delta(c0, "serve_reprefill_tokens") == 0

    def test_corrupt_snapshot_falls_back_whole_bit_identical(self, model):
        """serve.snapshot_corrupt tears the capture mid-write: adopt's
        validation rejects it (SnapshotError, serve_snapshot_rejected) and
        the supervisor falls back WHOLE to the PR 12 requeue/re-prefill
        path — same bit-identity, nothing half-adopted."""
        rng = np.random.RandomState(23)
        prompts = _prompts(6, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=300)
                        for p in prompts]
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=4;serve.snapshot_corrupt")
        with ServingSupervisor(model, watchdog_s=4.0, snapshot=True,
                               **_KW) as sup:
            hs = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            last = sup.health()["last_recovery"]
            assert last["mode"] == "reprefill"
            assert last["requeued"] == len(prompts)
            assert last["blocks_reattached"] == 0
        assert outs == baseline
        assert _delta(c0, "serve_snapshot_rejected") == 1
        assert _delta(c0, "serve_requeued") == len(prompts)
        assert _delta(c0, "serve_reattached_blocks") == 0
        assert _delta(c0, "serve_reprefill_tokens") > 0

    def test_mixed_running_and_queued_all_complete(self, model):
        """max_batch smaller than the load: at crash time some requests are
        mid-decode (re-attached) and some still queued (requeued fresh by
        the harvest). Every stream completes bit-identical either way."""
        rng = np.random.RandomState(24)
        prompts = _prompts(6, rng)
        kw = dict(_KW, max_batch=2)
        with Engine(model, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=300)
                        for p in prompts]
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=4")
        with ServingSupervisor(model, watchdog_s=4.0, snapshot=True,
                               **kw) as sup:
            hs = [sup.submit(p, max_new_tokens=10) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            last = sup.health()["last_recovery"]
            assert last["mode"] == "reattach"
            assert last["reattached"] + last["requeued"] == len(prompts)
            assert last["reattached"] > 0 and last["requeued"] > 0
        assert outs == baseline
        # queued requests had no prefill yet — still zero re-prefill
        assert _delta(c0, "serve_reprefill_tokens") == 0

    def test_streamed_request_reattaches_contiguously(self, model):
        """A streamed survivor keeps its ORIGINAL handle across the
        re-attach — no relay, no gap, the stream equals the uninterrupted
        generation."""
        rng = np.random.RandomState(25)
        p = rng.randint(0, 211, (6,)).tolist()
        with Engine(model, **_KW) as eng:
            ref = eng.submit(p, max_new_tokens=10).result(timeout=300)
        c0 = dict(profiler.counters())
        inject.arm("serve.crash:at=5")
        with ServingSupervisor(model, watchdog_s=4.0, snapshot=True,
                               **_KW) as sup:
            h = sup.submit(p, max_new_tokens=10, stream=True)
            got = list(h)
            assert sup.restarts == 1
        assert p + got == ref
        assert _delta(c0, "serve_relayed") == 0  # original handle, no relay
        assert _delta(c0, "serve_reprefill_tokens") == 0

    def test_wedge_never_snapshots(self, model):
        """Snapshot is CRASH-only: a wedged scheduler thread may still be
        mutating state, so the supervisor must not capture it — the wedge
        path keeps its PR 12 semantics (structural failure + requeue)."""
        rng = np.random.RandomState(26)
        c0 = dict(profiler.counters())
        with ServingSupervisor(model, watchdog_s=3.0, snapshot=True,
                               **_KW) as sup:
            sup.generate(rng.randint(0, 211, (5,)).tolist(), max_new_tokens=3)
            inject.arm("serve.wedge:at=2,ms=60000")
            h = sup.submit(rng.randint(0, 211, (5,)).tolist(),
                           max_new_tokens=50)
            with pytest.raises(ServeError, match="wedged"):
                h.result(timeout=30)
            inject.disarm()
            assert sup.restarts == 1
            assert sup.health()["last_recovery"]["mode"] != "reattach"
            assert len(sup.generate(rng.randint(0, 211, (4,)).tolist(),
                                    max_new_tokens=3)) == 7
        assert _delta(c0, "serve_snapshots") == 0


# ----------------------------------------------------------------- handoff
class TestHandoff:
    def test_handoff_mid_decode_bit_identical(self, model):
        """Zero-downtime handoff: quiesce at a step boundary, successor
        adopts snapshot + handles, survivors resume mid-decode on their
        ORIGINAL handles with zero re-prefill, outputs bit-identical."""
        rng = np.random.RandomState(30)
        prompts = _prompts(6, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=10).result(timeout=300)
                        for p in prompts]
        c0 = dict(profiler.counters())
        old = Engine(model, **_KW)
        try:
            hs = [old.submit(p, max_new_tokens=10) for p in prompts]
            # let decode get going so the handoff is genuinely mid-flight
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = old.handoff()
            with pytest.raises(ServeError):
                old.submit([1, 2], max_new_tokens=2)  # terminally stopped
            with Engine(model, **_KW) as new:
                info = new.adopt(snap)
                assert info["mode"] == "reattach"
                assert info["reattached"] > 0
                assert info["reprefill_tokens"] == 0
                outs = [h.result(timeout=600) for h in hs]
                assert new.health()["last_recovery"]["mode"] == "reattach"
                assert new.stats()["pages_used"] == 0
        finally:
            old.close()
        assert outs == baseline
        assert _delta(c0, "serve_handoffs") == 1
        assert _delta(c0, "serve_adoptions") == 1
        assert _delta(c0, "serve_reprefill_tokens") == 0

    def test_handoff_transfers_queue(self, model):
        """Queued-but-unadmitted requests ride the handoff too: the
        successor admits them from the adopted queue."""
        rng = np.random.RandomState(31)
        prompts = _prompts(4, rng)
        kw = dict(_KW, max_batch=1)
        with Engine(model, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=8).result(timeout=300)
                        for p in prompts]
        old = Engine(model, **kw)
        try:
            hs = [old.submit(p, max_new_tokens=8) for p in prompts]
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = old.handoff()
            assert snap["queue"], "nothing was queued at handoff time"
            with Engine(model, **kw) as new:
                info = new.adopt(snap)
                assert info["queued"] == len(snap["queue"])
                outs = [h.result(timeout=600) for h in hs]
        finally:
            old.close()
        assert outs == baseline

    def test_handoff_prefix_chain_survives(self, model):
        """A prefix-cache-armed handoff carries the chain: the successor's
        index serves hits immediately, and conservation holds."""
        rng = np.random.RandomState(32)
        shared = rng.randint(0, 211, (40,)).tolist()
        prompts = [shared + rng.randint(0, 211, (5,)).tolist()
                   for _ in range(6)]
        kw = dict(_KW, num_blocks=128)
        with Engine(model, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=8).result(timeout=600)
                        for p in prompts]
        old = Engine(model, prefix_cache=True, **kw)
        try:
            first = [old.submit(p, max_new_tokens=8) for p in prompts]
            outs1 = [h.result(timeout=600) for h in first]
            snap = old.handoff()
            with Engine(model, prefix_cache=True, **kw) as new:
                new.adopt(snap)
                h0 = profiler.counters().get("serve_prefix_hits", 0)
                hs = [new.submit(p, max_new_tokens=8) for p in prompts]
                outs2 = [h.result(timeout=600) for h in hs]
                assert profiler.counters().get("serve_prefix_hits", 0) > h0
                st = new.stats()
                assert st["pages_used"] == st["pages_cached"] > 0
                new._pool.check()
        finally:
            old.close()
        assert outs1 == baseline and outs2 == baseline

    def test_handoff_to_unarmed_successor_releases_index(self, model):
        """Prefix-armed predecessor, cache-OFF successor: the adopted
        chain's index references are RELEASED (not leaked) — conservation
        holds with pages_cached == 0."""
        rng = np.random.RandomState(33)
        shared = rng.randint(0, 211, (24,)).tolist()
        prompts = [shared + rng.randint(0, 211, (4,)).tolist()
                   for _ in range(4)]
        kw = dict(_KW, num_blocks=128)
        with Engine(model, **kw) as eng:
            baseline = [eng.submit(p, max_new_tokens=6).result(timeout=600)
                        for p in prompts]
        old = Engine(model, prefix_cache=True, **kw)
        try:
            [old.submit(p, max_new_tokens=6).result(timeout=600)
             for p in prompts]
            snap = old.handoff()
            with Engine(model, **kw) as new:  # cache off
                new.adopt(snap)
                outs = [new.submit(p, max_new_tokens=6).result(timeout=600)
                        for p in prompts]
                st = new.stats()
                assert st["pages_cached"] == 0 and st["pages_used"] == 0
                new._pool.check()
        finally:
            old.close()
        assert outs == baseline

    def test_handoff_crash_before_quiesce_fails_whole(self, model):
        """The engine dies before the quiesce lands: handoff() raises
        ServeError, the crash path owns the handles (structural failure,
        never a hang), and a separately-built successor is untouched."""
        rng = np.random.RandomState(34)
        old = Engine(model, **_KW)
        try:
            inject.arm("serve.crash:at=2")
            h = old.submit(rng.randint(0, 211, (5,)).tolist(),
                           max_new_tokens=50)
            deadline = time.monotonic() + 30
            while not inject.fired_counts().get("serve.crash") \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            with pytest.raises(ServeError):
                old.handoff(timeout=10.0)
            inject.disarm()
            with pytest.raises(ServeError):
                h.result(timeout=10)  # failed structurally, not stranded
            with Engine(model, **_KW) as new:
                out = new.submit(rng.randint(0, 211, (4,)).tolist(),
                                 max_new_tokens=3).result(timeout=300)
                assert len(out) == 7
        finally:
            old.close()

    def test_handoff_corrupt_snapshot_reprefill_fallback(self, model):
        """serve.snapshot_corrupt during the handoff capture: adopt's
        default fallback re-prefills every survivor whole — the handoff
        still completes bit-identical, just without the re-attach win."""
        rng = np.random.RandomState(35)
        prompts = _prompts(4, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=8).result(timeout=300)
                        for p in prompts]
        c0 = dict(profiler.counters())
        old = Engine(model, **_KW)
        try:
            hs = [old.submit(p, max_new_tokens=8) for p in prompts]
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            inject.arm("serve.snapshot_corrupt")
            snap = old.handoff()
            inject.disarm()
            with Engine(model, **_KW) as new:
                info = new.adopt(snap)
                assert info["mode"] == "reprefill"
                assert "reject_reason" in info
                outs = [h.result(timeout=600) for h in hs]
                assert new.health()["last_recovery"]["mode"] == "reprefill"
        finally:
            old.close()
        assert outs == baseline
        assert _delta(c0, "serve_snapshot_rejected") == 1

    def test_kv_content_tamper_rejected(self, model):
        """Never a wrong-KV serve: a snapshot whose KV bytes diverge from
        the captured fingerprints is rejected outright with
        fallback='raise', and falls back whole by default."""
        import jax.numpy as jnp

        rng = np.random.RandomState(36)
        prompts = _prompts(3, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=32).result(timeout=300)
                        for p in prompts]
        old = Engine(model, **_KW)
        try:
            hs = [old.submit(p, max_new_tokens=32) for p in prompts]
            deadline = time.monotonic() + 30
            while old.stats()["decode_steps"] < 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            snap = old.handoff()
            snap["kpool"] = jnp.zeros_like(snap["kpool"])  # wrong KV bytes
            with Engine(model, **_KW) as new:
                with pytest.raises(SnapshotError, match="fingerprint"):
                    new.adopt(snap, fallback="raise")
                info = new.adopt(snap)  # default: whole-state re-prefill
                assert info["mode"] == "reprefill"
                outs = [h.result(timeout=600) for h in hs]
        finally:
            old.close()
        assert outs == baseline

    def test_adopt_refuses_geometry_mismatch_and_traffic(self, model):
        """Cross-config adoption is refused (compat key), and adopt into an
        engine that already served traffic is a hard error — never a merge
        of two pools."""
        rng = np.random.RandomState(37)
        old = Engine(model, **_KW)
        try:
            old.submit(rng.randint(0, 211, (5,)).tolist(),
                       max_new_tokens=4).result(timeout=300)
            snap = old.handoff()
            with Engine(model, **dict(_KW, num_blocks=32)) as other:
                with pytest.raises(SnapshotError, match="geometry"):
                    other.adopt(snap, fallback="raise")
            with Engine(model, **_KW) as busy:
                busy.submit(rng.randint(0, 211, (4,)).tolist(),
                            max_new_tokens=2).result(timeout=300)
                with pytest.raises(ServeError, match="fresh"):
                    busy.adopt(snap)
        finally:
            old.close()


# ------------------------------------------------------------ inert tripwire
class TestInertTripwire:
    def test_unconfigured_path_never_touches_durability(self, model,
                                                        monkeypatch):
        """With FLAGS_serve_snapshot off (the default) the durability layer
        must cost NOTHING: snapshot/restore/adopt are monkeypatch-exploded
        and a full supervised crash recovery (the PR 12 path) plus plain
        traffic never call them — byte-identical behaviour, zero per-step
        overhead."""
        import paddle_tpu.serving.engine as E
        import paddle_tpu.serving.pool as P

        def boom(*a, **k):
            raise AssertionError(
                "durability machinery ran on the unconfigured path")

        monkeypatch.setattr(P.PagePool, "snapshot", boom)
        monkeypatch.setattr(P.PagePool, "restore", boom)
        monkeypatch.setattr(E.Engine, "snapshot", boom)
        monkeypatch.setattr(E.Engine, "adopt", boom)
        monkeypatch.setattr(E.Engine, "handoff", boom)
        rng = np.random.RandomState(40)
        prompts = _prompts(4, rng)
        with Engine(model, **_KW) as eng:
            baseline = [eng.submit(p, max_new_tokens=8).result(timeout=300)
                        for p in prompts]
        inject.arm("serve.crash:at=3")
        with ServingSupervisor(model, watchdog_s=4.0, **_KW) as sup:
            hs = [sup.submit(p, max_new_tokens=8) for p in prompts]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.restarts == 1
            assert sup.health()["last_recovery"]["mode"] == "reprefill"
        assert outs == baseline
