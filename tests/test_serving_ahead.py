"""The decode loop one step ahead of the host (``serving.Engine._decode``).

Step k+1 is enqueued before step k's tokens are read, its tokens fed back on
the device (``generation.feed_tokens_back``). What must hold:

* greedy outputs are the dense reference's, token for token, whatever joins
  or leaves the batch while a step is in flight (staggered admissions, rows
  that end on their budget or on an EOS value in the middle of a batch, a
  cancel, a forced eviction, buckets changing up and down), on the CPU tier's
  gather step (tiny GPT) and on the MLA arch's block-table step;
* the loop really runs ahead: every landed step was enqueued behind another
  (``serve_decode_ahead``) or started an empty pipeline, and a pipeline is
  empty only after a drain or after every row ended;
* a row that ends on EOS costs exactly one thrown-away row-step, whose token
  reaches neither ``result()`` nor the stream, and a peer that inherits its
  freed block is unharmed;
* whatever needs the true state lands the step in flight first (``_drain``):
  handoff, a crash's snapshot, shutdown, the OOM back-off; a device error
  surfaces at the step's read with the pipeline dropped; the watchdog's beat
  covers the enqueue and the read apart.
"""
import contextlib
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.fault import inject
from paddle_tpu.profiler import spans
from paddle_tpu.serving import (
    Engine, RequestCancelled, ServeError, ServingSupervisor,
)
from serving_util import ENGINE_KW, tiny_gpt


class Arch:
    """A tiny model with its dense reference: ``check`` holds an engine's
    output against it token for token."""

    def __init__(self, name):
        self.name = name
        if name == "gpt":
            self.model, self.vocab = tiny_gpt(seed=0), 211
        else:
            import test_mla_moe as T

            self.model, self._w = T.build(T.TINY)
            self._T, self.vocab = T, T.TINY["vocab_size"]

    def prompts(self, lens, seed):
        rng = np.random.RandomState(seed)
        return [rng.randint(0, self.vocab, (n,)).astype(np.int32).tolist()
                for n in lens]

    def eos_case(self, eng, length, new, seed):
        """A prompt, its greedy continuation and a token of it that first
        occurs in the MIDDLE of it: an EOS value that ends the row there."""
        for k in range(64):
            prompt, = self.prompts((length,), seed + 1000 * k)
            full = eng.submit(prompt, max_new_tokens=new).result(timeout=600)
            tail = full[length:]
            for i in range(2, new - 3):
                if tail[i] not in tail[:i]:
                    return prompt, full, tail[i], length + i
        raise AssertionError("no continuation with a fresh token in its middle")

    def check(self, prompt, out, new=None):
        """``out`` is the prompt and then the reference's greedy tokens
        (``new`` of them, where the count is known)."""
        assert out[:len(prompt)] == list(prompt)
        n = len(out) - len(prompt)
        assert n >= 1 and (new is None or n == new), (n, new)
        if self.name == "gpt":
            ref = self.model.generate(
                paddle.to_tensor(np.asarray([prompt], np.int64)),
                max_new_tokens=n, do_sample=False)
            assert out == np.asarray(ref._data)[0].tolist()
        else:
            # float32 on both sides: every served token is the reference's
            # best given the tokens before it, which is greedy decoding
            T, pad = self._T, 64
            ids = np.zeros((1, pad), np.int64)
            ids[0, :len(out) - 1] = out[:-1]
            ref = T.FAM.reference.forward_logits(T.TINY, self._w, ids, "f32")
            ref = np.asarray(ref)[0, len(prompt) - 1:len(out) - 1]
            best = ref.argmax(-1)
            gap = ref.max(-1) - ref[np.arange(n), out[len(prompt):]]
            assert gap.max() <= 1e-5, (best.tolist(), out[len(prompt):])


@pytest.fixture(scope="module", params=["gpt", "mla"])
def arch(request):
    return Arch(request.param)


@pytest.fixture(scope="module")
def gpt_model():
    return Arch("gpt")


@pytest.fixture(scope="module")
def engine(arch):
    """One engine an arch for the cases that need none of their own: its
    programs compile once."""
    with Engine(arch.model, **ENGINE_KW) as eng:
        yield eng


class Watch:
    """The decode spans and the loop's counters while a case runs."""

    def __init__(self, eng):
        self.eng, self.rows = eng, []

    def __enter__(self):
        self.s0 = self.eng.stats()
        spans.add_span_observer(self.rows.append)
        return self

    def __exit__(self, *exc):
        spans.remove_span_observer(self.rows.append)
        return False

    def delta(self, key):
        return self.eng.stats()[key] - self.s0[key]

    def steps(self):
        return [sp for sp in self.rows if sp.name == "decode_step"]

    def landed_steps(self):
        read = {sp.parent_id for sp in self.rows
                if sp.name == "decode_readback"}
        return [sp for sp in self.steps() if sp.span_id in read]

    def check_ahead(self, drains=None):
        """Every step that landed was enqueued behind another or started an
        empty pipeline; a pipeline is empty after a drain, or after a step
        that every row ended with (a tail), and no oftener."""
        steps, landed = self.steps(), self.landed_steps()
        starts = [sp for sp in steps if sp not in landed]
        tails = [sp for sp in landed
                 if not sp.attrs["ahead"] and "drain" not in sp.attrs]
        n, ahead, dr = (self.delta("decode_steps"),
                        self.delta("decode_ahead"),
                        self.delta("decode_drains"))
        assert n == len(steps) - len(starts) > 0
        assert ahead == sum(sp.attrs["ahead"] for sp in steps) == n - len(starts)
        assert len(starts) <= len(tails) + dr
        assert dr == sum("drain" in sp.attrs for sp in steps)
        if drains is not None:
            assert dr == drains
        return ahead / n


def wait_steps(eng, n, timeout=120):
    end = time.monotonic() + timeout
    while eng.stats()["decode_steps"] < n and time.monotonic() < end:
        time.sleep(0.002)


def idle(eng):
    end = time.monotonic() + 60
    while time.monotonic() < end:
        st = eng.stats()
        if not (st["running"] or st["queue_depth"] or st["preempted_waiting"]) \
                and eng._flight is None:
            return
        time.sleep(0.002)
    raise AssertionError("engine did not go idle")


# -- the loop keeps the reference's tokens ---------------------------------------
def case_staggered(arch, eng, w):
    """Rows join while a step is in flight: they are fed from the host
    (``src`` -1) beside rows fed from the device."""
    ps = arch.prompts((5, 11, 3, 17, 9, 6), 41)
    new = (30, 26, 12, 10, 8, 6)
    base = eng.stats()["decode_steps"]
    hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps[:2], new)]
    wait_steps(eng, base + 3)
    hs += [eng.submit(p, max_new_tokens=n) for p, n in zip(ps[2:5], new[2:])]
    wait_steps(eng, base + 7)
    hs.append(eng.submit(ps[5], max_new_tokens=new[5]))
    for p, n, h in zip(ps, new, hs):
        arch.check(p, h.result(timeout=600), n)
    idle(eng)
    assert w.check_ahead(drains=0) >= 0.8
    assert w.delta("decode_wasted_rows") == 0


def case_budgets(arch, eng, w):
    """Rows that end on ``max_new_tokens`` in the middle of a batch: the host
    knows without the token, and leaves them out of the next step."""
    ps = arch.prompts((7, 4, 12, 9), 42)
    new = (3, 7, 12, 20)
    hs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, new)]
    for p, n, h in zip(ps, new, hs):
        arch.check(p, h.result(timeout=600), n)
    idle(eng)
    assert w.check_ahead(drains=0) >= 0.8
    # nothing was computed for a row past its budget
    assert w.delta("decode_wasted_rows") == 0
    assert sum(sp.attrs["rows"] for sp in w.landed_steps()) \
        == sum(n - 1 for n in new)


def case_eos(arch, eng, w):
    """A row ends on an EOS value in the middle of a batch: found one step
    late, its peers untouched."""
    ps = arch.prompts((6, 9, 5), 43)
    ps[1], full, eos, first = arch.eos_case(eng, 9, 12, 43)
    arch.check(ps[1], full, 12)
    idle(eng)
    wasted0 = eng.stats()["decode_wasted_rows"]
    hs = [eng.submit(ps[0], max_new_tokens=15),
          eng.submit(ps[1], max_new_tokens=12, eos_token_id=eos),
          eng.submit(ps[2], max_new_tokens=15)]
    outs = [h.result(timeout=600) for h in hs]
    assert outs[1] == full[:first + 1]
    arch.check(ps[0], outs[0], 15)
    arch.check(ps[2], outs[2], 15)
    idle(eng)
    w.check_ahead(drains=0)
    assert eng.stats()["decode_wasted_rows"] - wasted0 == 1


def case_cancel(arch, eng, w):
    """A cancel while the row's step is in flight: that row-step is thrown
    away, what the client already has is the reference's, peers go on."""
    ps = arch.prompts((8, 5, 10), 44)
    hs = [eng.submit(p, max_new_tokens=24, stream=True) for p in ps]
    it = iter(hs[1])
    got = [next(it) for _ in range(3)]
    hs[1].cancel()
    got += list(it)
    with pytest.raises(RequestCancelled):
        hs[1].result(timeout=600)
    arch.check(ps[1], ps[1] + got)
    for i in (0, 2):
        out = hs[i].result(timeout=600)
        arch.check(ps[i], out, 24)
        assert list(hs[i]) == out[len(ps[i]):]
    idle(eng)
    w.check_ahead(drains=0)
    assert w.delta("decode_wasted_rows") <= 1
    assert eng.stats()["pages_used"] == 0


def case_buckets(arch, eng, w):
    """The batch width goes up through the buckets and down again: ``prev``
    is ``max_batch`` long whatever bucket produced it."""
    ps = arch.prompts((4, 6, 3, 5, 7, 4), 45)
    new = (44, 34, 5, 7, 9, 11)
    base = eng.stats()["decode_steps"]
    hs = [eng.submit(ps[0], max_new_tokens=new[0])]
    wait_steps(eng, base + 3)
    hs.append(eng.submit(ps[1], max_new_tokens=new[1]))
    wait_steps(eng, base + 6)
    hs += [eng.submit(p, max_new_tokens=n) for p, n in zip(ps[2:], new[2:])]
    for p, n, h in zip(ps, new, hs):
        arch.check(p, h.result(timeout=600), n)
    idle(eng)
    assert w.check_ahead(drains=0) >= 0.8
    buckets = [sp.attrs["bucket"] for sp in w.steps()]
    assert {1, 2, 8} <= set(buckets)
    peak = buckets.index(8)
    assert 1 in buckets[:peak] and 1 in buckets[peak:]


CASES = {"staggered": case_staggered, "budgets": case_budgets,
         "eos": case_eos, "cancel": case_cancel, "buckets": case_buckets}


@pytest.mark.parametrize("case", sorted(CASES))
def test_greedy_tokens_are_the_references(arch, engine, case):
    idle(engine)
    with Watch(engine) as w:
        CASES[case](arch, engine, w)
    assert engine.stats()["pages_used"] == 0
    engine._pool.check()


def test_forced_eviction_lands_the_step_in_flight_first(arch):
    """A pool too small for its rows: growth preempts a peer, which takes a
    sequence out of the running set, so the step in flight lands first (a
    drain) and the evicted row re-prefills from what it has landed."""
    ps = arch.prompts((14, 15, 13, 12), 46)
    c0 = profiler.counters()
    with Engine(arch.model, **dict(ENGINE_KW, num_blocks=12)) as eng, \
            Watch(eng) as w:
        hs = [eng.submit(p, max_new_tokens=40) for p in ps]
        outs = [h.result(timeout=900) for h in hs]
        idle(eng)
        w.check_ahead()
        assert w.delta("decode_drains") >= 1
        assert eng.stats()["pages_used"] == 0
        eng._pool.check()
    for p, out in zip(ps, outs):
        arch.check(p, out, 40)
    c1 = profiler.counters()
    assert c1.get("serve_preempted", 0) > c0.get("serve_preempted", 0)
    assert c1["serve_decode_drains"] - c0.get("serve_decode_drains", 0) >= 1


def test_eos_row_costs_one_row_step_and_its_block_is_inherited_clean(arch):
    """The EOS row is in the step enqueued behind the one that produced its
    EOS: that row-step is the cost (exactly one), its token goes nowhere, and
    the waiting request that is admitted into the blocks the row frees (the
    device runs its prefill after the dead row's last write) serves the
    reference's tokens."""
    b, c = arch.prompts((8, 20), 47)
    with Engine(arch.model, **ENGINE_KW) as eng:
        a, full, eos, first = arch.eos_case(eng, 8, 20, 47)
    freed, granted = [], {}
    # 7 blocks: A and B are admitted (1 each, 2 once they decode), C's 3
    # beside the running rows' spares are not there until A retires
    with Engine(arch.model, **dict(ENGINE_KW, num_blocks=8)) as eng:
        real_free, real_alloc = eng._pool.free, eng._pool.alloc

        def free(ids):
            freed.append(list(ids))
            return real_free(ids)

        def alloc(n):
            got = real_alloc(n)
            if got is not None:
                granted.setdefault(n, []).append(list(got))
            return got

        eng._pool.free, eng._pool.alloc = free, alloc
        c0 = profiler.counters()
        ha = eng.submit(a, max_new_tokens=20, eos_token_id=eos, stream=True)
        hb = eng.submit(b, max_new_tokens=16)
        hc = eng.submit(c, max_new_tokens=6)   # 3 blocks: waits for A's
        out_a = ha.result(timeout=600)
        out_b, out_c = hb.result(timeout=600), hc.result(timeout=600)
        idle(eng)
        st = eng.stats()
        c1 = profiler.counters()
    assert out_a == full[:first + 1]
    assert list(ha) == out_a[len(a):]           # the stream ends at the EOS
    assert st["decode_wasted_rows"] == 1
    assert c1["serve_decode_wasted_rows"] \
        - c0.get("serve_decode_wasted_rows", 0) == 1
    assert c1.get("serve_backpressure", 0) > c0.get("serve_backpressure", 0)
    a_blocks = set(freed[0])                     # A retires first
    assert a_blocks & set(granted[3][-1]), (freed, granted)
    arch.check(b, out_b, 16)
    arch.check(c, out_c, 6)


def test_sampling_rows_draw_from_a_key_a_step_beside_greedy_rows(gpt_model):
    """Only a sampling row reads the step's key: a step that has one gets a
    key of its own (its tokens are fed back on the device like any other's),
    an all-greedy step the base key as it lies on the device; the greedy peer
    of a sampling row serves the reference's tokens."""
    gpt = gpt_model
    g, s1 = gpt.prompts((7, 9), 48)
    with Engine(gpt.model, **ENGINE_KW) as eng:
        real, keys = eng._run, []

        def run(fn, params, *args, pools_first=False):
            if pools_first:
                keys.append(args[-1])
            return real(fn, params, *args, pools_first=pools_first)

        eng._run = run
        hg = eng.submit(g, max_new_tokens=30)
        hs = eng.submit(s1, max_new_tokens=12, temperature=1.5)
        out_g, out_s = hg.result(timeout=600), hs.result(timeout=600)
        idle(eng)
        base = eng._key
        assert eng.stats()["decode_ahead"] >= eng.stats()["decode_steps"] - 2
    gpt.check(g, out_g, 30)
    assert out_s[:len(s1)] == s1 and len(out_s) == len(s1) + 12
    assert all(0 <= t < gpt.vocab for t in out_s)
    own = [k for k in keys if k is not base]
    # the sampling row's 11 decode steps, each under another key; the greedy
    # row's later steps under the base key itself
    assert len(own) == 11 and len(keys) - len(own) >= 15
    assert len({tuple(np.asarray(k).tolist()) for k in own}) == 11


# -- the drains ---------------------------------------------------------------
@pytest.fixture(scope="module")
def gpt(gpt_model):
    return gpt_model


def _streams_are_results(prompts, hs, outs):
    """No token lost, none twice: each stream is its result's tail."""
    for p, h, out in zip(prompts, hs, outs):
        assert list(h) == out[len(p):]


def test_handoff_with_a_step_in_flight_loses_and_repeats_nothing(gpt):
    ps = gpt.prompts((6, 9, 4), 51)
    old = Engine(gpt.model, **ENGINE_KW)
    try:
        hs = [old.submit(p, max_new_tokens=40, stream=True) for p in ps]
        wait_steps(old, 3)
        snap = old.handoff()
        assert old.stats()["decode_drains"] == 1 and old._flight is None
        assert snap["seqs"]  # taken mid-decode
        with Engine(gpt.model, **ENGINE_KW) as new:
            info = new.adopt(snap)
            assert info["mode"] == "reattach"
            outs = [h.result(timeout=600) for h in hs]
    finally:
        old.close()
    for p, out in zip(ps, outs):
        gpt.check(p, out, 40)
    _streams_are_results(ps, hs, outs)


def test_crash_snapshot_with_a_step_in_flight_loses_and_repeats_nothing(gpt):
    """The containment path lands (or drops) the step in flight before the
    supervisor captures the dead engine: survivors re-attach at landed
    positions."""
    ps = gpt.prompts((7, 5, 10, 4), 52)
    c0 = profiler.counters()
    inject.arm("serve.crash:at=5")
    try:
        with ServingSupervisor(gpt.model, watchdog_s=4.0, snapshot=True,
                               **ENGINE_KW) as sup:
            hs = [sup.submit(p, max_new_tokens=24, stream=True) for p in ps]
            outs = [h.result(timeout=600) for h in hs]
            assert sup.health()["last_recovery"]["mode"] == "reattach"
    finally:
        inject.disarm()
    c1 = profiler.counters()
    for p, out in zip(ps, outs):
        gpt.check(p, out, 24)
    _streams_are_results(ps, hs, outs)
    assert c1["serve_decode_drains"] > c0.get("serve_decode_drains", 0)
    assert c1.get("serve_reprefill_tokens", 0) == c0.get(
        "serve_reprefill_tokens", 0)


@pytest.mark.parametrize("drain", [False, True], ids=["close", "drain"])
def test_shutdown_with_a_step_in_flight_loses_and_repeats_nothing(gpt, drain):
    ps = gpt.prompts((6, 8), 53)
    eng = Engine(gpt.model, **ENGINE_KW)
    hs = [eng.submit(p, max_new_tokens=60, stream=True) for p in ps]
    wait_steps(eng, 4)
    eng.close(drain=drain)
    assert eng._flight is None
    for p, h in zip(ps, hs):
        if drain:
            out = h.result(timeout=10)
            gpt.check(p, out, 60)
            assert list(h) == out[len(p):]
        else:
            with pytest.raises(ServeError):
                h.result(timeout=10)
            got = []
            with contextlib.suppress(ServeError):
                for t in h:
                    got.append(t)
            # what reached the client before the error is the reference's,
            # the step that was in flight included, each token once
            assert len(got) >= 4
            gpt.check(p, p + got)


def _poison_step(eng, at, msg):
    """Make the decode step enqueued as the ``at``-th fail at its read: the
    error of an asynchronous program surfaces when its output is read, after
    the step behind it was enqueued on that output."""
    real_run, real_read, n, bad = eng._run, eng._decode_readback, [0], []

    def run(fn, params, *args, pools_first=False):
        out = real_run(fn, params, *args, pools_first=pools_first)
        if pools_first:
            n[0] += 1
            if n[0] == at:
                bad.append(out[0])
        return out

    def read(*arrays):
        if bad and arrays[0] is bad[0]:
            raise RuntimeError(msg)
        return real_read(*arrays)

    eng._run, eng._decode_readback = run, read


def test_device_oom_of_the_step_in_flight_surfaces_at_its_read(gpt):
    """A RESOURCE_EXHAUSTED of step k is raised when step k is read, AFTER
    step k+1 was enqueued on its tokens: both records go, the OOM back-off
    runs on landed positions, and the rows are stepped again."""
    ps = gpt.prompts((6, 9, 4), 54)
    c0 = profiler.counters()
    with Engine(gpt.model, **ENGINE_KW) as eng:
        _poison_step(eng, 4, "RESOURCE_EXHAUSTED: out of memory while "
                             "running the decode step (injected)")
        hs = [eng.submit(p, max_new_tokens=16, stream=True) for p in ps]
        outs = [h.result(timeout=600) for h in hs]
        idle(eng)
        st = eng.stats()
        assert eng._flight is None and st["pages_used"] == 0
        eng._pool.check()
    c1 = profiler.counters()
    for p, out in zip(ps, outs):
        gpt.check(p, out, 16)
    _streams_are_results(ps, hs, outs)
    assert c1["serve_pool_shrunk"] > c0.get("serve_pool_shrunk", 0)
    assert c1.get("serve_engine_errors", 0) == c0.get("serve_engine_errors", 0)
    # the poisoned step and the one enqueued behind it never landed
    assert st["decode_steps"] >= 15


def test_other_device_error_of_the_step_in_flight_is_contained(gpt):
    """Not an exhaustion: the crash-containment path, with the in-flight
    record dropped, every handle failed and every row at a landed position."""
    ps = gpt.prompts((6, 9), 55)
    eng = Engine(gpt.model, **ENGINE_KW)
    try:
        _poison_step(eng, 3, "INTERNAL: the device halted (injected)")
        hs = [eng.submit(p, max_new_tokens=30, stream=True) for p in ps]
        for h in hs:
            with pytest.raises(ServeError):
                h.result(timeout=600)
        assert eng._flight is None and "halted" in repr(eng._broken)
        assert not eng.health()["ok"]
        for p, h in zip(ps, hs):
            got = []
            with contextlib.suppress(ServeError):
                for t in h:
                    got.append(t)
            gpt.check(p, p + got)  # first token + one landed step, at least
    finally:
        eng.close()


def test_watchdog_beat_covers_the_enqueue_and_the_read_apart(gpt, monkeypatch):
    """An enqueue and a read that each take most of the staleness limit, and
    together more than it, are two beats: no wedge is reported across an
    enqueue-then-read, and the compile grace ends with the call that
    compiled."""
    from paddle_tpu.framework import flags

    monkeypatch.setitem(flags._FLAGS, "FLAGS_serve_watchdog_s", 1.0)
    ps = gpt.prompts((6, 5), 56)
    with Engine(gpt.model, **ENGINE_KW) as eng:
        # warm the programs, so that no compile grace hides a stale beat
        for group in ([ps[0]], [ps[1]], ps):   # one row, then two
            for h in [eng.submit(p, max_new_tokens=12) for p in group]:
                h.result(timeout=600)
        idle(eng)
        assert {k[1] for k in eng._fns if k[0] == "decode"} >= {1, 2}
        real_run, real_read = eng._run, eng._decode_readback

        def run(*a, **k):
            time.sleep(0.6)
            return real_run(*a, **k)

        def read(*a):
            time.sleep(0.6)
            return real_read(*a)

        eng._run, eng._decode_readback = run, read
        seen, stop = [], threading.Event()

        def poll():
            while not stop.is_set():
                h = eng.health()
                seen.append((h["stale"], h["beat_age_s"], eng._compiling))
                time.sleep(0.02)

        t = threading.Thread(target=poll, daemon=True)
        t.start()
        try:
            hs = [eng.submit(p, max_new_tokens=4) for p in ps]
            outs = [h.result(timeout=600) for h in hs]
        finally:
            stop.set()
            t.join()
    for p, out in zip(ps, outs):
        gpt.check(p, out, 4)
    assert len(seen) > 50
    assert not any(stale for stale, _, _ in seen), max(a for _, a, _ in seen)
    assert max(age for _, age, _ in seen) > 0.5   # the sleeps were seen
    assert not any(c for _, _, c in seen)         # warm: no grace was open
