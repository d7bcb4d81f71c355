"""Structured runtime telemetry.

Covers the observability subsystem end to end:
* hot-path wiring — dispatch / lazy flush / compiled train step emit events
  and spans while a Profiler is active (reference imperative/tracer.cc:177);
* span tracer — correct ``train_step`` → ``lazy_flush`` →
  ``trace``/``donate``/``compile``/``execute`` nesting with cache hit/miss
  and donation attributes;
* scheduler — make_scheduler state transitions driving ``Profiler.step()``;
* exporters — chrome trace (merged sinks + metadata snapshot), JSON-lines
  round-trip, Prometheus text metrics;
* memory accounting — per-flush ``jax.live_arrays()`` census + peak gauge;
* flight recorder — always-on ring, crash dumps;
* overhead guard — the CLOSED profiler (flight recorder included) must not
  tax the hot dispatch loop.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import profiler
from paddle_tpu.profiler import ProfilerState, flight, make_scheduler


def _train_loop(steps=3, span_per_step=False):
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
    lossf = nn.CrossEntropyLoss()
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.RandomState(1).randint(0, 4, (4,)))
    for step in range(steps):
        if span_per_step:
            with profiler.span("train_step", step=step):
                loss = lossf(model(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                loss.item()  # materialize INSIDE the step span
        else:
            loss = lossf(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            loss.item()
    return float(loss.item())


class TestProfilerWiring:
    def test_eager_train_loop_emits_op_events(self):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop()
        p.stop()
        names = [e.name for e in profiler.events()]
        op_events = [n for n in names if n.startswith("op::")]
        assert len(op_events) > 10, f"dispatch not instrumented: {names[:20]}"
        # the lazy engine flushed at least once (loss.item materializes)
        spans = [s["name"] for s in profiler.span_events()]
        assert "lazy_flush" in spans, spans[:20]

    def test_compiled_train_step_emits_span(self):
        model = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
        step = paddle.jit.compile_train_step(
            model, lambda m, x, y: nn.functional.mse_loss(m(x), y), opt
        )
        x = paddle.to_tensor(np.zeros((2, 8), np.float32))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        with profiler.Profiler(timer_only=True):
            step(x, y)
            step(x, y)
        spans = [
            s for s in profiler.span_events()
            if s["name"] == "train_step" and s["attrs"].get("kind") == "jit"
        ]
        assert len(spans) == 2, profiler.span_events()

    def test_chrome_export_contains_named_spans(self, tmp_path):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=1)
        p.stop()
        out = tmp_path / "trace.json"
        p.export(str(out))
        trace = json.loads(out.read_text())
        events = trace["traceEvents"]
        assert len(events) >= 5
        assert all("name" in e and "dur" in e for e in events)
        assert any(e["name"].startswith("op::") for e in events)
        assert any(e.get("cat") == "span" for e in events)

    def test_summary_aggregates_and_sorts(self):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=1)
        p.stop()
        s = p.summary()
        assert "op::" in s and "calls" in s
        assert "avg_ms" in s and "min_ms" in s and "max_ms" in s
        by_calls = p.summary(sorted_by="calls").splitlines()[1:]
        counts = [int(line.split()[-5]) for line in by_calls]
        assert counts == sorted(counts, reverse=True)
        by_name = p.summary(sorted_by="name").splitlines()[1:]
        names = [line.split()[0] for line in by_name]
        assert names == sorted(names)
        with pytest.raises(ValueError, match="sorted_by"):
            p.summary(sorted_by="bogus")

    def test_disabled_profiler_records_nothing(self):
        before_ev = len(profiler.events())
        before_sp = len(profiler.span_events())
        _train_loop(steps=1)
        # session sinks untouched; the always-on flight ring still observes
        assert len(profiler.events()) == before_ev
        assert len(profiler.span_events()) == before_sp


class TestSpanTracer:
    def test_nesting_and_cache_attribution(self):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=3, span_per_step=True)
        p.stop()
        spans = profiler.span_events()
        by_id = {s["span_id"]: s for s in spans}
        steps = [s for s in spans if s["name"] == "train_step"]
        flushes = [s for s in spans if s["name"] == "lazy_flush"]
        assert len(steps) == 3 and len(flushes) >= 3
        # the per-step flushes nest under their train_step span (model-init
        # flushes, if any, legitimately sit at the root)
        nested = [
            f for f in flushes
            if by_id.get(f["parent_id"], {}).get("name") == "train_step"
        ]
        assert len(nested) >= 3, flushes
        # compile on the first (cache-miss) flush; cache hits then DISPATCH
        # the executable without blocking (async runtime; the "execute" name
        # survives only on the FLAGS_lazy_async=0 path and eager fallbacks)
        kids = [s for s in spans if s["name"] in ("compile", "execute", "dispatch")]
        assert any(s["name"] == "compile" for s in kids)
        assert any(
            s["name"] in ("dispatch", "execute") and s["attrs"].get("cache") == "hit"
            for s in kids
        )
        for s in kids:
            assert by_id[s["parent_id"]]["name"] == "lazy_flush"
        # hit/miss is recorded on the flush span itself too, and a hit's key
        # matches the miss that compiled its executable
        assert {f["attrs"]["cache"] for f in flushes} == {"hit", "miss"}
        hit = next(f for f in flushes if f["attrs"]["cache"] == "hit")
        miss_keys = {
            f["attrs"]["cache_key"] for f in flushes if f["attrs"]["cache"] == "miss"
        }
        assert hit["attrs"]["cache_key"] in miss_keys
        # the steady-state step donated its rebound param/moment buffers
        assert any(f["attrs"].get("donated_buffers", 0) > 0 for f in flushes)
        assert any(f["attrs"].get("donated_bytes", 0) > 0 for f in flushes)

    def test_trace_and_donate_child_spans(self):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=2)
        p.stop()
        spans = profiler.span_events()
        by_id = {s["span_id"]: s for s in spans}
        for name in ("trace", "donate"):
            sub = [s for s in spans if s["name"] == name]
            assert sub, f"no {name} spans in {[s['name'] for s in spans]}"
            assert all(by_id[s["parent_id"]]["name"] == "lazy_flush" for s in sub)

    def test_memory_accounting_census(self):
        p = profiler.Profiler(timer_only=True, profile_memory=True)
        p.start()
        _train_loop(steps=2)
        p.stop()
        flushes = [
            s for s in profiler.span_events() if s["name"] == "lazy_flush"
        ]
        assert flushes
        assert all("live_bytes" in f["attrs"] for f in flushes)
        assert all("delta_bytes" in f["attrs"] for f in flushes)
        stats = profiler.memory_stats()
        assert stats["peak_live_bytes"] >= stats["live_bytes"] > 0
        assert stats["censuses"] >= 2

    def test_span_records_error_attr(self):
        with pytest.raises(ValueError):
            with profiler.span("doomed"):
                raise ValueError("boom")
        sp = flight.recent_spans()[-1]
        assert sp.name == "doomed" and sp.attrs["error"] == "ValueError"


class TestProgramTracing:
    """Spans lie in the profiler's own trace, and compilation is charged to
    the span it fired under (profiler/spans.py)."""

    COMPILE_ATTRS = ("compile_trace_s", "compile_lower_s", "compile_backend_s")
    COMPILE_COUNTERS = ("compile_trace_ns", "compile_lower_ns",
                        "compile_backend_ns")

    @staticmethod
    def _fresh_jit(width):
        """A jitted function no test has compiled: nested jits inside, so a
        first call fires inner and outer trace events."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def inner(x):
            return jnp.where(x > 0, x, 0) * 2

        @jax.jit
        def outer(x):
            y = inner(x)
            for i in range(6):
                y = jnp.tanh(y) @ y + jnp.roll(y, i + width)
            return y.sum()

        return outer, jnp.ones((width, width), jnp.float32)

    def test_first_call_charges_its_span_and_a_warm_call_nothing(self):
        fn, x = self._fresh_jit(11)
        c0 = profiler.counters()
        with profiler.span("outer_step") as outer:
            with profiler.span("step", k=1) as first:
                fn(x).block_until_ready()
            c1 = profiler.counters()
            with profiler.span("step", k=2) as warm:
                fn(x).block_until_ready()
        c2 = profiler.counters()
        for a in self.COMPILE_ATTRS:
            assert first.attrs.get(a, 0) > 0, (a, first.attrs)
            assert a not in warm.attrs and a not in outer.attrs  # innermost only
        for c in self.COMPILE_COUNTERS:
            assert c1.get(c, 0) > c0.get(c, 0), c
            assert c2.get(c, 0) == c1.get(c, 0), c
        # the span carries what the counters gained, and nested traces count
        # once: the three stages fit inside the span they ran under
        assert first.attrs["compile_trace_s"] == pytest.approx(
            (c1["compile_trace_ns"] - c0.get("compile_trace_ns", 0)) / 1e9)
        assert sum(first.attrs[a] for a in self.COMPILE_ATTRS) \
            <= first.dur_ns / 1e9

    def test_compile_outside_any_span_bumps_no_counter(self):
        fn, x = self._fresh_jit(13)
        c0 = profiler.counters()
        fn(x).block_until_ready()
        c1 = profiler.counters()
        for c in self.COMPILE_COUNTERS + ("compile_cache_hits",):
            assert c1.get(c, 0) == c0.get(c, 0), c

    def test_compile_on_another_thread_is_not_charged_to_this_span(self):
        import threading

        fn, x = self._fresh_jit(17)
        with profiler.span("holder") as sp:
            t = threading.Thread(target=lambda: fn(x).block_until_ready())
            t.start()
            t.join(timeout=120)
            assert not t.is_alive()
        assert not any(a in sp.attrs for a in self.COMPILE_ATTRS), sp.attrs

    def test_cache_hit_event_counts_only_under_a_span(self):
        from paddle_tpu.profiler import spans

        c0 = profiler.counters().get("compile_cache_hits", 0)
        spans._on_compile_event("/jax/compilation_cache/cache_hits")
        assert profiler.counters().get("compile_cache_hits", 0) == c0
        with profiler.span("load") as sp:
            spans._on_compile_event("/jax/compilation_cache/cache_hits")
            spans._on_compile_event("/jax/compilation_cache/cache_misses")
        # (a miss, a program compiled and written to the cache, is charged
        # beside it since PR 40)
        assert sp.attrs == {"compile_cache_hits": 1, "compile_cache_misses": 1}
        assert profiler.counters()["compile_cache_hits"] == c0 + 1

    def test_nested_trace_events_count_once(self):
        from paddle_tpu.profiler import spans

        trace = "/jax/core/compile/jaxpr_trace_duration"
        c0 = profiler.counters().get("compile_trace_ns", 0)
        with profiler.span("tracing") as sp:
            t0 = time.perf_counter()
            time.sleep(0.002)
            spans._on_compile_duration(trace, 0.001)   # an inner jit, ended
            time.sleep(0.002)
            spans._on_compile_duration(trace, 0.0015)  # its sibling
            time.sleep(0.001)
            whole = time.perf_counter() - t0
            spans._on_compile_duration(trace, whole)   # the jit that held both
        assert sp.attrs["compile_trace_s"] == pytest.approx(whole, rel=1e-6)
        assert profiler.counters()["compile_trace_ns"] - c0 \
            == pytest.approx(whole * 1e9, rel=1e-6)

    def test_span_lies_in_the_host_plane_of_a_jax_profiler_trace(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with profiler.span("schedule", step=3) as outer:
                with profiler.span("decode_step", bucket=4, traces=(1, 2)) as sp:
                    jnp.ones((8, 8)).sum().block_until_ready()
                    sp.set(rows=2)
        finally:
            jax.profiler.stop_trace()
        assert outer.dur_ns >= sp.dur_ns > 0  # the host-clock record stands
        files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        assert files
        found = {}
        for plane in ProfileData.from_file(files[0]).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("schedule", "decode_step"):
                        found[e.name] = (e.duration_ns, dict(e.stats))
        assert set(found) == {"schedule", "decode_step"}
        # scalar attributes as they stood at exit are the event's stats (a
        # compile under the span among them); the tuple is not
        stats = found["decode_step"][1]
        assert stats["bucket"] == 4 and stats["rows"] == 2
        assert "traces" not in stats
        assert set(stats) - {"bucket", "rows"} <= set(self.COMPILE_ATTRS) \
            | {"compile_cache_hits"}
        assert found["schedule"][1] == {"step": 3}
        assert found["schedule"][0] >= found["decode_step"][0] > 0

    @pytest.mark.parametrize("fails_at", ["construct", "close"])
    def test_a_failing_annotation_does_not_fail_the_span(self, monkeypatch,
                                                         fails_at):
        from paddle_tpu.profiler import spans

        class Broken:
            @staticmethod
            def is_enabled():
                return True

            def __init__(self, name):
                if fails_at == "construct":
                    raise RuntimeError("no profiler here")

            def __enter__(self):
                return self

            def set_metadata(self, **kw):
                raise RuntimeError("no profiler here")

        monkeypatch.setattr(spans, "_annotation", Broken)
        with profiler.span("survives", k=1) as sp:
            sp.set(done=True)
        assert sp.dur_ns > 0 and sp.attrs == {"k": 1, "done": True}
        assert spans._annotation is False  # turned itself off
        with profiler.span("after") as sp2:  # and stays off, spans go on
            pass
        assert sp2._ann is None
        assert flight.recent_spans()[-1].name == "after"


class TestSetupAccount:
    """``profiler.setup_account()``: the spans that compiled and the few that
    build a process, kept where the listener charges them and made into rows
    when read. A warm step never comes near it."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    @pytest.fixture(autouse=True)
    def fresh_account(self):
        from paddle_tpu.profiler import spans

        before = list(spans._kept)
        spans._reset_account()
        yield spans
        spans._kept[:] = before

    def test_a_span_that_compiles_is_kept_once_with_its_stages(self):
        fn, x = TestProgramTracing._fresh_jit(19)
        with profiler.span("outer_step"):
            with profiler.span("step", bucket=4, note=(1, 2)) as first:
                fn(x).block_until_ready()
        rows = profiler.setup_account()
        assert [r["name"] for r in rows] == ["step"]  # once, innermost only
        row = rows[0]
        assert row["site"] is False and row["bucket"] == 4 and "note" not in row
        assert (row["t0_ns"], row["t1_ns"]) == (first.t0, first.t1)
        for stage in ("trace", "lower", "backend"):
            assert row[stage + "_s"] == first.attrs[f"compile_{stage}_s"] > 0
        # nested traces count once: the stages and the first run ARE the span
        assert row["trace_s"] + row["lower_s"] + row["backend_s"] \
            + row["first_run_s"] == pytest.approx(first.dur_ns / 1e9)
        assert not any(k.startswith("compile_") for k in row)

    def test_a_hit_and_a_miss_are_told_apart(self, fresh_account):
        spans = fresh_account
        c0 = profiler.counters()
        with profiler.span("loaded") as a:
            spans._on_compile_event(self.HIT)
            spans._on_compile_duration(
                "/jax/compilation_cache/compile_time_saved_sec", 2.5)
            spans._on_compile_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
            spans._on_compile_duration(self.BACKEND, 0.3)
        with profiler.span("compiled") as b:
            spans._on_compile_duration(self.BACKEND, 3.0)
            spans._on_compile_event(self.MISS)
        spans._on_compile_event(self.MISS)  # outside any span: nobody's
        loaded, compiled = profiler.setup_account()
        assert (loaded["cache_hits"], loaded["cache_misses"]) == (1, 0)
        assert (loaded["cache_saved_s"], loaded["cache_load_s"]) == (2.5, 0.25)
        assert (compiled["cache_hits"], compiled["cache_misses"]) == (0, 1)
        assert compiled["backend_s"] == 3.0 and a.t1 <= b.t0
        c1 = profiler.counters()
        assert c1["compile_cache_misses"] - c0.get("compile_cache_misses", 0) == 1
        assert c1["compile_cache_hits"] - c0.get("compile_cache_hits", 0) == 1

    @pytest.mark.parametrize("site", ["engine_init", "program_build", "step_text"])
    def test_a_kept_site_makes_one_row(self, fresh_account, site):
        with profiler.kept_span(site, kind="decode", text_bytes=7) as sp:
            # a site that also compiles is still ONE row
            fresh_account._on_compile_duration(self.TRACE, 0.001)
            fresh_account._on_compile_duration(self.BACKEND, 0.002)
        with profiler.kept_span(site):
            pass  # and one that compiles nothing is a row all the same
        first, second = profiler.setup_account()
        assert first["name"] == second["name"] == site
        assert first["site"] and second["site"]
        assert first["kind"] == "decode" and first["text_bytes"] == 7
        assert first["backend_s"] == 0.002 and second["backend_s"] == 0.0
        assert first["t1_ns"] == sp.t1

    def test_warm_steps_add_no_row_and_touch_no_list(self, fresh_account):
        """The guard that the hot path pays nothing: after warm-up the kept
        list is the same list of the same spans, whatever runs."""
        model = nn.Linear(6, 3)
        step = paddle.jit.compile_train_step(
            model, lambda m, x, y: nn.MSELoss()(m(x), y),
            paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=model.parameters()))
        x = paddle.to_tensor(np.ones((2, 6), np.float32))
        y = paddle.to_tensor(np.zeros((2, 3), np.float32))
        for _ in range(2):
            step(x, y)
        kept = list(fresh_account._kept)
        rows = profiler.setup_account()
        assert {"program_build", "train_step"} <= {r["name"] for r in rows}
        dropped = profiler.counters().get("setup_account_dropped", 0)
        for _ in range(25):
            step(x, y)
            with profiler.span("decode_step", bucket=2):
                with profiler.span("decode_readback"):
                    pass
        assert len(fresh_account._kept) == len(kept)
        assert all(a is b for a, b in zip(fresh_account._kept, kept))
        assert profiler.setup_account() == rows
        assert profiler.counters().get("setup_account_dropped", 0) == dropped

    def test_the_hot_path_never_names_the_account(self):
        """``Span.__enter__`` / ``__exit__`` / ``_emit`` and ``span()`` are
        the code a warm step runs: none of them knows the account exists."""
        import inspect

        from paddle_tpu.profiler import spans

        for fn in (spans.Span.__init__, spans.Span.__enter__,
                   spans.Span.__exit__, spans._emit, spans.span):
            src = inspect.getsource(fn)
            assert "_keep" not in src and "_kept" not in src, fn

    def test_the_bound_counts_what_it_drops(self, fresh_account):
        spans = fresh_account
        c0 = profiler.counters().get("setup_account_dropped", 0)
        for i in range(spans._KEPT_MAX + 3):
            with profiler.span("flush", i=i):
                spans._on_compile_duration(self.TRACE, 0.001)
                spans._on_compile_duration(self.LOWER, 0.001)
                spans._on_compile_duration(self.BACKEND, 0.001)
        with profiler.kept_span("program_build"):
            spans._on_compile_duration(self.TRACE, 0.001)
        rows = profiler.setup_account()
        assert len(rows) == len(spans._kept) == spans._KEPT_MAX == 256
        assert rows[-1]["i"] == 255  # the first 256, nothing after them
        # each span past the bound is counted once, whatever it was charged
        assert profiler.counters()["setup_account_dropped"] - c0 == 4

    def test_rows_add_up_where_kept_spans_nest(self, fresh_account):
        spans = fresh_account
        with profiler.span("train_step") as outer:
            spans._on_compile_duration(self.BACKEND, 0.001)
            with profiler.kept_span("program_build") as build:
                time.sleep(0.003)
            with profiler.span("step_compile") as inner:
                time.sleep(0.002)
                spans._on_compile_duration(self.BACKEND, 0.002)
                with profiler.kept_span("nested_deeper"):
                    pass
            time.sleep(0.001)
        rows = {r["name"]: r for r in profiler.setup_account()}
        assert list(rows) == ["train_step", "program_build", "step_compile",
                              "nested_deeper"]
        own = rows["train_step"]["first_run_s"]
        assert own == pytest.approx(
            (outer.dur_ns - build.dur_ns - inner.dur_ns) / 1e9 - 0.001)
        assert 0 < own < 0.05 and rows["program_build"]["first_run_s"] >= 0.003

    def test_import_and_parameter_init_are_counted(self):
        c0 = profiler.counters()
        assert c0["setup_import_ns"] > 0
        layer = nn.Linear(16, 8)  # a weight and a bias
        c1 = profiler.counters()
        assert c1["param_init_leaves"] - c0.get("param_init_leaves", 0) == 2
        assert c1["param_init_bytes"] - c0.get("param_init_bytes", 0) \
            == sum(int(p._data.nbytes) for p in layer.parameters())
        assert c1["param_init_ns"] > c0.get("param_init_ns", 0)
        assert c1["setup_import_ns"] == c0["setup_import_ns"]  # once a process
        # ... so a reset of the counters, which cannot count it again, keeps it
        try:
            profiler.reset_counters()
            assert profiler.counters() == {"setup_import_ns": c0["setup_import_ns"]}
        finally:
            for k, v in c1.items():
                if k != "setup_import_ns":
                    profiler.counter_inc(k, v)


class TestProgramNames:
    """The benchmark's readers tell the program's executables apart by the
    names their jitted callables give them on the device line
    (``jit_step``, ``jit_prefill``, ``jit_step_fn``;
    ``benchmark/metrics/decode_hbm_roofline.py`` matches ``^jit_step\\(``). A
    refactor that renames one fails here and not as a silent ``None`` on the
    chip."""

    @pytest.mark.parametrize("kw", [{}, {"int8": True}, {"spec_k": 2,
                                                         "drafter": "ngram"},
                                    {"prefix_cache": True}],
                             ids=["plain", "int8", "spec", "prefix"])
    def test_serving_programs_are_step_and_prefill(self, kw):
        from paddle_tpu.serving import Engine
        from serving_util import ENGINE_KW, tiny_gpt

        p = np.random.RandomState(3).randint(0, 211, (19,)).tolist()
        with Engine(tiny_gpt(), **dict(ENGINE_KW, **kw)) as eng:
            eng.submit(p, max_new_tokens=3).result(timeout=300)
            eng.submit(p, max_new_tokens=3).result(timeout=300)  # a prefix hit
            names = {key[0]: fn.__name__ for key, fn in eng._fns.items()}
        want = {"prefill": "prefill", "spec" if "spec_k" in kw else "decode": "step"}
        if "prefix_cache" in kw:
            want["prefill_tail"] = "prefill"
        assert names == want

    @pytest.mark.parametrize("entry", ["compile_train_step", "hybrid_engine"])
    def test_train_steps_jit_step_fn(self, entry):
        import jax
        from jax.sharding import Mesh

        from paddle_tpu.distributed.engine import HybridParallelEngine

        model = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=model.parameters())
        loss = lambda m, x, y: nn.functional.mse_loss(m(x), y)
        x = paddle.to_tensor(np.zeros((4, 8), np.float32))
        y = paddle.to_tensor(np.zeros((4, 4), np.float32))
        if entry == "compile_train_step":
            step = paddle.jit.compile_train_step(model, loss, opt)
            step(x, y)
        else:
            mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
            step = HybridParallelEngine(model, opt, loss, mesh=mesh)
            step.train_step(x, y)
        assert step._jit.__name__ == "step_fn"
        # and the scopes XProf groups the step's operations by; the engine's
        # step on a mesh with a 'dp' axis is one shard_map over that axis
        # (PR 30), its gradient reduces under a scope of their own
        text = step.lower(x, y).as_text(debug_info=True)
        # (the lowered text names the body of a shard_map from its own root:
        # "jit(step_fn)/shard_map" + these, joined in the compiled module)
        top = '"jit(step_fn)/' if entry == "compile_train_step" else '"'
        for scope in (top + "jvp(loss)/jit(linear)",  # ops by name
                      top + "transpose(jvp(loss))/",
                      top + "optimizer_update/"):
            assert scope in text, scope
        if entry == "hybrid_engine":
            assert '"jit(step_fn)/shard_map"' in text
            assert '"optimizer_update/dp_reduce/ppermute"' in text
        assert "jit(<lambda>)" not in text


class TestScheduler:
    def test_make_scheduler_state_sequence(self):
        sched = make_scheduler(closed=1, ready=1, record=2, skip_first=1)
        got = [sched(s) for s in range(9)]
        C, R, REC, RAR = (
            ProfilerState.CLOSED, ProfilerState.READY,
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
        )
        assert got == [C, C, R, REC, RAR, C, R, REC, RAR]

    def test_repeat_bounds_cycles(self):
        sched = make_scheduler(closed=0, ready=0, record=1, repeat=2)
        assert sched(0) == ProfilerState.RECORD_AND_RETURN
        assert sched(1) == ProfilerState.RECORD_AND_RETURN
        assert sched(2) == ProfilerState.CLOSED
        assert sched(100) == ProfilerState.CLOSED

    def test_make_scheduler_validates(self):
        with pytest.raises(ValueError):
            make_scheduler(record=0)
        with pytest.raises(ValueError):
            make_scheduler(closed=-1)

    def test_profiler_step_drives_recording_windows(self):
        traces = []
        p = profiler.Profiler(
            timer_only=True,
            scheduler=make_scheduler(closed=1, ready=1, record=2),
            on_trace_ready=lambda prof: traces.append(prof.step_num),
        )
        p.start()
        seen = []
        for _ in range(8):
            seen.append((p.current_state, profiler._enabled))
            p.step()
        p.stop()
        C, R, REC, RAR = (
            ProfilerState.CLOSED, ProfilerState.READY,
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
        )
        assert [s for s, _ in seen] == [C, R, REC, RAR, C, R, REC, RAR]
        # recording is enabled exactly for RECORD/RECORD_AND_RETURN steps
        assert [e for _, e in seen] == [
            st in (REC, RAR) for st, _ in seen
        ]
        # each completed RECORD_AND_RETURN window handed a trace over
        assert traces == [4, 8]

    def test_scheduled_window_scopes_events(self):
        p = profiler.Profiler(
            timer_only=True, scheduler=make_scheduler(closed=2, record=1)
        )
        p.start()
        assert p.current_state == ProfilerState.CLOSED
        _train_loop(steps=1)
        assert profiler.events() == [] and profiler.span_events() == []
        p.step()  # -> CLOSED
        p.step()  # -> RECORD_AND_RETURN
        _train_loop(steps=1)
        assert any(e.name.startswith("op::") for e in profiler.events())
        p.stop()


class TestExporters:
    def test_jsonl_roundtrip(self, tmp_path):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=2, span_per_step=True)
        p.stop()
        out = tmp_path / "trace.jsonl"
        p.export(str(out), format="jsonl")
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        kinds = {l["type"] for l in lines}
        assert kinds == {"span", "event", "metrics"}
        flushes = [
            l for l in lines if l["type"] == "span" and l["name"] == "lazy_flush"
        ]
        assert flushes and all("cache" in f["attrs"] for f in flushes)
        metrics = [l for l in lines if l["type"] == "metrics"][-1]
        assert metrics["counters"].get("lazy_flushes", 0) > 0
        assert "memory" in metrics and "flags" in metrics

    def test_chrome_metadata_self_describing(self, tmp_path):
        p = profiler.Profiler(timer_only=True)
        p.start()
        _train_loop(steps=1)
        p.stop()
        out = tmp_path / "trace.json"
        p.export(str(out))
        trace = json.loads(out.read_text())
        meta = trace["metadata"]
        assert meta["counters"].get("lazy_flushes", 0) > 0
        assert "FLAGS_check_nan_inf" in meta["flags"]
        assert "peak_live_bytes" in meta["memory"]

    def test_prometheus_text_format(self):
        profiler.counter_inc("lazy_flushes", 0)  # key exists
        text = profiler.export_metrics(format="prometheus")
        assert "# TYPE paddle_tpu_lazy_flushes counter" in text
        assert "# TYPE paddle_tpu_memory_peak_live_bytes gauge" in text
        for line in text.splitlines():
            if not line.startswith("#"):
                name, val = line.rsplit(" ", 1)
                float(val)  # every sample parses as a number...
                if name.startswith(("paddle_tpu_lazy", "paddle_tpu_memory_")):
                    int(val)  # ...counters and memory gauges as integers
                    # (provider lines — serving SLO histograms, drift/rate
                    # gauges — are legitimately floats)

    def test_export_metrics_json_file(self, tmp_path):
        out = tmp_path / "metrics.json"
        text = profiler.export_metrics(str(out), format="json")
        doc = json.loads(out.read_text())
        assert doc == json.loads(text)
        assert "counters" in doc and "memory" in doc

    def test_unknown_formats_raise(self, tmp_path):
        p = profiler.Profiler(timer_only=True)
        with pytest.raises(ValueError):
            p.export(str(tmp_path / "x"), format="xml")
        with pytest.raises(ValueError):
            profiler.export_metrics(format="xml")


class TestFlightRecorder:
    def test_ring_observes_without_profiler(self):
        flight.clear()
        _train_loop(steps=1)
        names = [sp.name for sp in flight.recent_spans()]
        assert "lazy_flush" in names  # always-on, profiler closed

    def test_ring_is_bounded(self):
        flight.clear()
        for i in range(flight.capacity() + 50):
            with profiler.span("tick", i=i):
                pass
        spans = flight.recent_spans()
        assert len(spans) == flight.capacity()
        assert spans[-1].attrs["i"] == flight.capacity() + 49

    def test_manual_dump_contents(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        _train_loop(steps=1)
        path = flight.dump("manual", extra={"note": "hello"})
        doc = json.loads(open(path).read())
        assert doc["reason"] == "manual" and doc["extra"]["note"] == "hello"
        assert any(s["name"] == "lazy_flush" for s in doc["recent_spans"])
        assert doc["counters"].get("lazy_flushes", 0) > 0
        assert "pending_graph" in doc and "flags" in doc
        assert flight.last_dump() == path
        assert profiler.counters().get("flight_dumps", 0) > 0

    def test_on_crash_guard_dumps(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        with pytest.raises(RuntimeError):
            with flight.on_crash():
                _train_loop(steps=1)
                raise RuntimeError("train loop died")
        doc = json.loads(open(flight.last_dump()).read())
        assert doc["reason"] == "uncaught_exception"
        assert "train loop died" in doc["extra"]["exception"]


class TestOverheadGuard:
    def test_closed_profiler_does_not_tax_dispatch(self):
        """Tier-1 tripwire: the disabled path (profiler constructed but
        CLOSED, flight recorder running) must stay within noise of no
        profiler at all on a hot record+flush loop. This guard uses
        interleaved min-of-N so CI noise
        can't fail it while a real regression (a per-op allocation, an
        unconditional census) still trips."""

        def loop(n):
            t = paddle.to_tensor(np.ones(64, np.float32))
            for _ in range(n):
                t = t + 1.0
                t.numpy()  # flush per iteration: span path included

        loop(30)  # warm the flush executable cache

        def timed():
            t0 = time.perf_counter()
            loop(50)
            return time.perf_counter() - t0

        absent = [timed() for _ in range(5)]
        p = profiler.Profiler(timer_only=True)
        p.start()
        p.stop()  # CLOSED again; session existed (flight recorder still on)
        closed = [timed() for _ in range(5)]
        assert min(closed) < min(absent) * 1.5, (absent, closed)
