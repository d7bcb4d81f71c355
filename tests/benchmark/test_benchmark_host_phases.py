"""The readers of PR 24's per-layer metrics, on hand-made span rows: the
decode step's host phases, the scheduler's unnamed rest, and the program's
own count of tracing and lowering."""
import pytest

from benchmark.manifest import Manifest
from benchmark.monitor import Spans

MS = 1_000_000
NEW = ("decode_host_ms.build", "decode_host_ms.dispatch",
       "decode_host_ms.readback", "decode_host_ms.land", "schedule_self_ms",
       "setup_trace_s")


def spans_of(rows):
    s = Spans()
    s.close()  # no observer: the rows are ours
    s.rows = [(name, int(t0 * MS), int(t1 * MS), tid, attrs)
              for name, t0, t1, tid, attrs in rows]
    return s


def step(at, tid=0, build=1.0, dispatch=2.0, readback=90.0, land=3.0, rest=0.5):
    """One scheduler step from ``at`` ms, as the engine's spans nest: rows
    in order of FINISH, as an observer receives them."""
    b0 = at + rest / 2
    s0 = b0 + build
    r0 = s0 + dispatch
    l0 = r0 + readback
    end = l0 + land
    return [("decode_build", b0, s0, tid, {"rows": 64}),
            ("decode_readback", r0, l0, tid, {}),
            ("decode_land", l0, end, tid, {"tokens": 64}),
            ("decode_step", s0, end, tid, {"rows": 64, "bucket": 64}),
            ("schedule", at, end + rest / 2, tid, {})]


def run_of(rows, window=(0.0, 1e6)):
    return {"spans": spans_of(rows),
            "span_window_ns": (int(window[0] * MS), int(window[1] * MS))}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


def test_manifest_is_sound_with_the_new_entries(manifest):
    assert manifest.validate() == []
    serve = [m["name"] for m in manifest.metrics_of("serve-xl-chat-sat", "per_layer")]
    assert set(NEW) <= set(serve)
    for cell in ("train-xl-s2048", "train-hybrid-4chip"):
        mine = [m["name"] for m in manifest.metrics_of(cell, "per_layer")]
        assert "setup_trace_s" in mine
        assert not any(n.startswith(("decode_host_ms", "schedule_self")) for n in mine)
    # there, in the issue's order (later PRs append behind them)
    assert [m["name"] for m in manifest.data["per_layer"] if m["name"] in NEW] == list(NEW)


@pytest.mark.parametrize("metric,want", [
    ("decode_host_ms.build", 1.5), ("decode_host_ms.dispatch", 2.5),
    ("decode_host_ms.readback", 85.0), ("decode_host_ms.land", 3.5),
    ("schedule_self_ms", 0.75)])
def test_phase_readers_take_the_mean_over_the_steps_of_the_window(manifest, metric, want):
    rows = step(0.0) + step(100.0, build=2.0, dispatch=3.0, readback=80.0,
                            land=4.0, rest=1.0)
    # a step that closes after the window, and another thread's span inside
    # a step, move nothing
    rows += step(200.0, build=9.0, dispatch=9.0, readback=9.0, land=9.0, rest=9.0)
    rows += [("client_iter", 1.0, 95.0, 7, {})]
    run = run_of(rows, window=(0.0, 199.0))
    assert manifest.reader(metric)(run) == pytest.approx(want)


def test_dispatch_takes_off_only_what_its_own_thread_holds(manifest):
    rows = step(0.0) + [("decode_land", 10.0, 50.0, 7, {})]  # another engine's
    assert manifest.reader("decode_host_ms.dispatch")(run_of(rows)) == pytest.approx(2.0)


def test_phases_add_up_to_the_step_the_older_reader_times(manifest):
    run = run_of(step(0.0) + step(100.0) + step(200.0))
    parts = sum(manifest.reader(f"decode_host_ms.{p}")(run)
                for p in ("dispatch", "readback", "land"))
    # decode_step_ms runs on to the end of `schedule`: half the rest more
    assert manifest.reader("decode_step_ms")(run) == pytest.approx(parts + 0.25)
    assert manifest.reader("decode_rows_mean")(run) == 64


def test_schedule_self_takes_the_union_of_what_it_holds(manifest):
    # admit 0-1, prefill 1-11 holding readback 5-9 and land 9-10.5: children
    # of children are not taken off twice, and an idle schedule is all self
    rows = [("admit", 0.0, 1.0, 0, {}), ("prefill_readback", 5.0, 9.0, 0, {}),
            ("prefill_land", 9.0, 10.5, 0, {}), ("prefill", 1.0, 11.0, 0, {}),
            ("schedule", 0.0, 12.0, 0, {}), ("schedule", 20.0, 20.5, 0, {})]
    assert manifest.reader("schedule_self_ms")(run_of(rows)) == pytest.approx((1.0 + 0.5) / 2)


@pytest.mark.parametrize("metric", NEW[:5])
def test_nothing_to_read_is_none(manifest, metric):
    read = manifest.reader(metric)
    assert read({"spans": None, "span_window_ns": None}) is None  # untraced
    assert read(run_of([])) is None                               # an empty window
    assert read(run_of(step(0.0), window=(500.0, 600.0))) is None
    # the parent's program: `decode_step` closes after the dispatch and holds
    # nothing, and no phase span exists. Only the scheduler's rest is there.
    parent = [("decode_step", 1.0, 3.5, 0, {"rows": 64}), ("schedule", 0.0, 100.0, 0, {})]
    got = read(run_of(parent))
    assert got == (pytest.approx(97.5) if metric == "schedule_self_ms" else None)


def test_setup_trace_reads_the_programs_counters(manifest, monkeypatch):
    from paddle_tpu import profiler

    read = manifest.reader("setup_trace_s")
    monkeypatch.setattr(profiler, "_counters", {"serve_tokens": 5})
    assert read({}) is None  # a program that does not count compilation
    monkeypatch.setattr(profiler, "_counters", {
        "compile_trace_ns": 1_500_000_000, "compile_lower_ns": 250_000_000,
        "compile_backend_ns": 9_000_000_000})
    assert read({}) == pytest.approx(1.75)
