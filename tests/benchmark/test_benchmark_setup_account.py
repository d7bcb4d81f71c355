"""The eight readers that split ``setup_s`` (PR 40), each over a hand-worked
set-up account: rows before and after the window's open, a hit and a miss, a
set-up site beside a program, a cell without the span, a program without an
account; the manifest with their entries and explicit lists; and the
chip-free rehearsal of one serving, one training and the four-chip cell, in
which every one of them finds something to read. ``BENCHMARK.json`` lists the
eight since PR 40, appended, and no file of the harness was edited for them."""
import json
import subprocess
import sys

import pytest

from benchmark.manifest import REPO, Manifest

CELLS = ["train-xl-s2048", "serve-xl-chat-sat", "train-hybrid-4chip",
         "serve-xing4-longanswer-pinned", "serve-phi4flash-reasoning",
         "serve-lfm2-toolturn-pinned"]
SERVING = [c for c in CELLS if c.startswith("serve-")]
# metric -> (source, layer, the cells it lists)
METRICS = {
    "setup_import_s": ("program_counter", "process start", CELLS),
    "setup_param_init_s": ("program_counter", "model build", CELLS),
    "setup_engine_init_s": ("program_span", "serve entry: scheduler", SERVING),
    "setup_programs": ("program_span", "compiled programs", CELLS),
    "setup_cache_misses": ("program_counter", "compiled programs", CELLS),
    "setup_first_run_s": ("program_span", "compiled programs", CELLS),
    "setup_program_max_s": ("program_span", "compiled programs", CELLS),
    "setup_step_text_s": ("program_span", "distributed", ["train-hybrid-4chip"]),
}
S = 10 ** 9
OPEN = 100 * S  # the window's open on the spans' clock


def _row(name, t0_s, dur_s, site=False, trace=0.0, lower=0.0, backend=0.0,
         hits=0, misses=0, own=None, **attrs):
    stages = trace + lower + backend
    return {"name": name, "site": site, **attrs, "tid": 1,
            "t0_ns": int(t0_s * S), "t1_ns": int((t0_s + dur_s) * S),
            "trace_s": trace, "lower_s": lower, "backend_s": backend,
            "cache_hits": hits, "cache_misses": misses, "cache_load_s": 0.0,
            "cache_saved_s": 0.0,
            "first_run_s": dur_s - stages if own is None else own}


# a serving process by hand: the engine (1.5 s, 1.25 of them the pools), two
# prefill programs and a decode program before the window opens (one loaded
# from the cache, one compiled and written, one too small for the cache), a
# program_build a program, and a decode step that compiled INSIDE the window
ACCOUNT = [
    _row("engine_init", 20.0, 1.5, site=True, backend=0.125, own=0.125,
         pool_blocks=3679),
    _row("pool_alloc", 20.125, 1.25, site=True, backend=0.25),
    _row("prefill", 30.0, 4.0, trace=1.0, lower=0.5, backend=2.0, hits=1, own=0.25,
         bucket_t=16, bucket_b=4),
    _row("program_build", 30.0, 0.25, site=True, kind="prefill"),
    _row("prefill", 40.0, 9.0, trace=1.5, lower=0.5, backend=6.0, misses=1,
         bucket_t=32, bucket_b=4),
    _row("decode_step", 50.0, 2.0, trace=0.75, lower=0.25, backend=0.5,
         compiled_bucket=2),
    _row("decode_step", 98.0, 4.0, trace=1.0, lower=1.0, backend=1.0),  # ends at 102
    _row("decode_step", 120.0, 30.0, trace=9.0, lower=9.0, backend=9.0, misses=1),
]
COUNTERS = {"setup_import_ns": 3_500_000_000, "param_init_ns": 1_250_000_000,
            "param_init_bytes": 2_600_000_000, "param_init_leaves": 292}
EXPECTED = {
    "setup_import_s": 3.5,
    "setup_param_init_s": 1.25,
    "setup_engine_init_s": 1.5,
    "setup_programs": 3.0,            # two prefills and a decode step
    "setup_cache_misses": 1.0,        # the 32-token prefill's
    "setup_first_run_s": 0.25 + 1.0 + 0.5,
    "setup_program_max_s": 8.0,       # the 32-token prefill: 1.5 + 0.5 + 6
    "setup_step_text_s": None,        # a serving process compiled no dp step
}


@pytest.fixture(scope="module")
def M():
    return Manifest()


@pytest.fixture
def program(monkeypatch):
    """``paddle_tpu.profiler`` answering with the hand-made account."""
    from paddle_tpu import profiler

    def answer(account, counters=COUNTERS):
        monkeypatch.setattr(profiler, "setup_account", lambda: account, raising=False)
        monkeypatch.setattr(profiler, "counters", lambda: dict(counters))

    return answer


def _facts(**over):
    return {"span_window_ns": (OPEN, OPEN + 51 * S), "setup_s": 95.0, **over}


def test_manifest_is_sound_with_the_eight_entries(M):
    assert M.validate() == []
    last = M.data["per_layer"][-len(METRICS):]
    assert [m["name"] for m in last] == list(METRICS)        # appended, in order
    assert [w["name"] for w in M.data["workloads"]] == CELLS  # no cell added
    for m in last:
        source, layer, cells = METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": "count" if m["name"] in (
            "setup_programs", "setup_cache_misses") else "s", "better": "lower",
            "source": source, "layer": layer, "moves": "setup_s", "workloads": cells}
        assert (M.root / "metrics" / f"{m['name']}.py").is_file()
    # a metric that moves setup_s WITHOUT a list belongs to every cell, those
    # of later PRs too: the two older ones do, none of the new ones may
    for cell in CELLS:
        mine = {m["name"] for m in M.metrics_of(cell, "per_layer")}
        assert {n for n, (_, _, cells) in METRICS.items() if cell in cells} \
            == mine & set(METRICS)


@pytest.mark.parametrize("metric", list(METRICS))
def test_reader_on_the_hand_worked_account(M, program, capsys, metric):
    program(ACCOUNT)
    value = M.reader(metric)(_facts())
    if EXPECTED[metric] is None:
        assert value is None
    else:
        assert value == pytest.approx(EXPECTED[metric])
    out = capsys.readouterr().out
    if metric == "setup_programs":
        # a traced run's log carries the account: six rows ended before the
        # window opened, from the process's start (the open less setup_s)
        assert "account: 6 rows before the window's open" in out
        assert "account: +15.000s engine_init [pool_blocks=3679] dur 1.500" in out
        assert "account: +35.000s prefill [bucket_t=32 bucket_b=4] dur 9.000 trace 1.500 " \
               "lower 0.500 backend 6.000 hits 0 misses 1" in out


def test_step_text_and_a_cell_without_its_span(M, program):
    hybrid = [_row("train_step", 10.0, 40.0, backend=0.5, own=1.0, kind="engine"),
              _row("step_lower", 11.0, 12.0, trace=9.0, lower=2.5),
              _row("step_compile", 23.0, 20.0, backend=19.5, hits=1),
              _row("step_text", 43.0, 2.75, site=True, text_bytes=8_800_000,
                   dp_reduce_leaves=70)]
    program(hybrid)
    assert M.reader("setup_step_text_s")(_facts()) == pytest.approx(2.75)
    assert M.reader("setup_engine_init_s")(_facts()) is None  # no engine built
    assert M.reader("setup_programs")(_facts()) == 2.0  # train_step, step_compile
    # the engine times the stages apart: the costliest ROW is the compile
    assert M.reader("setup_program_max_s")(_facts()) == pytest.approx(19.5)
    assert M.reader("setup_first_run_s")(_facts()) == pytest.approx(1.0 + 0.5)
    # a row that ends as the window opens is set-up; one that ends after is not
    program([_row("step_text", 90.0, 10.0, site=True), _row("step_text", 95.0, 5.5, site=True)])
    assert M.reader("setup_step_text_s")(_facts()) == pytest.approx(10.0)


def test_a_program_without_an_account_reads_none(M, monkeypatch):
    """The parent of PR 40 has neither ``setup_account`` nor the counters: each
    reader returns None and the result's line leaves its metric out."""
    from paddle_tpu import profiler

    monkeypatch.delattr(profiler, "setup_account")
    monkeypatch.setattr(profiler, "counters", lambda: {"compile_trace_ns": 5})
    for metric in METRICS:
        assert M.reader(metric)(_facts()) is None, metric


def test_a_model_that_draws_nothing_reads_zero_and_an_empty_account_none(M, program):
    program([], {"setup_import_ns": 2 * S})
    assert M.reader("setup_param_init_s")(_facts()) == 0.0
    assert M.reader("setup_programs")(_facts()) == 0.0
    assert M.reader("setup_cache_misses")(_facts()) == 0.0
    assert M.reader("setup_first_run_s")(_facts()) == 0.0
    assert M.reader("setup_program_max_s")(_facts()) is None
    # without the window on the spans' clock no row can be placed
    assert M.reader("setup_programs")({"span_window_ns": None}) is None


def _says_read_something(out, cell):
    for name, (_, _, cells) in METRICS.items():
        said = f"reader: {name} read something" in out
        assert said == (cell in cells), (name, cell)
        if cell not in cells:
            assert f"reader: {name} " not in out
    assert "account: " in out


def _rehearse(cell, seconds, **env):
    """A process of its own, as the driver runs it: the account is the
    process's, and in this one other tests have compiled before."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
         "2147483659", "--seconds", seconds, "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu", **env})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    _says_read_something(p.stdout, cell)
    return p.stdout


@pytest.mark.parametrize("cell", ["serve-xl-chat-sat", "train-xl-s2048"])
def test_the_readers_read_in_a_rehearsal(cell):
    out = _rehearse(cell, "1.5")
    assert " program_build [kind=" in out
    if cell.startswith("serve-"):
        assert " engine_init [pool_bytes=" in out and " pool_alloc [pools=" in out


def test_the_four_chip_cell_reads_its_step_text():
    out = _rehearse("train-hybrid-4chip", "0.5",
                    XLA_FLAGS="--xla_force_host_platform_device_count=4")
    text = next(l for l in out.splitlines() if " step_text [text_bytes=" in l)
    for count in ("dp_reduce_leaves=", "dp_reduce_async=", "mp_weight_exchanges=",
                  "mp_activation_gathers=", "mp_reduce_exchanges=", "mp_reduce_async=",
                  "mp_activation_reduces="):
        assert count in text, (count, text)
    assert " step_lower [" in out and " step_compile [" in out
