"""``benchmark.run`` end to end on the CPU: no TPU means no result; the
rehearsal prints counts only; a broken timed path comes out not correct."""
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from benchmark import run

REPO = Path(__file__).resolve().parents[2]


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "train-xl-s2048",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_four_chip_cell_rehearses_on_four_virtual_devices():
    """dp2 x mp2 through HybridParallelEngine, the reference spread over four
    devices: own process, because fleet's mesh is process-wide state."""
    env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "train-hybrid-4chip",
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["counts"]["tokens"] == line["counts"]["steps"] * 4 * 64
    assert "reader: collective_exposed_pct found nothing to read" in p.stdout


@pytest.mark.parametrize("cell,counts", [
    ("train-xl-s2048", ("steps", "tokens")),
    ("serve-xl-chat-sat", ("requests", "tokens", "prompt_tokens", "output_tokens")),
])
def test_rehearsal_prints_counts_only(cell, counts, capsys):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds", "1.5",
                   "--trace", "1", "--rehearse"])
    out, err = capsys.readouterr()
    line = _last_json(out)
    assert rc == 0 and line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["counts"]) == set(counts) and "metrics" not in line
    assert "check: compiles_in_window = 0" in out
    # each number compared beside its limit: last in the line, last on standard error
    assert list(line)[-1] == "check" and set(line["check"]) > {"compiles_in_window", "failed"}
    assert line["check"]["compiles_in_window"] == {"value": 0.0, "limit": 0.0}
    said = err.strip().splitlines()[-len(line["check"]):]
    assert [s.split()[1] for s in said] == sorted(line["check"]) and all(
        s.startswith("check: ") and s.endswith(" ok") for s in said)


class _FakeCapture:
    """Stands where ``trace.capture.Capture`` does and notes who drove it."""

    def __init__(self):
        self.started, self.start_s, self.stop_s = False, 0.0, 0.0
        self.calls, self.threads = [], set()

    def _note(self, what):
        self.calls.append((what, time.monotonic()))
        self.threads.add(threading.current_thread())

    def start(self):
        self.started = True
        self._note("start")

    def mark_end(self):
        self._note("mark_end")

    def finish(self):
        self._note("finish")
        return None


@pytest.mark.parametrize("cell", ["train-xl-s2048", "serve-xl-chat-sat"])
def test_traced_window_drives_its_profiler_from_its_own_thread(cell, monkeypatch, capsys):
    """One window loop for traced and untraced runs: the thread that drives it
    starts the profiler for the window's last stretch, marks the end of the
    work, and stops the profiler only after the window has closed."""
    fake, real, seen = _FakeCapture(), run.Ctx.open_window, {}

    def open_window(self, t_open):
        spans = real(self, t_open)
        self.capture, self.trace_at = fake, t_open + 1.0
        seen["ctx"], seen["t_open"] = self, t_open
        return spans

    monkeypatch.setattr(run.Ctx, "open_window", open_window)
    run.main(["--workload", cell, "--seed", "17", "--seconds", "1.5", "--trace", "1",
              "--rehearse"])
    assert _last_json(capsys.readouterr().out)["correct"] is True
    assert [c[0] for c in fake.calls] == ["start", "mark_end", "finish"]
    assert fake.threads == {threading.main_thread()}
    t_open, t_close = seen["ctx"].window
    (_, started), (_, ended), (_, stopped) = fake.calls
    assert t_open + 1.0 <= started <= ended <= stopped
    assert started < t_close and ended <= t_close + 0.05 <= stopped + 0.05


def test_serving_offers_the_same_load_for_any_seed(capsys):
    seen = []
    for seed in (7, 3_000_000_019):
        run.main(["--workload", "serve-xl-chat-sat", "--seed", str(seed),
                  "--seconds", "1.5", "--rehearse"])
        c = _last_json(capsys.readouterr().out)["counts"]
        seen.append((c["requests"], c["prompt_tokens"], c["output_tokens"]))
    assert seen[0] == seen[1]


def test_train_step_that_keeps_its_state_is_not_correct(monkeypatch, capsys):
    """The timed path broken underneath: the compiled step computes its loss
    and returns its state unchanged."""
    from paddle_tpu import jit

    real = jit.CompiledTrainStep._call_impl

    def frozen(self, *batch):
        params = [p._data for p in self.params]
        loss = real(self, *batch)
        for p, a in zip(self.params, params):
            p._data = a  # donated on the chip; on the CPU tier the old buffers live
        return loss

    monkeypatch.setattr(jit.CompiledTrainStep, "__init__",
                        _no_donation(jit.CompiledTrainStep.__init__))
    monkeypatch.setattr(jit.CompiledTrainStep, "_call_impl", frozen)
    run.main(["--workload", "train-xl-s2048", "--seed", "11", "--seconds", "0.5",
              "--rehearse"])
    out = capsys.readouterr().out
    assert _last_json(out)["correct"] is False
    assert "change_norm_gap" in out and "OUTSIDE" in out


def _no_donation(init):
    def wrapped(self, model, loss_fn, optimizer, donate=True):
        init(self, model, loss_fn, optimizer, donate=False)
    return wrapped


def test_altered_served_token_is_not_correct(monkeypatch, capsys):
    """A token altered where it is produced: every 5th token the engine
    appends is moved to the next id."""
    from paddle_tpu.serving import engine as E

    real, n = E.Engine._append_token, [0]

    def altered(self, seq, tok):
        n[0] += 1
        return real(self, seq, (tok + 1) % 1024 if n[0] % 5 == 0 else tok)

    monkeypatch.setattr(E.Engine, "_append_token", altered)
    run.main(["--workload", "serve-xl-chat-sat", "--seed", "13", "--seconds", "1.5",
              "--rehearse"])
    out = capsys.readouterr().out
    assert _last_json(out)["correct"] is False
    assert "served_logit_gap" in out and "OUTSIDE" in out
