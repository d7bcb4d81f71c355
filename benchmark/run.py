"""One run of one cell: ``python -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

A new process every time: refuses to start without the TPU chips the cell
asks for, builds the cell's model on the device from the seed, warms exactly
the shapes the cell's traffic reaches, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints the
contract's JSON object as the last line of its standard output. The only
thing two runs share is JAX's persistent compilation cache.

``--rehearse`` (no chip needed) drives the same code at the sizes of
``rehearse.json`` (traffic) and of the family's ``REHEARSE`` (model) and
prints counts only: never a time, a rate or a share.
"""
from __future__ import annotations

import time

T0 = time.monotonic()  # process start, for setup_s

import argparse
import importlib
import json
import math
import os
import sys

from .manifest import Manifest

JOBS = {"train": "train_job", "open_loop": "serve_job"}


def log(msg):
    print(msg, flush=True)


class Ctx:
    """What a job needs from the harness: the cell's files, the window's
    bookkeeping (compiles, spans, device trace), and notes for the log."""

    def __init__(self, manifest, args):
        self.manifest, self.args = manifest, args
        self.workload = manifest.workload(args.workload)
        self.cell = manifest.cell(args.workload)
        self.config = manifest.config(self.workload["config"])
        self.family = manifest.family(self.config["family"])
        self.traffic = manifest.traffic(self.workload["traffic"])
        self.chips = int(self.workload["chips"])
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), bool(args.rehearse)
        self.notes, self.compiles, self.spans = {}, None, None
        self.capture, self.trace_at, self.reduced, self.window = None, None, None, None
        if self.rehearse:
            over = json.loads((manifest.root / "rehearse.json").read_text())
            self.config = {**self.config, **self.family.REHEARSE}
            self.traffic = {**self.traffic, **over[self.traffic["kind"]]}

    def note(self, key, value):
        self.notes[key] = value
        log(f"note: {key} = {value:.4f}" if isinstance(value, float)
            else f"note: {key} = {value}")

    def open_window(self, t_open):
        """Called right before the window opens at host time ``t_open``.
        Traced runs start the span observer here and plan their profiler
        for the window's last ``trace_s`` seconds."""
        if self.trace:
            from .monitor import Spans

            self.spans = Spans()
        if self.trace and not self.rehearse:
            from .trace.capture import Capture

            self.capture = Capture(
                os.path.join(self.manifest.repo, ".bench_trace", self.args.workload))
            self.trace_at = t_open + max(
                0.0, self.seconds - float(self.cell.get("trace_s", 3.0)))
        return self.spans

    def tick(self):
        """Called by the thread that drives the window, from inside it: a
        traced run's profiler starts here when its time has come."""
        cap = self.capture
        if cap is not None and not cap.started and time.monotonic() >= self.trace_at:
            cap.start()
            self.note("trace_start_s", cap.start_s)

    def sleep_until(self, t):
        """Sleep to host time ``t``, starting the profiler on the way."""
        if self.capture is not None and not self.capture.started:
            time.sleep(max(0.0, min(self.trace_at, t) - time.monotonic()))
            self.tick()
        time.sleep(max(0.0, t - time.monotonic()))

    def end_work(self):
        """The window's last piece of work has been handed out: the traced
        stretch ends here, and the profiler is stopped after the window."""
        if self.capture is not None:
            self.capture.mark_end()

    def close_window(self, t_open, t_close):
        self.window = (t_open, t_close)
        # the same window on the spans' clock (time.perf_counter_ns)
        off = time.perf_counter_ns() - int(time.monotonic() * 1e9)
        self.span_window_ns = (int(t_open * 1e9) + off, int(t_close * 1e9) + off)
        if self.capture is not None:
            t = time.monotonic()
            self.reduced = self.capture.finish()
            self.note("trace_stop_s", self.capture.stop_s)
            self.note("trace_load_s", time.monotonic() - t - self.capture.stop_s)
        if self.spans is not None:
            self.spans.close()

    def memory_peak(self):
        from .monitor import memory_peak_bytes

        return 0 if self.rehearse else memory_peak_bytes()


def _device(ctx):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if ctx.rehearse:
        return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}
    if dev.platform != "tpu":
        sys.exit(f"benchmark: no TPU: JAX found {dev.platform!r} "
                 f"({dev.device_kind}); no CPU fall-back, nothing reported")
    if len(devs) < ctx.chips:
        sys.exit(f"benchmark: cell {ctx.args.workload} needs {ctx.chips} chips, "
                 f"JAX found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": ctx.chips}


def per_layer(manifest, cell, facts) -> dict:
    """The cell's per-layer metrics as the result's line carries them; a
    reader that found nothing to read (``None``) leaves its metric out."""
    out = {}
    for m in manifest.metrics_of(cell, "per_layer"):
        value = manifest.reader(m["name"])(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _cache_dir():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else the fixed path inside the checkout that the program itself
    falls back to (``paddle_tpu`` sets it on import; the path is part of the
    cache's key, so it never moves)."""
    import jax

    import paddle_tpu  # noqa: F401  (sets the cache directory)

    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (tests; the data directories "
                         "are found beside it)")
    args = ap.parse_args(argv)
    manifest = Manifest(args.manifest)
    ctx = Ctx(manifest, args)
    device = _device(ctx)
    log(f"device: {json.dumps(device)}")
    _cache_dir()

    from . import check
    from .monitor import Compiles

    ctx.compiles = Compiles()
    job = importlib.import_module(f"{__package__}.{JOBS[ctx.traffic['kind']]}")
    res = job.run(ctx)
    t_open, t_close = res["t_open"], res["t_close"]

    in_window = ctx.compiles.between(t_open, t_close)
    numbers = dict(res["numbers"])
    numbers["compiles_in_window"] = (float(len(in_window)), "backend compile events")
    numbers["failed"] = (float(res["failed"]), f"of {res['attempted']} attempted")
    limits = {**ctx.cell["limits"], "compiles_in_window": 0.0, "failed": 0.0}
    checked = []  # the check's lines: in the log here, last on standard error at the end
    correct = check.judge(numbers, limits, out=checked.append)
    log("\n".join(checked))
    compared = {n: {"value": v if math.isfinite(v) else repr(v), "limit": limits[n]}
                for n, (v, _) in sorted(numbers.items()) if n in limits}

    def result(line):
        """The run's last words: each number compared beside its limit on
        standard error, then the result's line, which carries them last."""
        print("\n".join(checked), file=sys.stderr, flush=True)
        print(json.dumps({**line, "check": compared}))

    for k, v in sorted(ctx.notes.items()):
        if isinstance(v, float):
            log(f"part: {k} = {v:.3f}")

    setup_s = t_open - T0
    log(f"part: setup_s = {setup_s:.3f}")
    facts = {**res["facts"], "config": ctx.config, "family": ctx.family,
             "traffic": ctx.traffic,
             "cell": ctx.cell, "chips": ctx.chips, "window": (t_open, t_close),
             "seconds": t_close - t_open, "end_to_end": res["end_to_end"],
             "compiles": ctx.compiles, "setup_s": setup_s,
             "trace": ctx.reduced, "spans": ctx.spans,
             "span_window_ns": getattr(ctx, "span_window_ns", None),
             "peaks": manifest.peaks("TPU v5 lite" if ctx.rehearse else device["kind"])}

    if ctx.rehearse:
        # the readers run too (those that need no device trace), so that a
        # fault in one shows here and not on the chip; their values are of a
        # CPU and are not printed
        if ctx.trace:
            read = per_layer(manifest, args.workload, facts)
            for m in manifest.metrics_of(args.workload, "per_layer"):
                log(f"reader: {m['name']} " + ("read something" if m["name"] in read
                                                else "found nothing to read"))
        result({"rehearsal": True, "correct": correct,
                "attempted": res["attempted"], "failed": res["failed"],
                "counts": res["counts"], "device": device})
        return 0

    dev_out = dict(device, memory_peak_bytes=int(res["memory_peak_bytes"]))
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": {}, "device": dev_out}
    if not ctx.trace:
        values = {**res["end_to_end"], "setup_s": setup_s}
        for m in manifest.metrics_of(args.workload, "end_to_end"):
            line["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from .trace import summary

        t = time.monotonic()
        line["metrics"] = per_layer(manifest, args.workload, facts)
        dev_out.update(summary.busy_and_window(ctx.reduced))
        line["breakdown"] = summary.breakdown(ctx.reduced, ctx.spans)
        log(f"part: reduce_s = {time.monotonic() - t:.3f} over "
            f"{sum(len(d['ops']) for d in ctx.reduced['devices'].values())} device "
            f"operations and {len(ctx.spans.rows)} host spans")
    log(f"part: run_s = {time.monotonic() - T0:.3f}")
    result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
