"""Take a profiler trace of the end of the window and boil the xplane file down
to plain intervals that ``reduce.py`` (and its test, on a recorded copy)
work on:

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...],   # TraceAnnotations only
     "sync_ns": <trace clock minus time.perf_counter_ns>,
     "t0_ns": ..., "t1_ns": ...,                # traced stretch, trace clock
     "host_window": (t0, t1)}                   # the same, time.monotonic

A ``bench.sync`` annotation written at a known ``perf_counter_ns`` puts the
program's host spans (which are on that clock) on the trace's clock, so a
device gap can be attributed to the span the host was in.
"""
from __future__ import annotations

import glob
import os
import shutil
import time

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


class Capture:
    """The profiler over the LAST seconds of the window. The thread that
    drives the window starts it there (``start``), marks the end of the
    window's work (``mark_end``) and stops it once the window has closed
    (``finish``), so that stopping, which takes seconds, costs the window
    nothing. Never a side thread: one that has to start or stop the profiler
    waits for the interpreter while the driving thread sits in a dispatch
    (PR 23, call 10: three seconds late with eight steps in flight)."""

    def __init__(self, directory: str):
        self.dir = directory
        self.sync_perf_ns = None
        self.started, self.start_s, self.stop_s = False, None, None
        self.host_window = None

    def start(self):
        import jax

        t = time.monotonic()
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.sync"):
            self.sync_perf_ns = time.perf_counter_ns()
        self.host_window = [time.monotonic(), None]
        self.started, self.start_s = True, self.host_window[0] - t

    def mark_end(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.sync_end"):
            self.host_window[1] = time.monotonic()

    def finish(self) -> dict:
        import jax

        if not self.started:
            raise RuntimeError("the window closed before its profiler was started")
        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no xplane file under {self.dir}")
        return {**load_xplane(files[0], self.sync_perf_ns),
                "host_window": tuple(self.host_window)}


def load_xplane(path: str, sync_perf_ns=None) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "sync_ns": None, "t0_ns": None, "t1_ns": None}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.name, int(e.start_ns), int(e.duration_ns)])
    sync = [h for h in out["host"] if h[0] == "bench.sync"]
    end = [h for h in out["host"] if h[0] == "bench.sync_end"]
    if sync:
        out["t0_ns"] = sync[0][1]
        if sync_perf_ns is not None:
            out["sync_ns"] = sync[0][1] - int(sync_perf_ns)
    if end:
        out["t1_ns"] = end[0][1]
    return out
