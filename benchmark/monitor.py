"""What the benchmark observes of the program from outside: backend
compilations (``jax.monitoring``), the program's host spans (its public
span-observer hook), its counters, and the device's memory."""
from __future__ import annotations

import time


class Compiles:
    """Every XLA backend compilation (a persistent-cache hit included) with
    the host time it ended at."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events = []  # (t_end monotonic, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.events.append((time.monotonic(), float(duration)))

    def between(self, t0, t1):
        return [e for e in self.events if t0 <= e[0] <= t1]

    def seconds_before(self, t):
        return sum(d for at, d in self.events if at < t)


class Spans:
    """Finished host spans of the program, as (name, t0_ns, t1_ns, tid,
    attrs), on ``time.perf_counter_ns``. Registered only in traced runs."""

    def __init__(self):
        from paddle_tpu.profiler import spans

        self.rows = []
        self._mod = spans
        spans.add_span_observer(self._on)

    def _on(self, sp):
        self.rows.append((sp.name, sp.t0, sp.t1, sp.tid, dict(sp.attrs)))

    def close(self):
        self._mod.remove_span_observer(self._on)

    def mean_ms(self, name, t0_ns, t1_ns):
        """Mean duration of the spans of one name inside [t0, t1], or None."""
        rows = self.named(name, t0_ns, t1_ns)
        return sum(r[2] - r[1] for r in rows) / len(rows) / 1e6 if rows else None

    def named(self, name, t0_ns=None, t1_ns=None):
        return [r for r in self.rows if r[0] == name
                and (t0_ns is None or r[1] >= t0_ns)
                and (t1_ns is None or r[2] <= t1_ns)]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts them
    (buffers; an executable's temporaries are not in it: PERF.md s7)."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices())
