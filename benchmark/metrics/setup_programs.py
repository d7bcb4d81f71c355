"""Programs the process compiled (or loaded from the persistent cache) before
the window opened, as the program's own set-up account tells them
(``paddle_tpu.profiler.setup_account()``): its rows that carry a backend
stage and are no set-up site, each the ``prefill`` / ``decode_step`` /
``train_step`` / ``step_compile`` span a program's first call compiled
under. 13-14 in a serving cell (the warm-up's prefill and decode buckets).
With ``compiles_in_window.*`` at 0 the rows before the window's open are all
that compiled. None where the program keeps no account.

The other readers of the account take their rows from ``rows`` and
``programs`` below. A traced run's log gets the account itself from here, a
line a row (``account: ...``, times from the process's start): the split of
``setup_s`` that PERF.md section 5 is written from."""
import json


def rows(run):
    """The account's rows that ended before the window opened (the spans'
    clock, ``run["span_window_ns"]``), or None without an account."""
    from paddle_tpu import profiler

    account = getattr(profiler, "setup_account", None)
    if account is None or run.get("span_window_ns") is None:
        return None
    t_open = run["span_window_ns"][0]
    return [r for r in account() if r["t1_ns"] <= t_open]


def programs(run, found=None):
    """Of ``rows``, those a program's first call compiled under."""
    found = rows(run) if found is None else found
    if found is None:
        return None
    return [r for r in found if r["backend_s"] > 0 and not r["site"]]


def seconds_of(run, name):
    """Summed duration of the rows of one name (a set-up site: the whole
    span, what lies inside it included), or None where there is none."""
    found = [r for r in rows(run) or () if r["name"] == name]
    if not found:
        return None
    return sum(r["t1_ns"] - r["t0_ns"] for r in found) / 1e9


def describe(run, found):
    """The rows as lines of the log, with the account's size as JSON."""
    t_open = run["span_window_ns"][0]
    start = t_open - int(run.get("setup_s", 0.0) * 1e9)  # the process's, on the spans' clock
    fixed = {"name", "site", "tid", "t0_ns", "t1_ns", "trace_s", "lower_s", "backend_s",
             "cache_hits", "cache_misses", "cache_load_s", "cache_saved_s", "first_run_s"}
    print(f"account: {len(found)} rows before the window's open, "
          f"{len(json.dumps(found))} bytes as JSON", flush=True)
    for r in found:
        attrs = " ".join(f"{k}={v}" for k, v in r.items() if k not in fixed)
        print(f"account: +{(r['t0_ns'] - start) / 1e9:.3f}s {r['name']} [{attrs}] "
              f"dur {(r['t1_ns'] - r['t0_ns']) / 1e9:.3f} trace {r['trace_s']:.3f} "
              f"lower {r['lower_s']:.3f} backend {r['backend_s']:.3f} "
              f"hits {r['cache_hits']} misses {r['cache_misses']} "
              f"load {r['cache_load_s']:.3f} saved {r['cache_saved_s']:.3f} "
              f"own {r['first_run_s']:.3f}", flush=True)


def read(run):
    found = rows(run)
    if found is None:
        return None
    describe(run, found)
    return float(len(programs(run, found)))
