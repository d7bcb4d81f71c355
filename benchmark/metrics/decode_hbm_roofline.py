"""Roofline share of the paged decode programs against HBM bandwidth: the
bytes the decode steps inside the traced window MUST read (the weights once
per step, the family's ``weight_bytes``, plus the cache of every live row at
its real context length, the family's ``cache_bytes_per_context_token`` x the
context of each token the clients received from a decode step in that window;
a family that gives no ``weight_bytes`` has no such share) over the HBM
peak, over the device time of the decode program's executions in the window
(the ``jit_step`` events of the device's per-program line; prefill programs
are ``jit_prefill``). What the gather reads beyond the live context (the
bucket's padded width) is the waste this share shows."""
import re

from benchmark import flops
from benchmark.trace import reduce as R, summary

DECODE_PROGRAM = re.compile(r"^jit_step\(")


def read(run):
    weight_bytes = getattr(run["family"], "weight_bytes", None)
    if run["trace"] is None or weight_bytes is None:
        return None
    red = run["trace"]
    t0, t1 = summary.window_ns(red)
    spent, steps = 0, 0
    for dev in red["devices"].values():
        ns, n = R.total_ns(R.clip(dev["modules"], t0, t1), DECODE_PROGRAM.pattern)
        spent, steps = spent + ns, steps + n
    if not steps:
        return None
    # tokens the clients got from decode steps while the trace ran; a token's
    # context is its prompt plus the tokens before it
    a, b = red["host_window"]
    sched, context_tokens = run["schedule"], 0
    for rec in run["served"]:
        plen = int(sched.prompt_len[rec.index])
        context_tokens += sum(plen + k for k, t in enumerate(rec.stamps)
                              if k > 0 and a <= t <= b)
    need = steps * weight_bytes(run["config"]) \
        + context_tokens * run["family"].cache_bytes_per_context_token(run["config"])
    return flops.share(need / run["peaks"]["hbm_bytes_per_s"], spent / 1e9,
                       "decode_hbm_roofline")
