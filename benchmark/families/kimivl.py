"""The ``kimivl`` family (the decoder of Kimi-VL-A3B and siblings of its
lineage: latent attention with a direct query projection and plain rotary
positions, a sigmoid router over routed experts beside shared ones, one
residual stream; served long prompts in calls against the paged latent
cache): where its configurations meet the program (``build``:
``paddle_tpu.models.mla_moe``, every key of the file mapped onto
``MLAMoEConfig`` by name), the plain reference (``reference/kimivl.py``,
re-exported) and the counts its readers divide by.

The leaves, the keys of the program's ``state_dict`` and the decode step's
bytes are ``families/xing4.py``'s with the mechanisms this model lacks
switched off by their keys (``hc_mult`` 1: one stream, no maps); what is this
family's own is the reference's verdict rule (``ROUTER_MARGIN``, measured for
6 of 64 experts at a scale of 2.446), the logits that are never whole
(``forward_logits``) and the counts of a prefill CALL: the live (query, key)
pairs of the program's chunked ``prefill`` spans and the EXPANDED form's
operations a pair (``call_attention_flops``), whatever form the program runs.

Served only: no ``TrainReference``. The vision tower and its projector are
not held (``configs/kimi-vl-a3b-7l.json`` says why): every prompt position is
a token id.

Hand-worked values at the published widths are in
tests/benchmark/test_benchmark_kimivl.py.
"""
from __future__ import annotations

import bisect
import math
import re

from benchmark.families import xing4 as _x
from benchmark.families.afmoe import calls_between, kernel_calls, prefill_spans  # noqa: F401
from benchmark.families.lfm2 import span_mean  # noqa: F401
from benchmark.families.xing4 import decode_trace_facts, kernel_ns, state_key  # noqa: F401
from benchmark.reference import kimivl as reference
# at the top, not inside ``build``: a checkout whose program lacks the layer
# of a prefill call (the parent of the PR that added it) then fails when the
# cell's files are loaded, before it has made 8.5 GB of weights for a model
# it cannot serve a long prompt of
from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM, attend_call  # noqa: F401

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration:
# every mechanism present (1 dense + 2 expert layers, two shared experts,
# the direct query projection), nothing at a width worth timing
REHEARSE = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
            "num_attention_heads": 4, "intermediate_size": 160,
            "moe_intermediate_size": 32, "n_routed_experts": 8,
            "num_experts_per_tok": 3, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "max_position_embeddings": 256, "dtype": "float32"}


# The narrowest router margin (6th against 7th of score + e_bias, over the six
# expert layers) at which the float32 reference still gives a verdict on a
# served token. MEASURED for this family on the chip (PR 47, PERF.md section
# 2; every reading is in cells/serve-kimivl-longdoc-pinned.json): 3,214 served
# positions of two seeds, the program's gap over 0.2 by the position's
# narrowest margin: 11.9% under 0.001, 3.2% at 0.001-0.002, 1.3% at
# 0.002-0.003, none of 330 at 0.003-0.004 (one at 0.167), none of 726 from
# 0.004 on (widest 0.030). The share falls e-fold every 0.001, but this
# family's margins are narrow (6 of 64: 2% of the positions lie over 0.01,
# a tenth over 0.006), and a cell is run sixteen times a check with one gap
# over the limit refusing it: at 0.0075 the fit leaves one such gap in some
# 17 checks, at 0.01 one in some 200. 15-35 of a run's 900-1,700 sampled
# tokens are judged.
ROUTER_MARGIN = 0.01


def _whole(cfg: dict) -> dict:
    """The file's keys with the mechanisms this model lacks switched off by
    the keys ``families/xing4.py`` reads."""
    return {"hc_mult": 1, **cfg}


def forward_logits(cfg: dict, weights: dict, ids, mode: str):
    """The plain reference as the harness asks for it, its logits NEVER whole
    (``reference.Logits``: the harness slices ``[0, a:b]`` after this
    returns, and (24,960, 163,840) float32 would be 16 GB). In ``"f32"``,
    which judges a run, a verdict only where the reference's own routing is
    decided by ``ROUTER_MARGIN`` in every expert layer; the logits of the
    other positions are all zeros, so a served token's gap there reads 0
    (``families/xing4.forward_logits`` says why). ``"fp8"``, the control, is
    judged BY those verdicts and gives its own logits at every position. The
    program's routing is never looked at."""
    keep = ROUTER_MARGIN if mode == "f32" else 0.0
    logits, margin = reference.forward(cfg, weights, ids, mode, min_margin=keep)
    if mode == "f32":
        print(f"reference: a verdict at {int((margin >= keep).sum())} of {margin.size} "
              f"positions (padding included): every router margin >= {keep}", flush=True)
    return logits


def leaf_specs(cfg: dict) -> list:
    return _x.leaf_specs(_whole(cfg))


# ``MLAMoEForCausalLM`` at the file's sizes HOLDING the weights, and what the
# algorithm needs, from shapes (nothing padded, nothing recomputed):
# ``families/xing4.py``'s, which read no key this model lacks
build = _x.build
expert_layers = _x.expert_layers
attention_params = _x.attention_params
cache_row = _x.cache_row
cache_bytes_per_context_token = _x.cache_bytes_per_context_token
latent_bytes_per_token = _x.latent_bytes_per_token
expert_bytes = _x.expert_bytes


def param_count(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_specs(cfg))


def dense_bytes_per_step(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of weights EVERY decode step reads whatever its routing
    (``families/xing4.dense_bytes_per_step``: attention and norms of every
    layer, the dense FFN, each expert layer's router and shared MLP, the
    final norm and the untied head)."""
    return _x.dense_bytes_per_step(_whole(cfg), itemsize)


def decode_step_bytes(cfg: dict, touched: float, context_tokens: float) -> float:
    """What ONE decode step must read: the weights outside the routed
    experts, the three matrices of each expert its live rows hit
    (``touched``: summed over the expert layers), the cached latent row of
    every live row's context once a layer."""
    return (dense_bytes_per_step(cfg) + touched * expert_bytes(cfg)
            + context_tokens * cfg["num_hidden_layers"] * latent_bytes_per_token(cfg))


def call_pairs(start: int, feed: int) -> int:
    """Live (query, key) pairs of ONE row's prefill call in one layer and one
    head: each of its ``feed`` queries sees the ``start`` cached positions
    and the fed positions up to its own."""
    return feed * start + feed * (feed + 1) // 2


def call_attention_flops(cfg: dict, pairs: float) -> float:
    """One layer's attention over ``pairs`` live (query, key) pairs in the
    EXPANDED form, whatever form the program runs: a product of ``qk_nope +
    qk_rope`` and one of ``v_head_dim`` a pair and a head. The context's
    up-projection (``kv_b``) is not the kernel's and not in it."""
    per_pair = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return float(per_pair * cfg["num_attention_heads"] * pairs)


def chunked_prefill_spans(run) -> list:
    """The attributes of the program's chunked ``prefill`` spans (those that
    carry ``start`` and ``feed``: the parent has none) that lie wholly inside
    the traced stretch, in order, each with ``t0_ns`` / ``t1_ns`` on the
    device's clock (``families/afmoe.prefill_spans``) and ``program``, the
    ``(start ns, duration ns)`` of the call's ``jit_prefill`` program on the
    device (None where the trace's edge cut it). None without a trace.

    Which program is a span's: the engine keeps ONE call in flight (it waits
    for the call before, inside the span, then dispatches), so the programs
    do not overlap and lie in the spans' order. The span of a prompt's LAST
    call (``calls_left`` 0) closes after the read-back of its logits: its
    program is the last that ended before the span did. Any other span closes
    right behind the dispatch, the call before it done: its program is the
    first that ends after the span does."""
    rows = prefill_spans(run)
    if rows is None:
        return None
    programs = sorted((s, d) for dev in run["trace"]["devices"].values()
                      for n, s, d in dev["modules"] if re.match(r"jit_prefill\(", n))
    out = []
    for a in sorted(rows, key=lambda a: a["t0_ns"]):
        if not (a.get("chunked") and "feed" in a):
            continue
        if a.get("calls_left") == 0:
            mine = [p for p in programs if a["t0_ns"] <= p[0] and p[0] + p[1] <= a["t1_ns"]][-1:]
        else:
            mine = [p for p in programs if p[0] + p[1] > a["t1_ns"] and p[0] >= a["t0_ns"]][:1]
        out.append({**a, "program": mine[0] if mine else None})
    return out


def gaps_behind_a_call(run):
    """``(share in %, gaps)``: of the WINDOW's gaps between tokens (a
    ``decode_step`` span's live ``rows`` each) the share whose step ran in a
    scheduler pass (``schedule`` span) that also made a chunked prefill call:
    the streams that waited a call out. None where the program has no such
    calls or the run kept no spans. ISSUE 47 holds the cell's median to one
    mode by this share (35%)."""
    spans = run["spans"]
    if spans is None or not run.get("span_window_ns"):
        return None
    t0, t1 = run["span_window_ns"]
    passes = sorted(r[1] for r in spans.named("schedule"))
    if not passes:
        return None
    of = lambda r: bisect.bisect_right(passes, r[1]) - 1
    called = {of(r) for r in spans.named("prefill", t0, t1) if r[4].get("chunked")}
    steps = [(of(r), r[4]["rows"]) for r in spans.named("decode_step", t0, t1)
             if "rows" in r[4]]
    gaps = sum(n for _, n in steps)
    if not called or not gaps:
        return None
    return 100.0 * sum(n for i, n in steps if i in called) / gaps, gaps
