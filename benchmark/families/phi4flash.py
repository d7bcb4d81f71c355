"""The ``phi4flash`` family (Phi-4-mini-flash-reasoning: nine Mamba-1 layers,
eight window layers of differential attention, ONE full-attention layer whose
keys and values seven cross layers read again, seven gated memory units fed by
the last scan): where its configurations meet the program (``build``:
``paddle_tpu.models.phi4flash``, every key of the file mapped onto
``PhiFlashConfig`` by name), the plain reference (``reference/phi4flash.py``,
re-exported through ``forward_logits``) and the counts its readers divide by.

Served only: no ``TrainReference``. What a decode step must read is of four
kinds, and only one grows with the context: the weights (every parameter
once: the head is the embedding), the ONE cached layer's K and V over each
live row's context, once for each of its ``paged_readers`` (the layer itself
and the cross layers), the window layers' last ``sliding_window`` tokens a
row, and the scan layers' states, read and written. The step's share of the
HBM peak is ``hybrid_decode_hbm_mfu_pct``. The family gives no
``weight_bytes``: ``decode_hbm_roofline``, the dense model's share, lists its
own cell in ``BENCHMARK.json`` and is silent here.

Hand-worked values at the published sizes are in
tests/benchmark/test_benchmark_phi4flash.py.
"""
from __future__ import annotations

import math
import re

import numpy as np

from benchmark import model
from benchmark.reference import phi4flash as reference
# at the top, not inside ``build``: a checkout whose program lacks the class
# (the parent of the PR that added it) then fails when the cell's files are
# loaded, before it has made 7.7 GB of weights for a model it cannot build
from paddle_tpu.models.phi4flash import PhiFlashConfig, PhiFlashForCausalLM

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration:
# every kind of layer present (2 Mamba + the one that feeds the memory units,
# 2 window layers whose window wraps, the full layer, a GMU, a cross layer),
# nothing at a width worth timing
REHEARSE = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 8,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 128, "sliding_window": 8,
            "max_position_embeddings": 256, "dtype": "float32"}

DT_MIN, DT_MAX = 0.001, 0.1  # the published range of a channel's step size


def dt_rank(cfg: dict) -> int:
    return reference.dt_rank(cfg)


def d_inner(cfg: dict) -> int:
    return cfg.get("mamba_expand", 2) * cfg["hidden_size"]


def layer_kinds(cfg: dict) -> list:
    return [reference.layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]


def _mixer(cfg: dict, kind: str) -> list:
    d, H, G = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, di, N, K, R = d // H, d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_d_conv"], dt_rank(cfg)
    lam = [(f"lambda_{n}", (h,), "normal") for n in ("q1", "k1", "q2", "k2")] \
        + [("subln.g", (2 * h,), "gain")]
    return {
        "mamba": [("in_proj.w", (d, 2 * di), "normal"),
                  # about 1 a tap: the convolution's output, and with it B, C
                  # and what the state holds, is of the size of its input
                  ("conv.w", (K, di), "gain"), ("conv.b", (di,), "normal"),
                  ("x_proj.w", (di, R + 2 * N), "normal"),
                  ("dt_proj.w", (R, di), "normal"), ("dt_proj.b", (di,), "normal"),
                  ("A_log", (N, di), "normal"), ("D", (di,), "gain"),
                  ("out_proj.w", (di, d), "normal")],
        "attn": [("qkv.w", (d, (H + 2 * G) * h), "normal"),
                 ("o.w", (H * h, d), "normal")] + lam,
        "gmu": [("in_proj.w", (d, di), "normal"), ("out_proj.w", (di, d), "normal")],
        "cross": [("q.w", (d, H * h), "normal"), ("o.w", (H * h, d), "normal")] + lam,
    }[kind]


def leaf_specs(cfg: dict) -> list:
    """``[(name, shape, kind)]`` in a fixed order. Matrices are (in, out), as
    the equations in ``reference/phi4flash.py`` use them, the convolution (K,
    d_i) and ``A_log`` (N, d_i). The layers of a stack are ONE leaf a matrix,
    stacked over the stack's pairs, as the program scans them: ``front.*``
    the L/4 [Mamba, window] pairs, ``back.*`` the L/4 - 1 [GMU, cross] pairs,
    ``mid.*`` layers L/2 (the Mamba that feeds the memory units) and L/2 + 1
    (the full-attention layer)."""
    d, F, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    each = [("norm.g", (d,), "gain"), ("norm.b", (d,), "normal"),
            ("ffn_norm.g", (d,), "gain"), ("ffn_norm.b", (d,), "normal"),
            ("up.w", (d, 2 * F), "normal"), ("down.w", (F, d), "normal")]
    specs = [("wte", (cfg["vocab_size"], d), "normal")]
    for stack, lead, kinds in (("front", (L // 4,), ("mamba", "attn")),
                               ("mid", (), ("mamba", "attn")),
                               ("back", (L // 4 - 1,), ("gmu", "cross"))):
        if lead == (0,):
            continue
        for kind in kinds:
            specs += [(f"{stack}.{kind}.{name}", lead + shape, k)
                      for name, shape, k in each + _mixer(cfg, kind)]
    return specs + [("norm.g", (d,), "gain"), ("norm.b", (d,), "normal")]


def state_key(leaf: str) -> str:
    """The program's ``state_dict`` key of a leaf."""
    top = {"wte": "model.embed_tokens.weight",
           "norm.g": "model.final_layernorm.weight",
           "norm.b": "model.final_layernorm.bias"}
    if leaf in top:
        return top[leaf]
    return "model." + re.sub(r"\.b$", ".bias", re.sub(r"\.(w|g)$", ".weight", leaf))


def initial_values(cfg: dict, weights: dict) -> dict:
    """The drawn leaves laid over Mamba's published initialisation, for the
    program (``build``) and the reference (``forward_logits``) alike. Drawn
    as every other leaf is (``normal(0, 0.02)``), ``A = -exp(A_log)`` would be
    -1 and ``Delta = softplus(0)`` 0.69 in every channel: a state that halves
    every token carries nothing, and no check would notice one dropped, reset
    or rounded. So ``A_log = log(1..N) + draw`` (state row ``n`` decays at
    rate ``n``) and ``dt_proj.b = softplus^-1(dt_c) + draw`` with ``dt_c``
    log-spaced over the channels from 0.001 to 0.1: the states remember 10 to
    1,000 tokens, as trained ones do. (``D`` and the convolution's taps are
    drawn about 1 already: their leaves are of kind ``gain``.)"""
    import jax.numpy as jnp

    N, di = cfg["mamba_d_state"], d_inner(cfg)
    rates = jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None]
    dt_c = jnp.exp(jnp.linspace(math.log(DT_MIN), math.log(DT_MAX), di))
    inverse = dt_c + jnp.log(-jnp.expm1(-dt_c))  # softplus^-1
    out = dict(weights)
    for stack in ("front", "mid"):
        for leaf, base in ((f"{stack}.mamba.A_log", rates),
                           (f"{stack}.mamba.dt_proj.b", inverse)):
            w = weights[leaf]
            out[leaf] = (base + w.astype(jnp.float32)).astype(w.dtype)
    return out


def forward_logits(cfg: dict, weights: dict, ids, mode: str):
    """The plain reference as the harness asks for it, over the same initial
    values the program is built with."""
    return reference.forward_logits(cfg, initial_values(cfg, weights), ids, mode)


def build(cfg: dict, weights: dict):
    """``PhiFlashForCausalLM`` at the file's sizes HOLDING ``weights`` (over
    ``initial_values``): the class wraps the arrays it is given (a model that
    initialised itself first would not fit beside them) and refuses a leaf it
    has no parameter for, or a parameter no leaf fills. Returns ``(model,
    {leaf: Parameter})``."""
    weights = initial_values(cfg, weights)
    with model.default_dtype(cfg["dtype"]):
        net = PhiFlashForCausalLM(PhiFlashConfig.from_dict(cfg),
                                  weights={state_key(k): v for k, v in weights.items()})
    state = net.state_dict()
    return net, {leaf: state[state_key(leaf)] for leaf in weights}


# -- what the algorithm needs, from shapes: nothing padded, nothing recomputed
def param_count(cfg: dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(cfg))


def weight_bytes_per_step(cfg: dict, itemsize: int = 2) -> float:
    """What every decode step reads of the weights: every parameter once.
    The head IS the embedding (tied), read whole; the token lookup's rows of
    it are not counted a second time."""
    return float(param_count(cfg) * itemsize)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one token in ONE attention layer."""
    h = cfg["hidden_size"] // cfg["num_attention_heads"]
    return float(2 * cfg["num_key_value_heads"] * h * itemsize)


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """What a token of context occupies over ALL layers: one layer's K and V
    (it sizes the pool). The window and the state do not grow with it."""
    return kv_bytes_per_token(cfg, itemsize) * layer_kinds(cfg).count("full")


def paged_readers(cfg: dict) -> int:
    """Layers that read the one paged pool a step: the full-attention layer
    and the cross layers."""
    kinds = layer_kinds(cfg)
    return kinds.count("full") + kinds.count("cross")


def window_bytes_per_row(cfg: dict, itemsize: int = 2) -> float:
    """The window layers' K and V of a row whose windows are full."""
    return (layer_kinds(cfg).count("window") * cfg["sliding_window"]
            * kv_bytes_per_token(cfg, itemsize))


def window_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """What a step reads of one token inside the windows, over the window
    layers."""
    return layer_kinds(cfg).count("window") * kv_bytes_per_token(cfg, itemsize)


def state_bytes_per_row(cfg: dict) -> float:
    """The scan layers' states of a row, float32."""
    return float(layer_kinds(cfg).count("mamba") * cfg["mamba_d_state"] * d_inner(cfg) * 4)


def conv_tail_bytes_per_row(cfg: dict, itemsize: int = 2) -> float:
    """The scan layers' last K - 1 convolution inputs of a row."""
    return float(layer_kinds(cfg).count("mamba") * (cfg["mamba_d_conv"] - 1)
                 * d_inner(cfg) * itemsize)


def state_update_bytes(cfg: dict, rows: float) -> float:
    """One call of the ``state_update`` kernel (one layer, ``rows`` live
    rows): the state read and written, each row's Delta and c read and its y
    written (float32), its B and C."""
    di, N = d_inner(cfg), cfg["mamba_d_state"]
    return float(rows * (2 * N * di * 4 + 3 * di * 4 + 2 * N * 4))


def state_update_flops(cfg: dict, rows: float) -> float:
    """Of the same call: a state entry's decay (exp, multiply), its input
    (multiply, add) and its part of the output (multiply, add)."""
    return float(rows * 6 * cfg["mamba_d_state"] * d_inner(cfg))


def selective_scan_bytes(cfg: dict, tokens: float, rows: float) -> float:
    """One call of the ``selective_scan`` kernel (one layer, ``tokens`` real
    prompt tokens of ``rows`` rows): Delta and c read and y written a token
    (float32), its B and C, and the last state written a row."""
    di, N = d_inner(cfg), cfg["mamba_d_state"]
    return float(tokens * (3 * di * 4 + 2 * N * 4) + rows * N * di * 4)


def selective_scan_flops(cfg: dict, tokens: float) -> float:
    return float(tokens * 6 * cfg["mamba_d_state"] * d_inner(cfg))


def decode_step_bytes(cfg: dict, rows: float, shared_kv_tokens: float,
                      window_tokens: float) -> float:
    """What ONE decode step must read and write: the weights, the one cached
    layer's K and V of every live row's context once a reader, the tokens
    inside the windows once a window layer, and every live row's states and
    convolution tails, read and written."""
    return (weight_bytes_per_step(cfg)
            + paged_readers(cfg) * shared_kv_tokens * kv_bytes_per_token(cfg)
            + window_tokens * window_bytes_per_token(cfg)
            + 2 * rows * (state_bytes_per_row(cfg) + conv_tail_bytes_per_row(cfg)))


# -- what the device-trace readers share: the decode steps and the prefills of
# the traced stretch, from the device's program line and the program's spans
def trace_facts(run):
    """``None`` without a device trace or without ``shared_kv_tokens`` on the
    program's ``decode_step`` spans (a program that lacks the arch has none),
    else a dict: ``steps`` and ``step_ns`` (count and summed device time of
    the ``jit_step`` programs in the traced window); ``rows``,
    ``shared_kv_tokens`` and ``window_tokens`` (means a step of the spans'
    attributes, over the ``decode_step`` spans that start inside it);
    ``scan_tokens`` and ``scan_rows`` (sums over the ``prefill`` spans
    there); ``ops`` (the device's leaf operations there)."""
    from benchmark.trace import reduce as R, summary

    red, spans = run["trace"], run["spans"]
    if red is None or spans is None or red.get("sync_ns") is None:
        return None
    t0, t1 = summary.window_ns(red)
    off = red["sync_ns"]
    inside = lambda name: [r[4] for r in spans.named(name) if t0 <= r[1] + off <= t1]
    seen = [a for a in inside("decode_step") if "shared_kv_tokens" in a]
    step_ns = steps = 0
    for dev in red["devices"].values():
        ns, k = R.total_ns(R.clip(dev["modules"], t0, t1), r"^jit_step\(")
        step_ns, steps = step_ns + ns, steps + k
    if not seen or not steps:
        return None
    mean = lambda key: sum(a[key] for a in seen) / len(seen)
    fills = [a for a in inside("prefill") if "scan_tokens" in a]
    return {"steps": steps, "step_ns": step_ns, "rows": mean("rows"),
            "shared_kv_tokens": mean("shared_kv_tokens"),
            "window_tokens": mean("window_tokens"),
            "scan_tokens": sum(a["scan_tokens"] for a in fills),
            "scan_rows": sum(a["rows"] for a in fills),
            "ops": [e for ev in summary.device_ops(red).values() for e in ev]}


def kernel_ns(ops, name: str) -> tuple:
    """(summed duration, count) of the device operations whose HLO
    instruction is named ``name`` (a Pallas kernel carries its own)."""
    hit = [d for line, _, d in ops if re.match(rf"%?{re.escape(name)}[.\d]* = ", line)]
    return sum(hit), len(hit)


def span_mean(run, key: str):
    """Mean over the window's ``decode_step`` spans of an attribute, or None
    where no span carries it."""
    if run["spans"] is None:
        return None
    rows = [r[4][key] for r in run["spans"].named("decode_step", *run["span_window_ns"])
            if key in r[4]]
    return sum(rows) / len(rows) if rows else None
