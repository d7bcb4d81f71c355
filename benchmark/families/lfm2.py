"""The ``lfm2`` family (LiquidAI LFM2-MoE: gated short convolutions with a
two-tap state a row beside grouped-query attention layers of 64-wide heads,
a sigmoid router over routed experts with no shared one behind two dense
layers): where its configurations meet the program (``build``:
``paddle_tpu.models.lfm2_moe``, every key of the file mapped onto
``Lfm2MoeConfig`` by name), the plain reference (``reference/lfm2.py``,
re-exported through ``forward_logits``) and the counts its readers divide by.

Served only: no ``TrainReference``. What a decode step must move is of four
kinds: the weights outside the experts (every step, whatever its routing),
the three matrices of each expert its live rows hit, K and V of the attention
layers over each live row's context, and every live row's convolution states,
read and written. The step's share of the HBM peak is
``lfm2_decode_hbm_mfu_pct``. The family gives no ``weight_bytes``:
``decode_hbm_roofline``, the dense model's share, lists its own cell.

Hand-worked values at the published widths are in
tests/benchmark/test_benchmark_lfm2.py.
"""
from __future__ import annotations

import re

import numpy as np

from benchmark import model
from benchmark.families.xing4 import decode_trace_facts, kernel_ns  # noqa: F401
from benchmark.reference import lfm2 as reference
# at the top, not inside ``build``: a checkout whose program lacks the class
# (the parent of the PR that added it) then fails when the cell's files are
# loaded, before it has made 10.5 GB of weights for a model it cannot build
from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeForCausalLM

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration:
# two dense convolution layers, then two turns of [attention, conv] with
# routed experts; nothing at a width worth timing
REHEARSE = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 6,
            "layer_types": ["conv", "conv", "full_attention", "conv",
                            "full_attention", "conv"],
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 160, "moe_intermediate_size": 32,
            "num_experts": 8, "num_experts_per_tok": 2,
            "max_position_embeddings": 256, "dtype": "float32"}

# The narrowest router margin (4th against 5th of ``score + expert_bias``,
# over the expert layers of a position) at which the float32 reference still
# gives a verdict on a served token, as ``families/xing4.py`` withholds it and
# for its reason: top-k routing is a step function, and at a near-tie bfloat16
# and float32 pick different experts and both are the model. MEASURED on this
# family, not copied (PERF.md section 2; chip runs of PR 38, 39,050 served
# tokens of three seeds, the program's widest gap / the float8 control's over
# 24 requests, by the margin kept): all positions 0.68 / 1.84-1.85; 0.005:
# 0.52 / 1.48-1.84; 0.0075: 0.339 / 1.39-1.48 (1,660 judged, the five widest
# 0.322-0.339); 0.01: 0.32 / 1.10-1.48; 0.015: 0.13 / 0.46-0.79, but of some 10 tokens a
# run. At 0.0075 the two readings lie farthest apart among the margins that
# still judge enough tokens for either to be steady: 4.2% of the served
# tokens, about 180 of a run's 4,300.
ROUTER_MARGIN = 0.0075


def forward_logits(cfg: dict, weights: dict, ids, mode: str):
    """The plain reference as the harness asks for it. In ``"f32"``, which
    judges a run, a verdict only where the reference's own routing is decided
    by ``ROUTER_MARGIN`` in every expert layer; the logits of the other
    positions are all zeros, so a served token's gap there reads 0. ``"fp8"``,
    the control, is judged BY those verdicts and gives its own logits whole.
    The program's routing is never looked at."""
    keep = ROUTER_MARGIN if mode == "f32" else 0.0
    logits, margin = reference.forward(cfg, weights, ids, mode, min_margin=keep)
    if mode == "f32":
        print(f"reference: a verdict at {int((margin >= keep).sum())} of {margin.size} "
              f"positions (padding included): every router margin >= {keep}", flush=True)
    return logits


def unrolled_layers(cfg: dict) -> list:
    """Layers that have leaves of their own (``h<i>.*``): the dense ones and
    what a last, partial repetition of the pattern leaves; the others are
    stacked by position of the period (``body.<j>.*``)."""
    nd, p = cfg["num_dense_layers"], len(reference.period(cfg))
    whole = (cfg["num_hidden_layers"] - nd) // p * p if p else 0
    return [i for i in range(cfg["num_hidden_layers"]) if not nd <= i < nd + whole]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def _layer(cfg: dict, kind: str, experts: bool) -> list:
    d, H, G = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = d // H
    out = [("op_norm.g", (d,), "gain"), ("ffn_norm.g", (d,), "gain")]
    if kind == "conv":
        # the taps about 1 (kind ``gain``): the convolution's output is then
        # of the size of its input, and no tap is lost to rounding
        out += [("conv.in_proj.w", (d, 3 * d), "normal"),
                ("conv.conv.w", (cfg["conv_L_cache"], d), "gain"),
                ("conv.out_proj.w", (d, d), "normal")]
    else:
        out += [("attn.qkv.w", (d, (H + 2 * G) * D), "normal"),
                ("attn.q_norm.g", (D,), "gain"), ("attn.k_norm.g", (D,), "gain"),
                ("attn.o.w", (H * D, d), "normal")]
    if not experts:
        F = cfg["intermediate_size"]
        return out + [("mlp.gate.w", (d, F), "normal"), ("mlp.up.w", (d, F), "normal"),
                      ("mlp.down.w", (F, d), "normal")]
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return out + [("mlp.router.w", (d, E), "normal"),
                  ("mlp.router.expert_bias", (E,), "normal"),
                  ("mlp.experts.gate", (E, d, f), "normal"),
                  ("mlp.experts.up", (E, d, f), "normal"),
                  ("mlp.experts.down", (E, f, d), "normal")]


def leaf_specs(cfg: dict) -> list:
    """``[(name, shape, kind)]`` in a fixed order. Matrices are (in, out), as
    the equations in ``reference/lfm2.py`` use them, the convolution (taps,
    d), a layer's experts one leaf a matrix, stacked (experts, in, out). The
    layers behind the dense ones are ONE leaf a matrix and a position of the
    layer pattern, stacked over its repetitions, as the program scans them
    (``body.<j>.*``); the dense layers and a partial last repetition are
    ``h<i>.*``."""
    kinds, nd = cfg["layer_types"], cfg["num_dense_layers"]
    own = unrolled_layers(cfg)
    specs = [("wte", (cfg["vocab_size"], cfg["hidden_size"]), "normal")]
    for i in own:
        specs += [(f"h{i}.{n}", s, k) for n, s, k in _layer(cfg, kinds[i], i >= nd)]
    turns = (cfg["num_hidden_layers"] - len(own)) // max(len(reference.period(cfg)), 1)
    if turns:
        for j, kind in enumerate(reference.period(cfg)):
            specs += [(f"body.{j}.{n}", (turns,) + s, k) for n, s, k in _layer(cfg, kind, True)]
    return specs + [("norm.g", (cfg["hidden_size"],), "gain")]


def state_key(leaf: str) -> str:
    """The program's ``state_dict`` key of a leaf."""
    top = {"wte": "model.embed_tokens.weight", "norm.g": "model.norm.weight"}
    if leaf in top:
        return top[leaf]
    where, _, rest = leaf.partition(".")
    if where == "body":
        j, _, rest = rest.partition(".")
        where = f"model.body.{j}."
    else:
        where = f"model.layers.{int(where[1:])}."
    return where + re.sub(r"\.(w|g)$", ".weight", rest)


def build(cfg: dict, weights: dict):
    """``Lfm2MoeForCausalLM`` at the file's sizes HOLDING ``weights``: the
    class wraps the arrays it is given (a model that initialised itself first
    would not fit beside them) and refuses a leaf it has no parameter for, or
    a parameter no leaf fills. Returns ``(model, {leaf: Parameter})``."""
    with model.default_dtype(cfg["dtype"]):
        net = Lfm2MoeForCausalLM(Lfm2MoeConfig.from_dict(cfg),
                                 weights={state_key(k): v for k, v in weights.items()})
    state = net.state_dict()
    return net, {leaf: state[state_key(leaf)] for leaf in weights}


# -- what the algorithm needs, from shapes: nothing padded, nothing recomputed
def param_count(cfg: dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(cfg))


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The three matrices of one routed expert."""
    return float(3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize)


def dense_bytes_per_step(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of weights EVERY decode step reads whatever its rows and their
    routing: every parameter outside the routed experts, once. The head IS
    the embedding (tied), read whole; the token lookup's rows of it are not
    counted a second time."""
    routed = expert_layers(cfg) * cfg["num_experts"] * expert_bytes(cfg, 1)
    return float((param_count(cfg) - routed) * itemsize)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one token in ONE attention layer."""
    D = cfg["hidden_size"] // cfg["num_attention_heads"]
    return float(2 * cfg["num_key_value_heads"] * D * itemsize)


def attention_layers(cfg: dict) -> int:
    return list(cfg["layer_types"]).count("full_attention")


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """What a token of context occupies over ALL layers: K and V of the
    attention layers (it sizes the pool). The convolutions' states do not
    grow with it."""
    return kv_bytes_per_token(cfg, itemsize) * attention_layers(cfg)


def conv_state_bytes_per_row(cfg: dict, itemsize: int = 2) -> float:
    """The convolution layers' last ``conv_L_cache - 1`` inputs of a row."""
    return float(list(cfg["layer_types"]).count("conv") * (cfg["conv_L_cache"] - 1)
                 * cfg["hidden_size"] * itemsize)


def decode_step_bytes(cfg: dict, rows: float, touched: float,
                      paged_kv_tokens: float) -> float:
    """What ONE decode step must read and write: the weights outside the
    experts, the three matrices of each expert its live rows hit (``touched``:
    summed over the expert layers), K and V of every live row's context once
    an attention layer, and every live row's convolution states, read and
    written."""
    return (dense_bytes_per_step(cfg) + touched * expert_bytes(cfg)
            + paged_kv_tokens * cache_bytes_per_context_token(cfg)
            + 2 * rows * conv_state_bytes_per_row(cfg))


def paged_read_bytes(cfg: dict, rows: float, paged_kv_tokens: float) -> float:
    """One call of the block-table read over two-heads-a-line K/V (one layer,
    ``rows`` live rows whose contexts sum to ``paged_kv_tokens``): K and V of
    every live token, each row's padded queries in and their results out."""
    padded = cfg["num_attention_heads"] * 2 * (cfg["hidden_size"] // cfg["num_attention_heads"])
    return paged_kv_tokens * kv_bytes_per_token(cfg) + rows * 2 * padded * 2


# -- what the device-trace readers share beyond ``decode_trace_facts``
def span_mean(run, key: str, traced: bool = False):
    """Mean over the window's ``decode_step`` spans (``traced``: over those
    that start inside the traced stretch) of an attribute, or None where no
    span carries it."""
    spans = run["spans"]
    if spans is None:
        return None
    if traced:
        from benchmark.trace import summary

        red = run["trace"]
        if red is None or red.get("sync_ns") is None:
            return None
        t0, t1 = summary.window_ns(red)
        rows = [r[4] for r in spans.named("decode_step")
                if t0 <= r[1] + red["sync_ns"] <= t1]
    else:
        rows = [r[4] for r in spans.named("decode_step", *run["span_window_ns"])]
    rows = [a[key] for a in rows if key in a]
    return sum(rows) / len(rows) if rows else None
