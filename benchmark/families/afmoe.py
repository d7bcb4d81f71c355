"""The ``afmoe`` family (arcee-ai Trinity-Mini: window layers of 2,048 with
rotary positions beside full layers without any, grouped-query heads of 128
with a sigmoid gate on their output, norms before and after both sub-layers,
128 routed experts at 8 a token and a shared one behind two dense layers):
where its configurations meet the program (``build``:
``paddle_tpu.models.afmoe``, every key of the file mapped onto ``AfmoeConfig``
by name), the plain reference (``reference/afmoe.py``, re-exported through
``forward_logits``) and the counts its readers divide by.

A configuration is ONE CHIP'S SHARE of a layer under expert parallelism:
``num_experts`` says how many routed experts are held here (the first ones),
``published.num_experts`` is the router's width, which is never cut; the
program (``AfmoeConfig.held_experts``) and the reference compute the held
experts' part of every layer plus the shared expert, and that partial result
goes on to the next layer. ``vocab_size`` is the chip's slice of the
vocabulary: a smaller vocabulary.

Served only: no ``TrainReference``. What a decode step must move is of four
kinds: the weights outside the routed experts (every step; of the embedding
only the fed rows), the three matrices of each HELD expert its live rows hit,
K and V of the full layers over each live row's context, and of the window
layers over the part of it inside the window. The step's share of the HBM
peak is ``trinity_decode_hbm_mfu_pct``. The family gives no ``weight_bytes``:
``decode_hbm_roofline``, the dense model's share, lists its own cell.

Hand-worked values at the published widths are in
tests/benchmark/test_benchmark_trinity.py.
"""
from __future__ import annotations

import re

import numpy as np

from benchmark import model
# the same functions of the same keys: layers with leaves of their own, the
# expert layers, one expert's three matrices, the spans' means
from benchmark.families.lfm2 import (expert_bytes, expert_layers, span_mean,  # noqa: F401
                                     unrolled_layers)
from benchmark.families.xing4 import decode_trace_facts, kernel_ns  # noqa: F401
from benchmark.reference import afmoe as reference
# at the top, not inside ``build``: a checkout whose program lacks the class
# (the parent of the PR that added it) then fails when the cell's files are
# loaded, before it has made 4.2 GB of weights for a model it cannot build
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration:
# two dense layers, one whole period [sliding, full, sliding, sliding] and half
# a one, a window of 8 that every request of the rehearsal passes, 4 of 16
# experts held; nothing at a width worth timing
REHEARSE = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 8,
            "layer_types": ["sliding_attention", "sliding_attention",
                            "sliding_attention", "full_attention"] * 2,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "intermediate_size": 160, "moe_intermediate_size": 32,
            "num_experts": 4, "num_experts_per_tok": 4,
            "published": {"num_experts": 16}, "sliding_window": 8,
            "max_position_embeddings": 256, "dtype": "float32"}

# The narrowest margin (``reference/afmoe.held_margin``: how far the nearest
# expert HELD HERE is from changing sides, in ``score + bias``, over the expert
# layers of a position) at which the float32 reference still gives a verdict on
# a served token, as ``families/xing4.py`` withholds it and for its reason:
# top-k routing is a step function, and at a near-tie bfloat16 and float32 pick
# different experts and both are the model. MEASURED on this family, not
# copied: cells/serve-trinity-shortlong-pinned.json's ``limit_note`` has the
# readings by margin.
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
              f"positions (padding included): every held expert's margin >= {keep}",
              flush=True)
    return logits


router_width = reference.router_width


def layers_of(cfg: dict, kind: str) -> int:
    return list(cfg["layer_types"]).count(kind)


def _layer(cfg: dict, experts: bool) -> list:
    d, H, G, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    out = [(f"{n}.g", (d,), "gain") for n in
           ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")]
    out += [("attn.qkvg.w", (d, 2 * (H + G) * D), "normal"),
            ("attn.q_norm.g", (D,), "gain"), ("attn.k_norm.g", (D,), "gain"),
            ("attn.o.w", (H * D, d), "normal")]
    if not experts:
        F = cfg["intermediate_size"]
        return out + [("mlp.gate.w", (d, F), "normal"), ("mlp.up.w", (d, F), "normal"),
                      ("mlp.down.w", (F, d), "normal")]
    E, held, f = router_width(cfg), cfg["num_experts"], cfg["moe_intermediate_size"]
    out += [("mlp.router.w", (d, E), "normal"), ("mlp.router.e_bias", (E,), "normal"),
            ("mlp.experts.gate", (held, d, f), "normal"),
            ("mlp.experts.up", (held, d, f), "normal"),
            ("mlp.experts.down", (held, f, d), "normal")]
    if cfg["num_shared_experts"]:
        out += [("mlp.shared.gate.w", (d, f), "normal"), ("mlp.shared.up.w", (d, f), "normal"),
                ("mlp.shared.down.w", (f, d), "normal")]
    return out


def leaf_specs(cfg: dict) -> list:
    """``[(name, shape, kind)]`` in a fixed order. Matrices are (in, out), as
    the equations in ``reference/afmoe.py`` use them; a layer's four
    projections are ONE leaf ``[q | k | v | gate]``, its HELD experts one leaf
    a matrix, stacked (held, in, out), the router over all published experts.
    The layers behind the dense ones are ONE leaf a matrix and a position of
    the layer pattern, stacked over its repetitions, as the program scans
    them: ``h<j>.stack.*``, ``j`` the first layer at that position (layers
    ``j``, ``j + 4``, ``j + 8`` at the published pattern), so that
    ``weights.py`` draws the four positions' leaves of one suffix together,
    as it draws those of the unrolled layers; the dense layers and a partial
    last repetition are ``h<i>.*``."""
    nd, own = cfg["num_dense_layers"], unrolled_layers(cfg)
    specs = [("wte", (cfg["vocab_size"], cfg["hidden_size"]), "normal")]
    for i in own:
        specs += [(f"h{i}.{n}", s, k) for n, s, k in _layer(cfg, i >= nd)]
    turns = (cfg["num_hidden_layers"] - len(own)) // max(len(reference.period(cfg)), 1)
    if turns:
        for j in range(len(reference.period(cfg))):
            specs += [(f"h{nd + j}.stack.{n}", (turns,) + s, k)
                      for n, s, k in _layer(cfg, True)]
    return specs + [("norm.g", (cfg["hidden_size"],), "gain"),
                    ("head.w", (cfg["hidden_size"], cfg["vocab_size"]), "normal")]


_KEYS = {"in_norm": "input_layernorm", "post_attn_norm": "post_attention_layernorm",
         "pre_mlp_norm": "pre_mlp_layernorm", "post_mlp_norm": "post_mlp_layernorm",
         "attn.qkvg": "self_attn.qkvg", "attn.q_norm": "self_attn.q_norm",
         "attn.k_norm": "self_attn.k_norm", "attn.o": "self_attn.o_proj"}


def state_key(leaf: str, dense_layers: int) -> str:
    """The program's ``state_dict`` key of a leaf; the stacked leaves start
    behind the ``dense_layers`` leading layers."""
    top = {"wte": "model.embed_tokens.weight", "norm.g": "model.norm.weight",
           "head.w": "lm_head.weight"}
    if leaf in top:
        return top[leaf]
    where, _, rest = leaf.partition(".")
    if rest.startswith("stack."):  # h<first layer>.stack.*: position j of the period
        rest = rest[len("stack."):]
        where = f"model.body.{int(where[1:]) - dense_layers}."
    else:
        where = f"model.layers.{int(where[1:])}."
    rest = rest.replace("mlp.router.e_bias", "mlp.router.expert_bias")
    stem, dot, end = rest.rpartition(".")
    if end in ("w", "g"):
        rest = _KEYS.get(stem, stem) + ".weight"
    return where + rest


def program_config(cfg: dict) -> AfmoeConfig:
    """The file's keys on the program's class: its ``num_experts`` is the
    router's width, and the experts the file counts are ``held_experts``."""
    return AfmoeConfig.from_dict({**cfg, "num_experts": router_width(cfg),
                                  "held_experts": reference.held(cfg)})


def build(cfg: dict, weights: dict):
    """``AfmoeForCausalLM`` at the file's sizes HOLDING ``weights``: the class
    wraps the arrays it is given and refuses a leaf it has no parameter for,
    or a parameter no leaf fills. Returns ``(model, {leaf: Parameter})``."""
    key = lambda leaf: state_key(leaf, cfg["num_dense_layers"])
    with model.default_dtype(cfg["dtype"]):
        net = AfmoeForCausalLM(program_config(cfg),
                               weights={key(k): v for k, v in weights.items()})
    state = net.state_dict()
    return net, {leaf: state[key(leaf)] for leaf in weights}


# -- what the algorithm needs, from shapes: nothing padded, nothing recomputed
def param_count(cfg: dict) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaf_specs(cfg))


def dense_bytes_per_step(cfg: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes of weights EVERY decode step reads whatever its routing: every
    parameter outside the routed experts once, but of the embedding (the head
    is untied) only the ``rows`` fed tokens' rows."""
    routed = expert_layers(cfg) * cfg["num_experts"] * expert_bytes(cfg, 1)
    table = cfg["vocab_size"] * cfg["hidden_size"]
    return float((param_count(cfg) - routed - table + rows * cfg["hidden_size"]) * itemsize)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one token in ONE attention layer."""
    return float(2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """What a token of context occupies over ALL layers that grow with it:
    K and V of the full layers (it sizes the pool). The window layers' rings
    are a fixed size a row slot (``window_bytes_per_row``), which the engine
    allocates by its rows: a cell leaves them room in ``headroom_bytes``."""
    return kv_bytes_per_token(cfg, itemsize) * layers_of(cfg, "full_attention")


def window_bytes_per_row(cfg: dict, itemsize: int = 2) -> float:
    """The window layers' rings of one row slot."""
    return (layers_of(cfg, "sliding_attention") * cfg["sliding_window"]
            * kv_bytes_per_token(cfg, itemsize))


def decode_step_bytes(cfg: dict, rows: float, touched: float, paged_kv_tokens: float,
                      window_tokens: float) -> float:
    """What ONE decode step must read: the weights outside the experts, the
    three matrices of each held expert its live rows hit (``touched``: summed
    over the expert layers), K and V of every live row's context once a full
    layer, and of its part inside the window once a window layer."""
    return (dense_bytes_per_step(cfg, rows) + touched * expert_bytes(cfg)
            + paged_kv_tokens * cache_bytes_per_context_token(cfg)
            + window_tokens * layers_of(cfg, "sliding_attention") * kv_bytes_per_token(cfg))


def paged_read_bytes(cfg: dict, rows: float, tokens: float) -> float:
    """One call of the block-table read over grouped 128-wide heads (one
    layer, ``rows`` live rows that see ``tokens`` cached tokens in all): K and
    V of every token seen, each row's queries in and their results out."""
    io = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    return tokens * kv_bytes_per_token(cfg) + rows * io


def band_flops(cfg: dict, band_tokens: float) -> float:
    """One layer's prompt attention over ``band_tokens`` (query, key) pairs
    inside the band (the program's ``band_tokens_*``: padding and the key
    blocks the kernel skips are not in them): two products of ``head_dim`` a
    pair and a query head."""
    return 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * band_tokens


def band_bytes(cfg: dict, prompt_tokens: float, itemsize: int = 2) -> float:
    """Of the same call: the queries in, the results out, K and V once."""
    H, G, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return float(prompt_tokens * 2 * (H + G) * D * itemsize)


# -- what the device-trace readers share beyond ``decode_trace_facts``
def kernel_calls(run, name: str):
    """``[(start ns, duration ns)]`` of the device operations whose HLO
    instruction is named ``name`` (a Pallas kernel carries its own); None
    without a device trace."""
    red = run["trace"]
    if red is None or red.get("sync_ns") is None:
        return None
    hit = re.compile(rf"%?{re.escape(name)}[.\d]* = ")
    return [(s, d) for dev in red["devices"].values() for line, s, d in dev["ops"]
            if hit.match(line)]


def calls_inside(run, name: str, outer: str) -> tuple:
    """``(summed duration ns, count)`` of the kernel calls named ``name`` that
    START inside a device program named like ``outer`` (a regular expression,
    ``^jit_step\\(``) in the traced window; ``(0, 0)`` without a trace."""
    from benchmark.trace import summary

    calls = kernel_calls(run, name)
    if not calls:
        return 0, 0
    t0, t1 = summary.window_ns(run["trace"])
    inside = [(s, s + d) for dev in run["trace"]["devices"].values()
              for n, s, d in dev["modules"] if re.search(outer, n) and t0 <= s <= t1]
    took = [d for s, d in calls if any(a <= s < b for a, b in inside)]
    return sum(took), len(took)


def calls_between(calls, t0_ns: int, t1_ns: int) -> tuple:
    """``(summed duration ns, count)`` of those of ``kernel_calls`` that start
    between two times of the device's clock."""
    took = [d for s, d in calls if t0_ns <= s <= t1_ns]
    return sum(took), len(took)


def prefill_spans(run, bucket: int = None) -> list:
    """The attributes of the program's ``prefill`` spans that lie WHOLLY
    inside the traced window (with ``bucket``: of that bucket alone), each
    with ``t0_ns`` / ``t1_ns`` on the device's clock. None without a trace."""
    from benchmark.trace import summary

    red, spans = run["trace"], run["spans"]
    if red is None or spans is None or red.get("sync_ns") is None:
        return None
    t0, t1 = summary.window_ns(red)
    off = red["sync_ns"]
    return [{**r[4], "t0_ns": r[1] + off, "t1_ns": r[2] + off}
            for r in spans.named("prefill")
            if t0 <= r[1] + off and r[2] + off <= t1
            and (bucket is None or r[4].get("bucket_t") == bucket)]
