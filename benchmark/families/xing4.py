"""The ``xing4`` family (Xing4.0-29B-A4B and siblings of its lineage: latent
attention with YaRN, a sigmoid router over routed experts beside a shared one,
hyper-connections over several residual streams): where its configurations
meet the program (``build``: ``paddle_tpu.models.mla_moe``, every key of the
file mapped onto ``MLAMoEConfig`` by name), the plain reference
(``reference/xing4.py``, re-exported) and the counts its readers divide by.

Served only: no ``TrainReference``. The bytes a decode step reads follow the
routing (the experts its live rows hit), which no static count can: the step's
share of the HBM peak is ``moe_decode_hbm_roofline``: what every step reads
whatever its routing (``dense_bytes_per_step``) + the touched experts x
``expert_bytes`` + the live context x ``latent_bytes_per_token``.
The family gives no ``weight_bytes``: ``decode_hbm_roofline``, the dense
model's share, lists its own cell in ``BENCHMARK.json`` and is silent here.

Hand-worked values at the published widths are in
tests/benchmark/test_benchmark_xing4.py.
"""
from __future__ import annotations

import re

from benchmark import model
from benchmark.reference import xing4 as reference
# at the top, not inside ``build``: a checkout whose program lacks the class
# (the parent of the PR that added it) then fails when the cell's files are
# loaded, before it has made 11 GB of weights for a model it cannot build
from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration:
# every mechanism present (query low-rank path, YaRN, 2 dense + 2 expert
# layers, a shared expert, four streams), nothing at a width worth timing
REHEARSE = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "intermediate_size": 160,
            "moe_intermediate_size": 32, "n_routed_experts": 8,
            "num_experts_per_tok": 2, "kv_lora_rank": 32, "q_lora_rank": 24,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "max_position_embeddings": 256, "dtype": "float32"}


# The narrowest router margin at which the float32 reference still gives a
# verdict on a served token (``forward_logits``). Readings on the chip, PR 27
# (PERF.md section 2): 32,284 served positions of seven seeds, the program's
# gap over 0.2 by the position's narrowest margin: 15% under 0.001, 4.3% at
# 0.003-0.004, 1.1% at 0.005-0.006, 0.26% at 0.0075-0.009, none of 1,859 from
# 0.009 on (widest 0.076). The share falls e-fold every 0.0017; a cell is run
# sixteen times a check and one gap over the limit refuses it, so the margin
# is where that fit leaves about one such gap in a thousand runs' verdicts
# (at 0.0125: one in ten checks). About 35 of a run's 4,400-5,600 sampled
# tokens are judged; over eleven seeds the widest judged gap read 0.095.
ROUTER_MARGIN = 0.015


def forward_logits(cfg: dict, weights: dict, ids, mode: str):
    """The plain reference as the harness asks for it. In ``"f32"``, which
    judges a run, a verdict only where the reference's own routing is decided
    by ``ROUTER_MARGIN`` in every expert layer; the logits of the other
    positions are all zeros, so a served token's gap there reads 0
    (``reference.forward``: top-k routing is a step function, and at a
    near-tie bfloat16 and float32 pick different experts and both are the
    model; the WIDEST gap of a run's sample, which is what the harness limits,
    is otherwise a flipped expert's in any precision, the float8 control's
    included). ``"fp8"``, the control, is judged BY those verdicts and gives
    its own logits whole. The program's routing is never looked at."""
    keep = ROUTER_MARGIN if mode == "f32" else 0.0
    logits, margin = reference.forward(cfg, weights, ids, mode, min_margin=keep)
    if mode == "f32":
        print(f"reference: a verdict at {int((margin >= keep).sum())} of {margin.size} "
              f"positions (padding included): every router margin >= {keep}", flush=True)
    return logits


def is_expert_layer(cfg: dict, i: int) -> bool:
    return bool(cfg.get("n_routed_experts")) and i >= cfg["first_k_dense_replace"]


def expert_layers(cfg: dict) -> int:
    return sum(is_expert_layer(cfg, i) for i in range(cfg["num_hidden_layers"]))


def leaf_specs(cfg: dict) -> list:
    """``[(name, shape, kind)]`` in a fixed order. Matrices are (in, out), as
    the equations in ``reference/xing4.py`` use them; a layer's experts are
    one leaf a matrix, stacked (experts, in, out). The three maps of a
    hyper-connection are one ``phi`` = [phi_pre | phi_post | phi_res], their
    scalars one ``alpha`` (kind ``gain``: about 1, so that the entries of the
    residual map spread by about +-2 before the Sinkhorn and its twenty rounds
    matter) and their biases one ``bias``."""
    d, H, n = cfg["hidden_size"], cfg["num_attention_heads"], cfg["hc_mult"]
    r, dr, f = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], cfg["moe_intermediate_size"]
    qk, kvw = cfg["qk_nope_head_dim"] + dr, cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    E = cfg.get("n_routed_experts") or 0
    specs = [("wte", (cfg["vocab_size"], d), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"h{i}."
        for sub in ("attn", "ffn"):
            if n > 1:
                specs += [(p + f"{sub}_hc.phi", (n * d, n * (2 + n)), "normal"),
                          (p + f"{sub}_hc.alpha", (3,), "gain"),
                          (p + f"{sub}_hc.bias", (n * (2 + n),), "normal")]
            specs.append((p + f"{sub}_norm.g", (d,), "gain"))
        if cfg.get("q_lora_rank"):
            q = cfg["q_lora_rank"]
            specs += [(p + "attn.q_a.w", (d, q), "normal"),
                      (p + "attn.q_a_norm.g", (q,), "gain"),
                      (p + "attn.q_b.w", (q, H * qk), "normal")]
        else:
            specs.append((p + "attn.q.w", (d, H * qk), "normal"))
        specs += [(p + "attn.kv_a.w", (d, r + dr), "normal"),
                  (p + "attn.kv_a_norm.g", (r,), "gain"),
                  (p + "attn.kv_b.w", (r, H * kvw), "normal"),
                  (p + "attn.o.w", (H * cfg["v_head_dim"], d), "normal")]
        if is_expert_layer(cfg, i):
            specs += [(p + "mlp.router.w", (d, E), "normal"),
                      (p + "mlp.router.e_bias", (E,), "normal"),
                      (p + "mlp.experts.gate", (E, d, f), "normal"),
                      (p + "mlp.experts.up", (E, d, f), "normal"),
                      (p + "mlp.experts.down", (E, f, d), "normal")]
            if cfg.get("n_shared_experts"):
                fs = f * cfg["n_shared_experts"]
                specs += [(p + "mlp.shared.gate.w", (d, fs), "normal"),
                          (p + "mlp.shared.up.w", (d, fs), "normal"),
                          (p + "mlp.shared.down.w", (fs, d), "normal")]
        else:
            fd = cfg["intermediate_size"]
            specs += [(p + "mlp.gate.w", (d, fd), "normal"),
                      (p + "mlp.up.w", (d, fd), "normal"),
                      (p + "mlp.down.w", (fd, d), "normal")]
    specs.append(("norm.g", (d,), "gain"))
    if not cfg.get("tie_word_embeddings"):
        specs.append(("head.w", (d, cfg["vocab_size"]), "normal"))
    return specs


def state_key(leaf: str) -> str:
    """The program's ``state_dict`` key of a leaf."""
    top = {"wte": "model.embed_tokens.weight", "norm.g": "model.norm.weight",
           "head.w": "lm_head.weight"}
    if leaf in top:
        return top[leaf]
    layer, _, rest = leaf.partition(".")
    return f"model.layers.{int(layer[1:])}." + re.sub(r"\.(w|g)$", ".weight", rest)


def build(cfg: dict, weights: dict):
    """``MLAMoEForCausalLM`` at the file's sizes HOLDING ``weights``: the
    class wraps the arrays it is given (a model that initialised itself first
    would not fit beside them) and refuses a leaf it has no parameter for, or
    a parameter no leaf fills. Returns ``(model, {leaf: Parameter})``."""
    with model.default_dtype(cfg["dtype"]):
        net = MLAMoEForCausalLM(MLAMoEConfig.from_dict(cfg),
                                weights={state_key(k): v for k, v in weights.items()})
    state = net.state_dict()
    return net, {leaf: state[state_key(leaf)] for leaf in weights}


# -- what the algorithm needs, from shapes: nothing padded, nothing recomputed
def cache_row(cfg: dict) -> int:
    """Numbers a cached token OCCUPIES in a layer of the pool: the latent
    and the rotary key, padded to whole 128-lane tiles (576 -> 640)."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """What a token occupies in the pool over all layers, padding included:
    it sizes the pool."""
    return float(cfg["num_hidden_layers"] * cache_row(cfg) * itemsize)


def latent_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """What a decode step NEEDS of one cached token in ONE layer: the latent
    and the rotary key (576 numbers, 1,152 bytes)."""
    return float((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """The three matrices of one routed expert."""
    return float(3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize)


def attention_params(cfg: dict) -> int:
    d, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    q = cfg.get("q_lora_rank")
    query = d * q + q + q * H * qk if q else d * H * qk
    return (query + d * (r + cfg["qk_rope_head_dim"]) + r
            + r * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + H * cfg["v_head_dim"] * d)


def dense_bytes_per_step(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of weights EVERY decode step reads whatever its rows and their
    routing: attention, the hyper-connection maps and the norms of every
    layer, the dense FFNs, each expert layer's router and shared expert, the
    final norm and the untied head (the token lookup reads rows of the
    embedding: left out)."""
    d, n, L = cfg["hidden_size"], cfg["hc_mult"], cfg["num_hidden_layers"]
    hc = 2 * (n * d * n * (2 + n) + 3 + n * (2 + n)) if n > 1 else 0
    per_layer = attention_params(cfg) + hc + 2 * d
    n_moe = expert_layers(cfg)
    moe = d * cfg.get("n_routed_experts", 0) + cfg.get("n_routed_experts", 0) \
        + 3 * d * cfg["moe_intermediate_size"] * cfg.get("n_shared_experts", 0)
    dense = 3 * d * cfg["intermediate_size"]
    head = d + d * cfg["vocab_size"]
    return float(itemsize * (L * per_layer + n_moe * moe + (L - n_moe) * dense + head))


# -- what the three device-trace readers share: the decode steps of the traced
# stretch, from the device's program line, the program's spans and the clients
def decode_trace_facts(run):
    """``None`` without a device trace or without ``experts_touched`` on the
    program's ``decode_step`` spans (the parent has none), else a dict:
    ``steps`` and ``step_ns`` (count and summed device time of the ``jit_step``
    programs in the traced window), ``touched`` and ``rows`` (means a step of
    the spans' ``experts_touched``, summed over the expert layers, and
    ``rows``, over the spans that start inside it), ``context_tokens`` (the
    context of every token the clients got from a decode step while the trace
    ran), ``ops`` (the device's leaf operations there)."""
    from benchmark.trace import reduce as R, summary

    red, spans = run["trace"], run["spans"]
    if red is None or spans is None or red.get("sync_ns") is None:
        return None
    t0, t1 = summary.window_ns(red)
    off = red["sync_ns"]
    seen = [r[4] for r in spans.named("decode_step")
            if t0 <= r[1] + off <= t1 and "experts_touched" in r[4]]
    step_ns = steps = 0
    for dev in red["devices"].values():
        ns, k = R.total_ns(R.clip(dev["modules"], t0, t1), r"^jit_step\(")
        step_ns, steps = step_ns + ns, steps + k
    if not seen or not steps:
        return None
    a, b = red["host_window"]
    sched, context_tokens = run["schedule"], 0
    for rec in run["served"]:
        plen = int(sched.prompt_len[rec.index])
        context_tokens += sum(plen + k for k, t in enumerate(rec.stamps)
                              if k > 0 and a <= t <= b)
    return {"steps": steps, "step_ns": step_ns,
            "touched": sum(s["experts_touched"] for s in seen) / len(seen),
            "rows": sum(s["rows"] for s in seen) / len(seen),
            "context_tokens": context_tokens,
            "ops": [e for ev in summary.device_ops(red).values() for e in ev]}


def kernel_ns(ops, name: str) -> tuple:
    """(summed duration, count) of the device operations whose HLO
    instruction is named ``name`` (a Pallas kernel carries its own)."""
    hit = [d for line, _, d in ops if re.match(rf"%?{re.escape(name)}[.\d]* = ", line)]
    return sum(hit), len(hit)
