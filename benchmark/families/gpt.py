"""The GPT family (Brown et al. 2020: GPT-2's pre-LN block, learned positions,
tied head): where its configurations meet the program (``build``), the plain
reference (``reference/gpt.py``, re-exported) and the counts of what the
algorithm needs. The harness finds this file by the ``family`` key of a
configuration and asks for the names below and no others (README.md lists
which a family may leave out).

Hand-worked values for GPT-3 XL are in tests/benchmark/test_benchmark_flops.py.
"""
from __future__ import annotations

import dataclasses

from benchmark import model
from benchmark.reference.gpt import TrainReference, forward_logits  # noqa: F401

# sizes of the chip-free rehearsal (--rehearse), merged over a configuration
REHEARSE = {"vocab_size": 1024, "hidden_size": 128, "num_layers": 2, "num_heads": 4,
            "head_dim": 32, "intermediate_size": 512, "max_position_embeddings": 256,
            "dtype": "float32"}


def leaf_specs(cfg: dict) -> list:
    """``[(name, shape, kind)]`` in a fixed order; kind is ``normal`` (mean
    0) or ``gain`` (mean 1). Matrices are (in, out), as the layer equations
    in ``reference/gpt.py`` use them."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    specs = [("wte", (cfg["vocab_size"], d), "normal"),
             ("wpe", (cfg["max_position_embeddings"], d), "normal")]
    for i in range(cfg["num_layers"]):
        p = f"h{i}."
        specs += [
            (p + "ln1.g", (d,), "gain"), (p + "ln1.b", (d,), "normal"),
            (p + "qkv.w", (d, 3 * d), "normal"), (p + "qkv.b", (3 * d,), "normal"),
            (p + "proj.w", (d, d), "normal"), (p + "proj.b", (d,), "normal"),
            (p + "ln2.g", (d,), "gain"), (p + "ln2.b", (d,), "normal"),
            (p + "up.w", (d, f), "normal"), (p + "up.b", (f,), "normal"),
            (p + "down.w", (f, d), "normal"), (p + "down.b", (d,), "normal"),
        ]
    specs += [("lnf.g", (d,), "gain"), ("lnf.b", (d,), "normal")]
    return specs


# leaf name within a layer -> suffix of the program's state_dict key
_LAYER_KEY = {
    "ln1.g": "ln1.weight", "ln1.b": "ln1.bias",
    "qkv.w": "attn.qkv.weight", "qkv.b": "attn.qkv.bias",
    "proj.w": "attn.proj.weight", "proj.b": "attn.proj.bias",
    "ln2.g": "ln2.weight", "ln2.b": "ln2.bias",
    "up.w": "mlp.up.weight", "up.b": "mlp.up.bias",
    "down.w": "mlp.down.weight", "down.b": "mlp.down.bias",
}


def state_key(leaf: str) -> str:
    if leaf == "wte":
        return "gpt.embeddings.word_embeddings.weight"
    if leaf == "wpe":
        return "gpt.embeddings.position_embeddings.weight"
    if leaf.startswith("lnf."):
        return "gpt.final_ln." + ("weight" if leaf.endswith(".g") else "bias")
    layer, _, rest = leaf.partition(".")
    return f"gpt.layers.{int(layer[1:])}.{_LAYER_KEY[rest]}"


def build(cfg: dict, weights: dict):
    """``GPTForPretraining`` at the file's sizes, dropout 0, in the file's
    dtype, holding ``weights``. Returns ``(model, {leaf: Parameter})``. The
    file's keys are mapped onto ``GPTConfig`` by name, so a new configuration
    of this family is a new JSON file and no code."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    fields = {f.name for f in dataclasses.fields(GPTConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    if cfg["hidden_size"] != cfg["num_heads"] * cfg["head_dim"]:
        raise ValueError(f"{cfg['name']}: heads x head_dim != hidden_size")
    with model.default_dtype(cfg["dtype"]):
        net = GPTForPretraining(GPTConfig(
            hidden_dropout=0.0, attention_dropout=0.0, **kw))
    return net, model.hold(net, weights, state_key, cfg["name"])


# -- what the algorithm needs, from shapes: nothing padded, nothing recomputed
def matmul_params_per_layer(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 3 * d * d + d * d + 2 * d * f  # qkv, proj, up, down


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3 x forward), causal attention counted once: a
    token at position t attends t+1 keys, so QK^T and PV cost 4*d*(t+1)
    and the mean over a sequence is 2*d*(seq+1). Biases, norms, GELU and the
    softmax are left out (under 1% at these widths)."""
    d, layers, vocab = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    fwd = layers * (2 * matmul_params_per_layer(cfg) + 2 * d * (seq + 1)) \
        + 2 * d * vocab
    return 3.0 * fwd


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of weights one decode step must read: every layer's matrices,
    biases and norms, and the tied embedding once (the head reads all of it;
    the token and position lookups read rows of what is already counted)."""
    d, f, layers = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_layers"]
    per_layer = matmul_params_per_layer(cfg) + (3 * d + d + f + d) + 4 * d
    return float(itemsize * (layers * per_layer + cfg["vocab_size"] * d + 2 * d))


def cache_bytes_per_context_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one cached token over all layers, each of full width."""
    return float(2 * cfg["num_layers"] * cfg["hidden_size"] * itemsize)


def head_dim(cfg: dict) -> int:
    """Width of one attention head: what tells the flash kernels' calls
    apart by heads in the device trace."""
    return int(cfg["head_dim"])
