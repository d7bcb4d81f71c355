"""The one place where a configuration file becomes the program's model.

A configuration's keys are mapped onto the family's config class by name, so
a new configuration of a family listed here is a new JSON file and no code.
"""
from __future__ import annotations

import dataclasses

# benchmark leaf name (weights.leaf_specs) -> suffix of the program's
# state_dict key for the GPT family
_GPT_LEAF = {
    "ln1.g": "ln1.weight", "ln1.b": "ln1.bias",
    "qkv.w": "attn.qkv.weight", "qkv.b": "attn.qkv.bias",
    "proj.w": "attn.proj.weight", "proj.b": "attn.proj.bias",
    "ln2.g": "ln2.weight", "ln2.b": "ln2.bias",
    "up.w": "mlp.up.weight", "up.b": "mlp.up.bias",
    "down.w": "mlp.down.weight", "down.b": "mlp.down.bias",
}


def gpt_state_key(leaf: str) -> str:
    if leaf == "wte":
        return "gpt.embeddings.word_embeddings.weight"
    if leaf == "wpe":
        return "gpt.embeddings.position_embeddings.weight"
    if leaf.startswith("lnf."):
        return "gpt.final_ln." + ("weight" if leaf.endswith(".g") else "bias")
    layer, _, rest = leaf.partition(".")
    return f"gpt.layers.{int(layer[1:])}.{_GPT_LEAF[rest]}"


def build_gpt(cfg: dict, weights: dict):
    """``GPTForPretraining`` at the file's sizes, dropout 0, in the file's
    dtype, holding ``weights``. Returns ``(model, {leaf: Parameter})``."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    fields = {f.name for f in dataclasses.fields(GPTConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    if cfg["hidden_size"] != cfg["num_heads"] * cfg["head_dim"]:
        raise ValueError(f"{cfg['name']}: heads x head_dim != hidden_size")
    import paddle_tpu as paddle

    # parameters are created in the served dtype at once: a float32 model
    # first would hold twice the bytes on the chip for nothing
    default = paddle.get_default_dtype()
    paddle.set_default_dtype(cfg["dtype"])
    try:
        model = GPTForPretraining(GPTConfig(
            hidden_dropout=0.0, attention_dropout=0.0, **kw))
    finally:
        paddle.set_default_dtype(default)
    state = model.state_dict()
    params = {}
    for leaf, arr in weights.items():
        p = state[gpt_state_key(leaf)]
        p.set_value(arr)
        params[leaf] = p
    if len(params) != len(state):
        raise ValueError(f"{cfg['name']}: the model has {len(state)} leaves, "
                         f"the benchmark made {len(params)}")
    return model, params


BUILDERS = {"gpt": build_gpt}


def build_model(cfg: dict, weights: dict):
    try:
        builder = BUILDERS[cfg["family"]]
    except KeyError:
        raise ValueError(f"no model builder for family {cfg['family']!r}") from None
    return builder(cfg, weights)
