"""Model zoo (language models; vision models live in paddle_tpu.vision.models)."""
from .gpt import GPTConfig, GPTModel, GPTForPretraining, gpt3_1p3b, gpt_tiny  # noqa: F401
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM, llama_7b, llama_tiny  # noqa: F401
from .ernie import ErnieConfig, ErnieModel, ErnieForPretraining, ernie_3_base  # noqa: F401
from .mla_moe import MLAMoEConfig, MLAMoEForCausalLM  # noqa: F401
