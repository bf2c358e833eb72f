"""qwen2: the llama decoder with biases on the q, k and v projections (and
none on the output projection)."""
from families import llama
from families.llama import (embed, head_fn, layer_fn, layer_leaves,  # noqa: F401
                            model_config, top_leaves)


def dims(spec: dict) -> dict:
    return dict(llama.dims(spec), qkv_bias=True)
