"""qwen2 (Qwen2ForCausalLM): the dense GQA block of ``chipbench/dense.py``
with biases on q, k and v and none on o, gate, up and down
(https://huggingface.co/Qwen/Qwen2-0.5B and the Qwen2 modeling code).

The decode kernel plan, the model's operations and the pool's bytes are
the harness's dense-GQA defaults (``flops.py``, ``run.pool_bytes``)."""
from chipbench import dense

dims = dense.dims
init_leaf = dense.init_leaf
layer = dense.layer
head = dense.head


def program_config(config, ModelConfig):
    return dense.program_config(config, ModelConfig, qkv_bias=True)


def param_shapes(config):
    return dense.param_shapes(config, qkv_bias=True)
