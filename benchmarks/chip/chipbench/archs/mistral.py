"""mistral (MistralForCausalLM): the dense GQA block of
``chipbench/dense.py`` with no biases on any projection, an explicit
``head_dim`` and an untied head
(https://huggingface.co/mistralai/Mistral-Nemo-Base-2407).

The decode kernel plan, the model's operations and the pool's bytes are
the harness's dense-GQA defaults (``flops.py``, ``run.pool_bytes``)."""
from chipbench import dense

dims = dense.dims
init_leaf = dense.init_leaf
layer = dense.layer
head = dense.head


def program_config(config, ModelConfig):
    return dense.program_config(config, ModelConfig, qkv_bias=False)


def param_shapes(config):
    return dense.param_shapes(config, qkv_bias=False)
