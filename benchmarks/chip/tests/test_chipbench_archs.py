"""Architectures are files, found by ``model_type``: qwen2 moved into its
file computes what the harness computed before, and a third architecture
runs through set-up and a window with new files only."""
import argparse
import hashlib
import json

import numpy as np
import pytest

from chipbench_tiny import TINY_CONFIG, make_tiny_checkout

import run
from chipbench import layout, model, reference

# Computed by the harness before architectures were files (qwen2 in
# model.py and reference.py, one table of kernel peaks in kernels.py).
QWEN2_TINY_WEIGHTS_SHA256 = (
    "a5a79eea7d06347ce5e8ce798f6f096077ed257ada9231e46ab1acdafccaeb1c")
QWEN2_TINY_GAPS = {"served_sum": 14.44529689848423,
                   "served_max": 0.8716071844100952,
                   "control_sum": 2.407651424407959,
                   "control_max": 0.28900307416915894}
QWEN2_DECODE_STEP = {       # 32 slots x 2560: calls, ops, bytes
    "serve-w8a8-kv8": {"qmatmul_w8a8": (168, 22900899840, 387609600),
                       "quantize_act": (72, 15335424, 15344640),
                       "fused_decode": (24, 7046430720, 1074069504)},
    "serve-w8a16": {"qmatmul_w8a16": (168, 22900899840, 395452416)}}
QWEN2_MODEL_FLOPS = 747237048320.0      # 1000 tokens, 123456 keys, 77 heads


def _digest(params) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update("/".join(k.key for k in path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_qwen2_weights_are_the_seeds_as_before():
    assert _digest(model.make_params(TINY_CONFIG, 2 ** 33 + 5)) == (
        QWEN2_TINY_WEIGHTS_SHA256)


def test_qwen2_reference_computes_as_before():
    params = model.make_params(TINY_CONFIG, 2 ** 33 + 5)
    tokens = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    g = reference.logit_gaps(params, TINY_CONFIG, [(tokens[:9], tokens[9:])],
                             pad_to=64, with_control=True)[0]
    got = {"served_sum": float(np.sum(g["served"].astype(np.float64))),
           "served_max": float(g["served"].max()),
           "control_sum": float(np.sum(g["control"].astype(np.float64))),
           "control_max": float(g["control"].max())}
    assert got == QWEN2_TINY_GAPS


@pytest.mark.parametrize("config", ["qwen2-0.5b.w8a8-kv8",
                                    "qwen2-0.5b.w8a16"])
def test_qwen2_counts_at_full_width_are_as_before(config):
    cell = {"qwen2-0.5b.w8a8-kv8": "qwen2-0.5b.w8a8-kv8.decode-heavy",
            "qwen2-0.5b.w8a16": "qwen2-0.5b.w8a16.decode-heavy"}[config]
    from chipbench.context import Context

    ctx = Context(layout.load_cell(cell), 1, "TPU v5 lite")
    plan = ctx.decode_step_calls()
    got = {k: (len(v["calls"]), sum(o for o, _ in v["calls"]),
               sum(b for _, b in v["calls"])) for k, v in plan.items()}
    assert got == QWEN2_DECODE_STEP[ctx.serving["recipe"]]
    assert ctx.model_flops(1000, 123456, 77) == QWEN2_MODEL_FLOPS
    assert run.pool_bytes(ctx) == 32 * 2560 * (
        6_532 if ctx.serving["kv_bits"] == 8 else 12_292)


def test_an_unknown_model_type_names_the_file_it_looked_for(tiny):
    root, bench = tiny
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(dict(cfg, model_type="nope")))
    with pytest.raises(layout.LayoutError, match=r"archs/nope\.py"):
        layout.load_cell("tiny.closed", root, bench)


# A third architecture, written as a new file: the dense block without
# biases, with a kernel plan that names a kernel the harness has never
# heard of, and counts of its own for the model's operations and the pool.
TOY_ARCH = '''
from chipbench import dense, flops

dims = dense.dims
init_leaf = dense.init_leaf
layer = dense.layer
head = dense.head


def program_config(config, ModelConfig):
    return dense.program_config(config, ModelConfig, qkv_bias=False)


def param_shapes(config):
    return dense.param_shapes(config, qkv_bias=False)


def decode_step_calls(m, B, S, recipe):
    plan = flops.decode_step_calls(m, B, S, recipe)
    plan["toy_kernel"] = {"rate": "bf16", "calls": [(1.0, 2.0)] * m["L"]}
    return plan


def model_flops(m, tokens, attended, heads):
    return 7.0 * tokens


def pool_bytes_per_position(m, serving):
    return 1000
'''


def test_a_new_architecture_needs_only_new_files(tmp_path, monkeypatch):
    from chipbench.context import Context

    bench = make_tiny_checkout(tmp_path, gap_limit=10.0)
    (bench / "chipbench" / "archs" / "toy.py").write_text(TOY_ARCH)
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(dict(cfg, model_type="toy")))
    cell = layout.load_cell("tiny.closed", tmp_path, bench)
    assert cell.arch.__file__.endswith("archs/toy.py")
    ctx = Context(cell, 1, "TPU v5 lite")
    assert ctx.decode_step_calls()["toy_kernel"]["rate"] == "bf16"
    assert ctx.model_flops(3, 0, 0) == 21.0
    assert run.pool_bytes(ctx) == 4 * 96 * 1000

    monkeypatch.setattr(run, "_configure_jax", lambda root: None)
    args = argparse.Namespace(workload="tiny.closed", seed=2 ** 31 + 11,
                              seconds=1.0, trace=0, keep_trace=None)
    r = run.run_cell(args, tmp_path, bench, require_tpu=False)
    assert r["correct"] and r["attempted"] > 0
    assert r["metrics"]["decode_tok_s"]["value"] > 0


def _stand_in(name: str, with_eps: bool):
    """A frozen dataclass with the program's ``ModelConfig`` fields, less
    ``norm_eps`` whether or not the program has one, and with a
    ``norm_eps`` of its own where ``with_eps``."""
    import dataclasses

    from repro.models.config import ModelConfig

    def copy(f):
        if f.default is not dataclasses.MISSING:
            return dataclasses.field(default=f.default)
        if f.default_factory is not dataclasses.MISSING:
            return dataclasses.field(default_factory=f.default_factory)
        return dataclasses.field()

    fields = [(f.name, f.type, copy(f)) for f in dataclasses.fields(ModelConfig)
              if f.name != "norm_eps"]
    if with_eps:
        fields.append(("norm_eps", float, dataclasses.field(default=1e-6)))
    return dataclasses.make_dataclass(name, fields, frozen=True)


@pytest.mark.parametrize("eps", [1e-6, 1e-5])
def test_the_epsilon_goes_to_the_program_or_is_refused(eps):
    """A program with ``norm_eps`` gets the file's epsilon; one without it
    serves 1e-6 alone, and any other epsilon is refused by name rather
    than served at the wrong value. Both programs are stand-ins, so the
    test holds whether or not the program's own class has the field."""
    from chipbench_tiny import TINY_MISTRAL

    with_eps, without = _stand_in("WithEps", True), _stand_in("NoEps", False)
    cfg = dict(TINY_MISTRAL, rms_norm_eps=eps)
    assert model.program_config(cfg, with_eps).norm_eps == eps
    if eps == 1e-6:
        served = model.program_config(cfg, without)
        assert served.n_layers == 3 and not hasattr(served, "norm_eps")
    else:
        with pytest.raises(layout.LayoutError, match="norm_eps"):
            model.program_config(cfg, without)
