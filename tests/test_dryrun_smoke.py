"""Integration test of the distribution stack: lower + compile sharded
train/prefill/decode programs on a multi-device mesh (8 fake CPU devices,
(2, 4) data×model mesh) for representative smoke archs.

Runs in a subprocess because XLA_FLAGS must be set before jax initializes.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import ShapeConfig
from repro.models.model import input_specs
from repro.launch.steps import (
    configure_sharding_hints, make_decode_step, make_train_step, shardings_for)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}
for arch in ["qwen2-0.5b", "mixtral-8x22b", "mamba2-2.7b", "whisper-tiny"]:
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, d_model=128, d_ff=256, vocab_size=512,
                              n_heads=4, n_kv_heads=2, head_dim=32)
    train_shape = ShapeConfig("t", 32, 8, "train")
    sh = shardings_for(cfg, train_shape, mesh)
    configure_sharding_hints(cfg, mesh)
    model, train_step = make_train_step(cfg)
    specs = input_specs(cfg, train_shape)
    batch = {"tokens": specs["tokens"], "labels": specs["labels"]}
    if cfg.is_encdec:
        batch["frames"] = specs["frames"]
    with mesh:
        c = jax.jit(train_step, in_shardings=(
            sh["params"], sh["opt"],
            {k: (sh["frames"] if k == "frames" else sh["batch"]) for k in batch},
        )).lower(sh["params_shape"], sh["opt_shape"], batch).compile()
    ma = c.memory_analysis()
    out[arch + ".train"] = int(ma.temp_size_in_bytes)

    dec_shape = ShapeConfig("d", 64, 8, "decode")
    sh = shardings_for(cfg, dec_shape, mesh)
    model, decode_step = make_decode_step(cfg)
    specs = input_specs(cfg, dec_shape)
    with mesh:
        c = jax.jit(decode_step, in_shardings=(
            sh["params"], sh["cache"], sh["batch"])).lower(
            sh["params_shape"], sh["cache_shape"], specs["token"]).compile()
    out[arch + ".decode"] = int(c.memory_analysis().temp_size_in_bytes)
print(json.dumps(out))
"""


@pytest.mark.slow
def test_multidevice_lower_compile():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 8
    for k, v in out.items():
        assert v >= 0
