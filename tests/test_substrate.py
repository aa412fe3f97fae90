"""Distributed-substrate tests: optimizer, data determinism, checkpointing,
fault tolerance, gradient compression, sharding planner, quantized serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.data import calibration_tokens, synthetic_image_batch, token_batch
from repro.optim import (
    adamw_init,
    adamw_update,
    compressed_mean,
    cosine_schedule,
    ef_compress,
    ef_init,
)
from repro.runtime import FaultTolerantLoop, StragglerMonitor, elastic_restore


# ------------------------------------------------------------------ optimizer
def test_adamw_reduces_quadratic_loss():
    key = jax.random.PRNGKey(0)
    target = jax.random.normal(key, (8, 8))
    params = {"w": jnp.zeros((8, 8))}
    state = adamw_init(params)
    loss = lambda p: jnp.mean((p["w"] - target) ** 2)
    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(g, state, params, lr=3e-2,
                                        weight_decay=0.0)
    assert float(loss(params)) < 0.01 * l0


def test_adamw_clips_global_norm():
    params = {"w": jnp.zeros((4,))}
    state = adamw_init(params)
    huge = {"w": jnp.full((4,), 1e9)}
    _, _, gn = adamw_update(huge, state, params, lr=1e-3, clip_norm=1.0)
    assert float(gn) > 1e8  # reported pre-clip norm


def test_cosine_schedule_shape():
    assert float(cosine_schedule(0, peak_lr=1.0, warmup=10, total=100)) == 0.0
    assert abs(float(cosine_schedule(10, peak_lr=1.0, warmup=10, total=100)) - 1.0) < 1e-6
    assert float(cosine_schedule(100, peak_lr=1.0, warmup=10, total=100)) <= 0.11


# ----------------------------------------------------------------------- data
def test_token_batch_deterministic_and_shard_independent():
    a = token_batch(0, step=3, shard=1, batch=4, seq=16, vocab=100)
    b = token_batch(0, step=3, shard=1, batch=4, seq=16, vocab=100)
    c = token_batch(0, step=3, shard=2, batch=4, seq=16, vocab=100)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))
    assert int(jnp.max(a["tokens"])) < 100


def test_labels_are_next_tokens():
    b = token_batch(0, 0, 0, 2, 8, 50)
    # structurally: labels[t] should continue the stream (bigram structure is
    # learnable); here just check shapes/dtypes and range
    assert b["tokens"].shape == b["labels"].shape == (2, 8)


def test_calibration_tokens_data_free():
    t1 = calibration_tokens(0, 4, 32, 1000)
    t2 = calibration_tokens(0, 4, 32, 1000)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_synthetic_images_class_structure():
    b = synthetic_image_batch(0, 0, 64, 16, 3, 4)
    assert b["x"].shape == (64, 16, 16, 3)
    assert set(np.unique(np.asarray(b["y"]))) <= set(range(4))


# ----------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4) * 2}}
    ckpt.save(5, tree, blocking=True)
    restored, step = ckpt.restore(tree)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))


def test_checkpoint_retention_and_latest(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        ckpt.save(s, tree, blocking=True)
    assert ckpt.latest_step() == 4
    dirs = sorted(os.listdir(tmp_path))
    assert "step_1" not in dirs and "step_2" not in dirs


def test_checkpoint_ignores_partial_writes(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    tree = {"a": jnp.zeros(3)}
    ckpt.save(1, tree, blocking=True)
    os.makedirs(tmp_path / "step_9.tmp-123")  # simulated crash mid-write
    assert ckpt.latest_step() == 1
    ckpt2 = Checkpointer(str(tmp_path))  # restart cleans tmp
    assert not any(".tmp" in d for d in os.listdir(tmp_path))


def test_checkpoint_async(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    tree = {"a": jnp.arange(1000)}
    ckpt.save(7, tree, blocking=False)
    ckpt.wait()
    assert ckpt.latest_step() == 7


def test_elastic_restore_onto_new_mesh(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    tree = {"blocks": {"w": jnp.arange(512, dtype=jnp.float32).reshape(2, 16, 16)}}
    ckpt.save(1, tree, blocking=True)
    mesh = jax.make_mesh((1,), ("data",))  # "new" world: 1 device CPU
    restored, step = elastic_restore(ckpt, tree, mesh)
    np.testing.assert_array_equal(np.asarray(restored["blocks"]["w"]),
                                  np.asarray(tree["blocks"]["w"]))


# ----------------------------------------------------------- fault tolerance
def _toy_step(state, batch):
    state = {"x": state["x"] + jnp.sum(batch["tokens"]) * 0 + 1}
    return state, {"loss": 1.0 / float(state["x"])}


def test_ft_loop_runs_and_checkpoints(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    loop = FaultTolerantLoop(
        _toy_step, lambda s: token_batch(0, s, 0, 2, 8, 50), ckpt, ckpt_every=5
    )
    state, end = loop.run({"x": jnp.zeros(())}, 0, 12)
    assert end == 12 and loop.metrics.steps_run == 12
    assert ckpt.latest_step() == 12


def test_ft_loop_retries_and_restores(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(0, {"x": jnp.zeros(())}, blocking=True)
    fail_at = {7}
    fired = []

    def inject(step):
        if step in fail_at and step not in fired:
            fired.append(step)
            return True
        return False

    loop = FaultTolerantLoop(
        _toy_step, lambda s: token_batch(0, s, 0, 2, 8, 50), ckpt, ckpt_every=5
    )
    state, end = loop.run({"x": jnp.zeros(())}, 0, 10, inject_failure=inject)
    assert end == 10
    assert loop.metrics.retries == 1 and loop.metrics.restores == 1
    # replayed from step 5 checkpoint → state counts every step exactly once
    assert float(state["x"]) == 10.0


def test_ft_loop_preemption_checkpoint(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    loop = FaultTolerantLoop(
        _toy_step, lambda s: token_batch(0, s, 0, 2, 8, 50), ckpt, ckpt_every=100
    )

    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        if calls["n"] == 3:
            loop.request_preemption()
        return _toy_step(state, batch)

    loop.step_fn = step_fn
    state, end = loop.run({"x": jnp.zeros(())}, 0, 50)
    assert loop.metrics.preempted and end == 3
    assert ckpt.latest_step() == 3  # clean preemption checkpoint


def test_straggler_monitor_detects_slow_steps():
    mon = StragglerMonitor(threshold=2.0, warmup_steps=1)
    for s in range(10):
        mon.observe(s, 0.1)
    assert mon.observe(10, 0.5)  # 5× slower
    assert len(mon.events) == 1
    assert not mon.observe(11, 0.11)  # EMA not poisoned by the spike


# ------------------------------------------------------ gradient compression
def test_ef_compress_error_feedback_unbiased():
    """Error feedback makes the LONG-RUN compressed sum match fp: the paper's
    bias-correction principle applied to gradient compression."""
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (256,)) * 0.01
    residual = jnp.zeros_like(g)
    acc_q = jnp.zeros_like(g)
    for i in range(50):
        q, scale, residual = ef_compress(g, residual)
        acc_q = acc_q + q.astype(jnp.float32) * scale
    acc_fp = g * 50
    rel = float(jnp.linalg.norm(acc_q - acc_fp) / jnp.linalg.norm(acc_fp))
    assert rel < 0.01


def test_compressed_mean_under_shard_map():
    from functools import partial

    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("dp",))
    g = jax.random.normal(jax.random.PRNGKey(1), (64,))
    r = jnp.zeros_like(g)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def sync(g, r):
        return compressed_mean(g, r, "dp")

    mean, new_r = sync(g, r)
    np.testing.assert_allclose(np.asarray(mean + new_r), np.asarray(g),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- sharding planner
def test_params_pspecs_rules():
    from jax.sharding import PartitionSpec as P

    from repro.sharding import params_pspecs

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # simulate a (4, 4) production mesh via shape dict for rule checking

    class FakeMesh:
        shape = {"data": 4, "model": 4}

    shapes = {
        "embed": jax.ShapeDtypeStruct((1024, 512), jnp.float32),
        "lm_head": jax.ShapeDtypeStruct((512, 1024), jnp.float32),
        "blocks": {
            "attn": {"wq": jax.ShapeDtypeStruct((8, 512, 896), jnp.float32)},
            "mlp": {
                "experts": {"wu": jax.ShapeDtypeStruct((8, 4, 512, 1024), jnp.float32)}
            },
            "norm": {"w": jax.ShapeDtypeStruct((8, 512), jnp.float32)},
        },
    }
    specs = params_pspecs(shapes, FakeMesh(), heads={"n_q": 8, "n_kv": 8})
    assert specs["embed"] == P("model", "data")
    assert specs["lm_head"] == P("data", "model")
    assert specs["blocks"]["attn"]["wq"] == P(None, "data", "model")
    # expert dim (4, not ≥128) and scan dim never sharded
    assert specs["blocks"]["mlp"]["experts"]["wu"] == P(None, None, "data", "model")
    assert specs["blocks"]["norm"]["w"] == P()
    # head count not divisible by the model axis → attention out replicates
    specs_bad = params_pspecs(shapes, FakeMesh(), heads={"n_q": 14, "n_kv": 2})
    assert specs_bad["blocks"]["attn"]["wq"] == P(None, "data", None)
    # row-parallel second matrices: in=model, out=data
    shapes_wd = {"blocks": {"mlp": {"wd": jax.ShapeDtypeStruct((8, 1024, 512), jnp.float32)}}}
    specs_wd = params_pspecs(shapes_wd, FakeMesh())
    assert specs_wd["blocks"]["mlp"]["wd"] == P(None, "model", "data")


def test_cache_pspecs_long_context_seq_sharding():
    from jax.sharding import PartitionSpec as P

    from repro.sharding import cache_pspecs

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    shapes = {
        "k": jax.ShapeDtypeStruct((4, 1, 524288, 8, 128), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((4, 1, 524288, 8, 128), jnp.bfloat16),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    specs = cache_pspecs(shapes, FakeMesh(), batch=1)
    # batch=1 unshardable → sequence axis takes the data axis
    assert specs["k"][2] == "data"


# ---------------------------------------------------------- quantized serving
def test_qtensor_roundtrip_and_dispatch():
    from repro.quantized import QTensor, quantize_param

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 32)) * 0.1
    qt = quantize_param(w, per_channel=True)
    np.testing.assert_allclose(np.asarray(qt.dequant()), np.asarray(w),
                               atol=float(jnp.max(qt.scale)) * 0.51)
    from repro.models.layers import linear

    x = jax.random.normal(key, (4, 8, 64))
    y_fp = linear(x, w, None)
    y_q = linear(x, qt, None)
    rel = float(jnp.linalg.norm(y_q - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.02


def test_quantized_lm_serving_end_to_end():
    """DFQ → int8 serving params → decode matches fp within int8 noise, and
    parameter bytes shrink ≈ 4×."""
    from repro.configs import get_config
    from repro.core import DFQConfig, apply_dfq
    from repro.models import build_model
    from repro.quantized import dequantize_params, quantize_for_serving, serving_summary

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plan = model.dfq_plan()
    params_eq = apply_dfq(params, plan, DFQConfig())
    qparams = quantize_for_serving(params_eq, plan, mode="w8a16")

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    cache_fp = model.init_cache(2, 16, dtype=jnp.float32)
    cache_q = model.init_cache(2, 16, dtype=jnp.float32)
    logits_fp, _ = model.prefill(params_eq, tokens, cache_fp)
    logits_q, _ = model.prefill(qparams, tokens, cache_q)
    rel = float(jnp.linalg.norm(logits_q - logits_fp) / jnp.linalg.norm(logits_fp))
    assert rel < 0.05
    summary = serving_summary(qparams)
    assert summary["compression"] > 2.0
