"""int8 KV cache (beyond-paper: the paper's quantizer applied to the decode
memory wall): decode parity vs fp cache, ring-buffer behaviour, bytes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b"])
def test_int8_cache_decode_parity(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True), kv_cache_bits=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    full, _ = model.apply(params, toks)
    cache = model.init_cache(2, 24, dtype=jnp.float32)
    assert cache["k"].dtype == jnp.int8 and "k_scale" in cache
    # lane-dense payload: one cache position of every kv head is one row
    assert cache["k"].shape[3:] == (cfg.n_kv_heads * cfg.head_dim,)
    assert cache["k_scale"].shape[3:] == (cfg.n_kv_heads,)
    lp, cache = model.prefill(params, toks[:, :-1], cache)
    ld, cache = model.decode_step(params, toks[:, -1:], cache)
    denom = float(jnp.max(jnp.abs(full[:, -1]))) + 1e-9
    assert float(jnp.max(jnp.abs(ld - full[:, -1]))) / denom < 0.08


def test_int8_cache_bias_correct_decode_parity():
    """kv_bias_correct=True adds the v_err leaf and stays within the int8
    noise bound (the correction only removes the V error's mean component,
    it must never blow up the logits)."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b", smoke=True),
                              kv_cache_bits=8, kv_bias_correct=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    full, _ = model.apply(params, toks)
    cache = model.init_cache(2, 24, dtype=jnp.float32)
    assert "v_err" in cache
    _, cache = model.prefill(params, toks[:, :-1], cache)
    ld, cache = model.decode_step(params, toks[:, -1:], cache)
    denom = float(jnp.max(jnp.abs(full[:, -1]))) + 1e-9
    assert float(jnp.max(jnp.abs(ld - full[:, -1]))) / denom < 0.08


def test_int8_cache_halves_bytes():
    cfg8 = dataclasses.replace(get_config("yi-34b", smoke=True), kv_cache_bits=8)
    cfg16 = get_config("yi-34b", smoke=True)
    m8, m16 = build_model(cfg8), build_model(cfg16)
    c8 = jax.eval_shape(lambda: m8.init_cache(4, 128, jnp.bfloat16))
    c16 = jax.eval_shape(lambda: m16.init_cache(4, 128, jnp.bfloat16))

    def nbytes(tree, keys):
        return sum(np.prod(v.shape) * v.dtype.itemsize
                   for k, v in tree.items() if k in keys)

    b8 = nbytes(c8, ("k", "v", "k_scale", "v_scale"))
    b16 = nbytes(c16, ("k", "v"))
    assert b8 < 0.65 * b16  # payload halves; scales add hd/4 ≈ 25 % of that


def test_int8_cache_ring_buffer_swa():
    cfg = dataclasses.replace(get_config("mixtral-8x22b", smoke=True),
                              kv_cache_bits=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    T = 24  # > smoke window (16)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, cfg.vocab_size)
    full, _ = model.apply(params, toks)
    cache = model.init_cache(1, T, dtype=jnp.float32)
    logits = None
    for t in range(T):
        logits, cache = model.decode_step(params, toks[:, t:t + 1], cache)
    denom = float(jnp.max(jnp.abs(full[:, -1]))) + 1e-9
    assert float(jnp.max(jnp.abs(logits - full[:, -1]))) / denom < 0.08
