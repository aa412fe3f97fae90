"""Pallas TPU kernel: bf16 activations × int8 weights, dequant-in-VMEM.

The decode-shape kernel: weights stream from HBM as int8 (half the bytes of
bf16 ⇒ ~2× the HBM roofline for the memory-bound single-token GEMM) and are
dequantized to bf16 inside VMEM right before the MXU dot. fp32 accumulation
via a VMEM scratch; bias/scale epilogue on the last K step.

Decode blocks default to (bm, bn, bk) = (8, 512, 1024): M is the (small)
batch; wide N amortizes the per-block scale/bias loads.

Per-column scale and bias enter as lane-dense ``[1, N]`` rows and the
quantize-out row scale leaves as an ``[M, 1]`` column: Mosaic refuses
rank-1 blocks whose tiling differs from XLA's layout of the same vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, w_ref, sw_ref, bias_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(a_ref.dtype)  # int8 → compute dtype, in VMEM
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...] * sw_ref[...] + bias_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def qmatmul_w8a16_pallas(
    a: jnp.ndarray,
    w_q: jnp.ndarray,
    w_scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    bm: int = 8,
    bn: int = 512,
    bk: int = 1024,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    M, K = a.shape
    K2, N = w_q.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, w_q, w_scale.astype(jnp.float32).reshape(1, N),
      bias.astype(jnp.float32).reshape(1, N))


def _kernel_q8(a_ref, w_ref, sw_ref, bias_ref, q_ref, s_ref, acc_ref,
               *, n_k, qmax):
    """Quantize-out epilogue: per-row absmax + round in VMEM on the last K
    step — the GEMM hands the next W8A8 layer int8 directly."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(a_ref.dtype)  # int8 → compute dtype, in VMEM
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...] * sw_ref[...] + bias_ref[...]
        amax = jnp.max(jnp.abs(out), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / qmax
        q = jnp.clip(jnp.round(out / scale), -qmax - 1, qmax)
        q_ref[...] = q.astype(jnp.int8)
        s_ref[...] = scale


@functools.partial(
    jax.jit, static_argnames=("bm", "bk", "bits", "interpret")
)
def qmatmul_w8a16_q8_pallas(
    a: jnp.ndarray,
    w_q: jnp.ndarray,
    w_scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    bm: int = 8,
    bk: int = 1024,
    bits: int = 8,
    interpret: bool = False,
):
    """Weight-only GEMM emitting (int8 out, per-row scale). Single N block
    (the row absmax needs the whole row in the epilogue) → grid (M/bm, K/bk)."""
    M, K = a.shape
    K2, N = w_q.shape
    assert K == K2 and M % bm == 0 and K % bk == 0
    n_k = K // bk
    qmax = 2 ** (bits - 1) - 1
    grid = (M // bm, n_k)
    q, s = pl.pallas_call(
        functools.partial(_kernel_q8, n_k=n_k, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, N), lambda i, k: (k, 0)),
            pl.BlockSpec((1, N), lambda i, k: (0, 0)),
            pl.BlockSpec((1, N), lambda i, k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, N), lambda i, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int8),
            jax.ShapeDtypeStruct((M, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(a, w_q, w_scale.astype(jnp.float32).reshape(1, N),
      bias.astype(jnp.float32).reshape(1, N))
    return q, s[:, 0]
