"""Pallas TPU kernel: int8 × int8 → int32 GEMM with fused dequant epilogue.

Tiling: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) with an
int32 VMEM scratch accumulator; the epilogue (executed on the last K step)
applies per-row activation scales × per-col weight scales and adds the bias —
which, after DFQ, already contains the paper's ε·E[x] bias-correction term,
so correction costs zero extra bandwidth at inference.

Block defaults (bm, bn, bk) = (128, 128, 512) keep the MXU dims at the
native 128 lane width and the working set
  bm·bk (int8) + bk·bn (int8) + bm·bn (int32 acc + fp32 out) ≈ 260 KiB
far under the ~16 MiB v5e VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scale_blocks(a_scale, w_scale, bias):
    """Rank-1 scale/bias vectors → the 2-D blocks Mosaic lays out like XLA:
    per-row activation scales as an ``[M, 1]`` column, per-column weight
    scales and bias as lane-dense ``[1, N]`` rows."""
    f32 = jnp.float32
    return (a_scale.astype(f32).reshape(-1, 1),
            w_scale.astype(f32).reshape(1, -1), bias.astype(f32).reshape(1, -1))


def _kernel(a_ref, w_ref, sa_ref, sw_ref, bias_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * sa_ref[...] * sw_ref[...]
        out = out + bias_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def qmatmul_w8a8_pallas(
    a_q: jnp.ndarray,
    w_q: jnp.ndarray,
    a_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    M, K = a_q.shape
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
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a_q, w_q, *_scale_blocks(a_scale, w_scale, bias))


def _kernel_q8(a_ref, w_ref, sa_ref, sw_ref, bias_ref, q_ref, s_ref, acc_ref,
               *, n_k, qmax):
    """Quantize-out epilogue variant: the dequantized row never leaves VMEM —
    the last K step re-quantizes it per-row (the exact ``quantize_act``
    formula) so the next layer's W8A8 GEMM reads int8 straight from here."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * sa_ref[...] * sw_ref[...]
        out = out + bias_ref[...]
        amax = jnp.max(jnp.abs(out), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / qmax
        q = jnp.clip(jnp.round(out / scale), -qmax - 1, qmax)
        q_ref[...] = q.astype(jnp.int8)
        s_ref[...] = scale


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bits", "interpret"),
)
def qmatmul_w8a8_q8_pallas(
    a_q: jnp.ndarray,
    w_q: jnp.ndarray,
    a_scale: jnp.ndarray,
    w_scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    bm: int = 128,
    bk: int = 512,
    bits: int = 8,
    interpret: bool = False,
):
    """W8A8 GEMM emitting (int8 out, per-row scale). The N axis is a single
    block (the per-row absmax needs the whole output row in the epilogue),
    so the grid is (M/bm, K/bk) — decode/prefill N fits VMEM comfortably."""
    M, K = a_q.shape
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
            pl.BlockSpec((bm, 1), lambda i, k: (i, 0)),
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
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(a_q, w_q, *_scale_blocks(a_scale, w_scale, bias))
    return q, s[:, 0]
