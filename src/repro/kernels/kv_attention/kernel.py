"""Pallas TPU kernel: decode attention with int8 KV dequantized in VMEM.

The EXPERIMENTS §Perf C5 finding made concrete: at 32k context the decode
roofline is the KV-cache stream. This kernel reads the cache as int8 (half
the HBM bytes of bf16) and folds the per-token scales in at score
granularity, fused with the online-softmax accumulation — one HBM pass over
the cache per token.

Semantics shared with ``ref.kv_attention_ref`` (the bit-exact oracle, which
runs the same ``attend_block`` per kv head and block):

  * **zero-scale masking** — a key position whose scale is exactly 0 is
    invalid (ragged per-slot lengths, ring-buffer holes, block padding): its
    score is forced to ``_NEG`` before the online-softmax update, and its V
    scale of 0 zeroes its contribution, so stale int8 payload contributes
    an exact 0. Real tokens always carry a scale >= 1e-8/127 (see
    ``ops.quantize_kv``), so 0 is unambiguous.
  * **GQA** — q carries ``Hq = G * Hkv`` heads in the repeat-kv convention
    (q head ``h`` reads kv head ``h // G``), handled as ``[Hkv, G, hd]``
    without materializing repeated K/V.

Layout (what Mosaic accepts): the cache payload is lane-dense, ``[B, S,
Hkv·hd]`` int8 — one cache position of every kv head is one row, so with
``Hkv·hd`` a multiple of 128 XLA's own layout of the pool leaf is the
row-major tiling the kernel's ``(1, blk, Hkv·hd)`` block reads, with no
padding and no relayout copy around the call. Each kv head is read as its
``hd`` lanes of the block, a ``[blk, hd]`` tile, so every dot is a plain
2-D matmul — no in-kernel transpose. The per-token scales are passed
lane-dense as ``[B, Hkv, 1, S]`` (a cheap transpose of the small scale
arrays outside the kernel), so a head's scales and mask are a ``[1, blk]``
row that broadcasts over its scores.

Grid (B, S/blk), S innermost; per-batch scratch carries the online-softmax
state (m, l [Hkv, Gp, 1]; acc [Hkv, Gp, hd] fp32; Gp = G padded to 8). The
ops size a block by bytes (``ref.block_rows``: up to 512 KiB of payload
each for K and V, so a grid step's fixed cost is paid rarely): a whole
2560-position ring of Hkv·hd = 128 is one block, a ring of Hkv·hd = 1024
goes in blocks of 512 positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def bf16_terms(x) -> list:
    """``x`` as bf16 terms whose sum is ``x`` bit for bit: ``x`` itself
    when it is bf16, else three, which hold all 24 bits of an fp32
    significand. The first two are ``x``'s leading bits cut off by a bit
    mask (exact in bf16) and subtracted in fp32, with no fp32 → bf16 → fp32
    round trip that a compiler keeping excess precision (XLA on TPU) could
    fold away; so the kernel and its XLA oracle split alike."""
    if x.dtype == jnp.bfloat16:
        return [x]
    terms, rest = [], x.astype(jnp.float32)
    for _ in range(2):
        bits = jax.lax.bitcast_convert_type(rest, jnp.int32)
        head = jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                            jnp.float32)
        terms.append(head.astype(jnp.bfloat16))
        rest = rest - head
    return terms + [rest.astype(jnp.bfloat16)]


def int8_as_bf16(a):
    """An int8 payload tile in bf16, which holds every int8 value exactly
    (converted through fp32, as the fp32 dots it replaces did)."""
    return a.astype(jnp.float32).astype(jnp.bfloat16)


def dot_terms(terms, y, dims):
    """Σ dot(term, y), each an fp32-accumulated single bf16 MXU pass."""
    out = None
    for t in terms:
        d = jax.lax.dot_general(t, y, (dims, ((), ())),
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def attend_block(q, kq, ks, vq, vs, m, l, acc, *, scale):
    """One online-softmax step for ONE kv head over one cache block.

    q [G, hd] (bf16, or fp32); kq/vq [blk, hd] int8; ks/vs [1, blk] fp32
    per-token scales (0 = masked); m/l [G, 1] and acc [G, hd] fp32 running
    state. Returns the updated (m, l, acc). The kernel and the blocked
    oracle both call this, so their math is one expression sequence.

    The int8 payload is exact in bf16, so each dot runs as single bf16 MXU
    passes over exact bf16 terms of its other operand (``bf16_terms``),
    every product exact and accumulated in fp32, rather than as an fp32 dot
    at HIGHEST precision (six passes): q·kᵀ in one pass for a bf16 q
    (three for fp32), p·v in three."""
    s = dot_terms(bf16_terms(q), int8_as_bf16(kq), ((1,), (1,)))
    s = jnp.where(ks > 0, s * (ks * scale), _NEG)           # [G, blk]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = dot_terms(bf16_terms(p * vs), int8_as_bf16(vq), ((1,), (0,)))
    return m_new, l, acc * corr + pv


def finish(acc, l):
    """Normalize the accumulated output (fully masked rows give 0)."""
    return acc / jnp.maximum(l, 1e-30)


def head_major(q, n_kv):
    """[B, Hq, hd] → [B, Hkv, Gp, hd] in repeat-kv head order, each group of
    G q heads zero-padded to Gp, the next multiple of the 8-row fp32 sublane
    tile. A padded row attends with a zero query and is sliced off after.
    The padding keeps every dot an (8k × hd) matmul: the MXU pads to 8 rows
    anyway, and on the CPU a one-row dot is a matrix-vector product whose
    summation order depends on the surrounding program, which would break
    the interpret-vs-oracle bit parity."""
    B, Hq, hd = q.shape
    group = Hq // n_kv
    pad = -group % 8
    q = q.reshape(B, n_kv, group, hd)
    return jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q


def head_lanes(ref, h, hd):
    """Kv head ``h``'s ``[blk, hd]`` tile of a lane-dense ``(1, blk,
    Hkv·hd)`` cache block."""
    return ref[0, :, h * hd:(h + 1) * hd]


def lane_scales(s):
    """[B, S, Hkv] per-token scales → lane-dense [B, Hkv, 1, S] fp32."""
    return s.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]


def init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def scratch(n_kv, group, hd):
    return [pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, hd), jnp.float32)]


def _kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_blk, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    hd = q_ref.shape[-1]
    for h in range(q_ref.shape[1]):
        m, l, acc = attend_block(
            q_ref[0, h], head_lanes(kq_ref, h, hd),
            ks_ref[0, h], head_lanes(vq_ref, h, hd), vs_ref[0, h],
            m_ref[h], l_ref[h], acc_ref[h], scale=scale)
        m_ref[h], l_ref[h], acc_ref[h] = m, l, acc

    @pl.when(j == n_blk - 1)
    def _epilogue():
        for h in range(q_ref.shape[1]):
            o_ref[0, h] = finish(acc_ref[h], l_ref[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk", "out_dtype", "interpret"))
def kv_attention_pallas(q, k_q, k_s, v_q, v_s, *, blk=512,
                        out_dtype=jnp.float32, interpret=False):
    """q [B, Hq, hd]; k_q/v_q [B, S, Hkv·hd] int8; k_s/v_s [B, S, Hkv].

    S must be a multiple of ``blk`` here — ``ops.kv_attention`` pads ragged
    shapes with zero-scale (masked) positions before dispatching. On TPU
    ``blk`` is a multiple of 128 or all of S (the scales' lane axis).
    """
    B, S, width = k_q.shape
    Hkv = k_s.shape[-1]
    hd = width // Hkv
    Hq = q.shape[1]
    assert S % blk == 0
    assert Hq % Hkv == 0
    group = Hq // Hkv
    n_blk = S // blk
    scale = 1.0 / (hd ** 0.5)
    qh = head_major(q, Hkv)
    gp = qh.shape[2]
    cache_spec = pl.BlockSpec((1, blk, width), lambda b, j: (b, j, 0))
    scale_spec = pl.BlockSpec((1, Hkv, 1, blk), lambda b, j: (b, 0, 0, j))
    head_spec = pl.BlockSpec((1, Hkv, gp, hd), lambda b, j: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_blk=n_blk, scale=scale),
        grid=(B, n_blk),
        in_specs=[head_spec, cache_spec, scale_spec, cache_spec, scale_spec],
        out_specs=head_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, gp, hd), out_dtype),
        scratch_shapes=scratch(Hkv, gp, hd),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qh, k_q, lane_scales(k_s), v_q, lane_scales(v_s))
    return out[:, :, :group].reshape(B, Hq, hd)
