"""Pallas TPU megakernel: append-quantize + int8 decode attention, fused.

One kernel from roped hidden state to attention out: the decode step that
used to be three dispatches (quantize_kv → cache scatter → kv_attention)
is one ``pallas_call`` — the new token's K/V is quantized in VMEM with the
exact ``ops.quantize_kv`` formula, written into its ring position of the
int8 cache block in flight, and the online-softmax attention runs over the
updated block in the same pass. The cache leaves are outputs aliased onto
their inputs (``input_output_aliases``), so the append is in-place: the
cache makes exactly one HBM round trip per token, and the fp K/V never
touches HBM at all.

Semantics are the ``kv_attention`` kernel's, inherited verbatim (zero-scale
masking, GQA via padded repeat-kv head groups, grid (B, S/blk) with
per-batch online-softmax scratch, the same lane-dense ``[B, S, Hkv·hd]``
payload and ``[B, Hkv, 1, S]`` scale layouts) — the attention step IS
``kv_attention.kernel.attend_block`` over each kv head's lanes,
so the fused path stays bit-exact to the stepwise composition, which is
what the serving parity batteries pin. The ``valid`` mask is the caller's
post-append liveness mask (it must cover the new token's position — the
token attends to itself). The ring offsets ``idx`` arrive as a scalar-
prefetch operand in SMEM.

``quantize_out=True`` adds the W8A8 epilogue: the final block re-quantizes
the attention output row (flattened [Hq·hd], the exact ``quantize_act``
formula) so the wo projection reads int8 directly — deleting the standalone
quantize_act dispatch between attention and wo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kv_attention.kernel import (
    attend_block,
    finish,
    head_lanes,
    head_major,
    init_state,
    lane_scales,
    scratch,
)


def _quant127(row, hd):
    """The ``ops.quantize_kv`` formula, in-kernel, per kv head of a
    lane-dense row: [1, Hkv·hd] fp → (int8 [1, Hkv·hd], one fp32
    absmax/127 scale [1, 1] per head). Each head's absmax is taken over its
    own lanes (the others masked to 0, which no |x| exceeds) and every lane
    divides by its head's scale, so the result is expression-identical to
    the host-side quantizer — the scale floor keeps 0 reserved for
    "invalid"."""
    tf = row.astype(jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, tf.shape, 1) // hd
    lane_scale = jnp.zeros_like(tf)
    scales = []
    for h in range(tf.shape[1] // hd):
        amax = jnp.max(jnp.where(head == h, jnp.abs(tf), 0.0), axis=-1,
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        lane_scale = jnp.where(head == h, scale, lane_scale)
        scales.append(scale)
    q = jnp.clip(jnp.round(tf / lane_scale), -127, 127).astype(jnp.int8)
    return q, scales


def _append(new_ref, q_ref, s_ref, oq_ref, os_ref, hit_blk, hit_row, hd):
    """Quantize the new token's K or V and write it into its ring row of
    this cache block (a no-op select when the row is elsewhere): the int8
    payload as one lane-dense [blk, Hkv·hd] block, the scales per kv head
    as [1, blk] rows."""
    q_n, s_n = _quant127(new_ref[0], hd)               # [1, Hkv·hd], Hkv×[1, 1]
    oq_ref[0] = jnp.where(hit_blk, q_n, q_ref[0])
    for h, s_h in enumerate(s_n):
        os_ref[0, h] = jnp.where(hit_row, s_h, s_ref[0, h])


def _kernel(idx_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, kn_ref, vn_ref,
            valid_ref, o_ref, okq_ref, oks_ref, ovq_ref, ovs_ref, *rest,
            n_blk, blk, scale, group, quantize_out, qmax):
    if quantize_out:
        oq_ref, os_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    # ---- append-quantize: the new token lands in this block iff its ring
    # offset falls inside [j·blk, (j+1)·blk)
    hd = q_ref.shape[-1]
    off = idx_ref[b] - j * blk
    hit_blk = jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0) == off
    hit_row = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) == off
    _append(kn_ref, kq_ref, ks_ref, okq_ref, oks_ref, hit_blk, hit_row, hd)
    _append(vn_ref, vq_ref, vs_ref, ovq_ref, ovs_ref, hit_blk, hit_row, hd)
    # the stored scales are UNMASKED (the cache keeps every written token);
    # only the attention inputs see the caller's liveness mask
    live = valid_ref[0] > 0                                  # [1, blk]
    for h in range(q_ref.shape[1]):
        # ---- attention over the updated block: kv_attention's own step
        m, l, acc = attend_block(
            q_ref[0, h], head_lanes(okq_ref, h, hd),
            jnp.where(live, oks_ref[0, h], 0.0), head_lanes(ovq_ref, h, hd),
            jnp.where(live, ovs_ref[0, h], 0.0),
            m_ref[h], l_ref[h], acc_ref[h], scale=scale)
        m_ref[h], l_ref[h], acc_ref[h] = m, l, acc

    @pl.when(j == n_blk - 1)
    def _epilogue():
        outs = [finish(acc_ref[h], l_ref[h]).astype(o_ref.dtype)
                for h in range(q_ref.shape[1])]
        for h, o in enumerate(outs):
            o_ref[0, h] = o
        if quantize_out:
            # the exact quantize_act formula over the out_dtype-cast output
            # row (all real heads; the padded group rows are excluded) —
            # bit-identical to the stepwise attention → quantize_act pair
            real = jax.lax.broadcasted_iota(jnp.int32, outs[0].shape, 0) < group
            amax = functools.reduce(jnp.maximum, [
                jnp.max(jnp.max(jnp.where(real, jnp.abs(o.astype(jnp.float32)),
                                          0.0), axis=-1, keepdims=True),
                        axis=0, keepdims=True)
                for o in outs])                               # [1, 1]
            oscale = jnp.maximum(amax, 1e-8) / qmax
            for h, o in enumerate(outs):
                oq = jnp.clip(jnp.round(o.astype(jnp.float32) / oscale),
                              -qmax - 1, qmax)
                oq_ref[0, h] = oq.astype(jnp.int8)
            os_ref[0] = oscale


@functools.partial(jax.jit, static_argnames=("blk", "out_dtype",
                                             "quantize_out", "interpret"))
def fused_decode_pallas(q, k_q, k_s, v_q, v_s, k_new, v_new, idx, valid, *,
                        blk=512, out_dtype=jnp.float32, quantize_out=False,
                        interpret=False):
    """q [B, Hq, hd]; k_q/v_q [B, S, Hkv·hd] int8 (lane-dense: kv head h
    owns lanes [h·hd, (h+1)·hd)); k_s/v_s [B, S, Hkv]; k_new/v_new
    [B, Hkv, hd] fp; idx [B] int32 ring offsets; valid [B, S] fp mask
    (>0 = live, must include each row's new position).

    Returns (out, k_q', k_s', v_q', v_s') — the payload outputs aliased onto
    their inputs — plus (out_q [B, Hq·hd] int8, out_scale [B]) when
    ``quantize_out``. S must be a multiple of ``blk`` (``ops.fused_decode``
    pads with zero-scale masked positions); on TPU ``blk`` is a multiple of
    128 or all of S.
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
    head_spec = pl.BlockSpec((1, Hkv, gp, hd), lambda b, j, ix: (b, 0, 0, 0))
    cache_spec = pl.BlockSpec((1, blk, width), lambda b, j, ix: (b, j, 0))
    scale_spec = pl.BlockSpec((1, Hkv, 1, blk), lambda b, j, ix: (b, 0, 0, j))
    new_spec = pl.BlockSpec((1, 1, width), lambda b, j, ix: (b, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((B, Hkv, gp, hd), out_dtype),
        jax.ShapeDtypeStruct(k_q.shape, jnp.int8),
        jax.ShapeDtypeStruct((B, Hkv, 1, S), jnp.float32),
        jax.ShapeDtypeStruct(v_q.shape, jnp.int8),
        jax.ShapeDtypeStruct((B, Hkv, 1, S), jnp.float32),
    ]
    out_specs = [head_spec, cache_spec, scale_spec, cache_spec, scale_spec]
    if quantize_out:
        out_shape += [jax.ShapeDtypeStruct((B, Hkv, gp, hd), jnp.int8),
                      jax.ShapeDtypeStruct((B, 1, 1), jnp.float32)]
        out_specs += [head_spec,
                      pl.BlockSpec((1, 1, 1), lambda b, j, ix: (b, 0, 0))]
    res = pl.pallas_call(
        functools.partial(_kernel, n_blk=n_blk, blk=blk, scale=scale,
                          group=group, quantize_out=quantize_out, qmax=127),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n_blk),
            in_specs=[head_spec, cache_spec, scale_spec, cache_spec,
                      scale_spec, new_spec, new_spec,
                      pl.BlockSpec((1, 1, blk), lambda b, j, ix: (b, 0, j))],
            out_specs=out_specs,
            scratch_shapes=scratch(Hkv, gp, hd),
        ),
        out_shape=out_shape,
        # operand 0 is the scalar-prefetched idx: the cache leaves are
        # operands 2-5, updated in place as outputs 1-4
        input_output_aliases={2: 1, 3: 2, 4: 3, 5: 4},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(idx.astype(jnp.int32), qh, k_q, lane_scales(k_s), v_q, lane_scales(v_s),
      k_new.reshape(B, 1, width), v_new.reshape(B, 1, width),
      valid.astype(jnp.float32).reshape(B, 1, S))
    out = res[0][:, :, :group].reshape(B, Hq, hd)

    def seq_major(s):             # [B, Hkv, 1, S] → [B, S, Hkv]
        return s[:, :, 0, :].transpose(0, 2, 1)

    cache = (res[1], seq_major(res[2]), res[3], seq_major(res[4]))
    if quantize_out:
        oq = res[5][:, :, :group].reshape(B, Hq * hd)
        return (out, *cache, oq, res[6].reshape(B))
    return (out, *cache)
