"""Public int8-KV decode-attention ops: quantize, append, attend.

This module is the one truth for the serving KV-quantization scheme — the
paper's symmetric per-token/per-head absmax quantizer (§3) applied to the
decode memory wall:

  * ``quantize_kv``        — K/V tensor → int8 payload + fp32 scales.
  * ``kv_attention``       — single-token decode attention over an int8
    cache (backend-selected: Pallas on TPU, folded-scale XLA elsewhere).
    Ragged shapes are handled by **zero-scale masking**: any position whose
    scale is 0 is invalid and contributes an exact 0; non-multiple-of-blk S
    is padded with zero-scale positions before the Pallas dispatch.
  * ``kv_attention_decode`` — the fused append-quantize decode step: the
    new token's K/V is quantized once, scattered into the int8 cache, and
    attention runs over the updated cache — the cache itself is never
    re-quantized or re-materialized in fp.

The cache payload the ops read and write is lane-dense, ``[B, S, Hkv·hd]``
int8 (kv head ``h`` owns lanes ``[h·hd, (h+1)·hd)``), with scales
``[B, S, Hkv]``; the new token's K/V arrives per head, ``[B, T, Hkv, hd]``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..dispatch import register_impl, register_spec, resolve
from .kernel import kv_attention_pallas
from .ref import flat_heads, kv_attention_ref, kv_attention_xla, pad_to_block


def quantize_kv(t: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., hd] → (int8 payload, fp32 absmax scale over the last axis).

    The scale floor (1e-8/127) guarantees real tokens never carry scale 0 —
    zero is reserved as the "position invalid" marker the attention ops key
    their masking on.
    """
    tf = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(tf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(tf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _pallas_impl(q, k_q, k_s, v_q, v_s, *, blk, out_dtype, interpret):
    # zero-scale padding: padded positions are masked exactly inside the
    # kernel's online softmax, so any S works (ragged serving rings)
    k_q, k_s, v_q, v_s, blk_e = pad_to_block(k_q, k_s, v_q, v_s, blk)
    return kv_attention_pallas(q, k_q, k_s, v_q, v_s, blk=blk_e,
                               out_dtype=out_dtype, interpret=interpret)


@register_impl("kv_attention", "pallas", pad="zero-scale")
def _kv_pallas(q, k_q, k_s, v_q, v_s, *, blk, out_dtype):
    return _pallas_impl(q, k_q, k_s, v_q, v_s, blk=blk, out_dtype=out_dtype,
                        interpret=False)


@register_impl("kv_attention", "interpret", pad="zero-scale")
def _kv_interpret(q, k_q, k_s, v_q, v_s, *, blk, out_dtype):
    return _pallas_impl(q, k_q, k_s, v_q, v_s, blk=blk, out_dtype=out_dtype,
                        interpret=True)


@register_impl("kv_attention", "xla", pad="zero-scale")
def _kv_xla(q, k_q, k_s, v_q, v_s, *, blk, out_dtype):
    return kv_attention_xla(q, k_q, k_s, v_q, v_s, out_dtype)


@register_impl("kv_attention", "ref", pad="zero-scale")
def _kv_ref(q, k_q, k_s, v_q, v_s, *, blk, out_dtype):
    # the blocked oracle pads to the kernel's zero-scale convention itself
    return kv_attention_ref(q, k_q, k_s, v_q, v_s, out_dtype, blk=blk)


def kv_attention(q, k_q, k_s, v_q, v_s, *, blk: Optional[int] = None,
                 out_dtype=jnp.float32, backend: Optional[str] = None,
                 v_err: Optional[jnp.ndarray] = None):
    """Single-token decode attention over an int8 cache.

    q [B, Hq, hd]; k_q/v_q [B, S, Hkv·hd] int8; k_s/v_s [B, S, Hkv] with
    Hq a multiple of Hkv (GQA, repeat-kv head order). Positions with scale 0
    are masked (ragged per-slot lengths / ring holes / padding) — zero the
    scales of invalid positions instead of dequantizing-and-masking.
    ``v_err`` ([B, S, Hkv] per-token V dequant-error means) enables the
    optional bias correction — XLA path only: with ``backend=None`` it
    selects "xla", an explicit "pallas"/"interpret" raises (no silent
    hot-path fallback). ``blk`` is the kernel's block of cache positions;
    None sizes it by bytes (``ref.block_rows``).
    """
    if v_err is not None:
        if backend not in (None, "xla"):
            raise ValueError(
                f"kv_attention: V bias correction (v_err) is implemented on "
                f"the XLA path only, got backend={backend!r}; pass "
                f"backend='xla' or drop v_err"
            )
        return kv_attention_xla(q, k_q, k_s, v_q, v_s, out_dtype, v_err=v_err)
    impl = resolve("kv_attention", backend)
    return impl(q, k_q, k_s, v_q, v_s, blk=blk, out_dtype=out_dtype)


def append_quantize(cache_k, cache_ks, cache_v, cache_vs, k_new, v_new, idx,
                    *, cache_verr=None):
    """Quantize a new token's K/V once and scatter it into the int8 cache.

    k_new/v_new [B, T, Hkv, hd] fp, written as lane-dense [B, T, Hkv·hd]
    rows; idx [T] ring offsets (scalar-pos cache) or [B, T] per-slot
    offsets. Returns the updated cache leaves (+ the per-token V
    dequant-error means when ``cache_verr`` is given).
    """
    k_q, k_s = quantize_kv(k_new)
    v_q, v_s = quantize_kv(v_new)
    if idx.ndim == 2:                                  # per-slot [B, T]
        row = jnp.arange(k_new.shape[0])[:, None]
        at = lambda c, u: c.at[row, idx].set(u)
    else:                                              # shared ring offsets
        at = lambda c, u: c.at[:, idx].set(u)
    out = (at(cache_k, flat_heads(k_q)), at(cache_ks, k_s),
           at(cache_v, flat_heads(v_q)), at(cache_vs, v_s))
    if cache_verr is not None:
        err = jnp.mean(v_q.astype(jnp.float32) * v_s[..., None]
                       - v_new.astype(jnp.float32), axis=-1)
        out = out + (at(cache_verr, err),)
    return out


def kv_attention_decode(q, cache_k, cache_ks, cache_v, cache_vs, k_new, v_new,
                        idx, *, valid=None, out_dtype=jnp.float32,
                        backend: Optional[str] = None,
                        blk: Optional[int] = None,
                        cache_verr=None):
    """Fused decode step: append-quantize the new token, then attend.

    q [B, Hq, hd] (the new token's roped query); cache_k/cache_v
    [B, S, Hkv·hd] int8; k_new/v_new [B, 1, Hkv, hd];
    ``valid`` [B, S] marks live cache positions (None = all live). Returns
    (attn_out [B, Hq, hd], updated cache leaves) — the int8 cache is written
    once per token and never re-quantized.
    """
    updated = append_quantize(cache_k, cache_ks, cache_v, cache_vs,
                              k_new, v_new, idx, cache_verr=cache_verr)
    ck, ks, cv, vs = updated[:4]
    verr = updated[4] if cache_verr is not None else None
    ks_eff, vs_eff = ks, vs
    verr_eff = verr
    if valid is not None:
        ks_eff = jnp.where(valid[..., None], ks, 0.0)
        vs_eff = jnp.where(valid[..., None], vs, 0.0)
        if verr is not None:
            verr_eff = jnp.where(valid[..., None], verr, 0.0)
    out = kv_attention(q, ck, ks_eff, cv, vs_eff, blk=blk,
                       out_dtype=out_dtype, backend=backend, v_err=verr_eff)
    return out, updated


@register_spec("kv_attention_decode")
def _spec(*, head_dim: int = 16, n_kv_heads: int = 2, n_q_heads: int = 4,
          seq: int = 32, batch: int = 2, **_):
    B, S, Hq, Hkv, hd = batch, seq, n_q_heads, n_kv_heads, head_dim
    return (kv_attention_decode,
            (jnp.zeros((B, Hq, hd), jnp.float32),        # q
             jnp.zeros((B, S, Hkv * hd), jnp.int8),      # cache_k
             jnp.ones((B, S, Hkv), jnp.float32),         # cache_ks
             jnp.zeros((B, S, Hkv * hd), jnp.int8),      # cache_v
             jnp.ones((B, S, Hkv), jnp.float32),         # cache_vs
             jnp.zeros((B, 1, Hkv, hd), jnp.float32),    # k_new
             jnp.zeros((B, 1, Hkv, hd), jnp.float32),    # v_new
             jnp.zeros((B, 1), jnp.int32)),              # idx
            {"valid": jnp.ones((B, S), bool)})
