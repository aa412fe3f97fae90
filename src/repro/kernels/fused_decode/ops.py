"""Public fused decode op: one dispatch from roped q/k/v to attention out.

``fused_decode`` is the megakernel face of the decode hot path:

  * **pallas / interpret** — the true fusion (``kernel.py``): in-VMEM
    append-quantize + int8 online-softmax attention (+ optional W8A8
    quantize-out epilogue), cache leaves aliased in place.
  * **xla** — the exact stepwise composition the serving engine shipped
    before this op existed (``kv_attention_decode`` on its XLA tier +
    ``quantize_act``), so CPU serving graphs — and the lint contracts
    pinning them — are unchanged by construction.
  * **ref** — the composition over the blocked oracles (``ref.py``), the
    bit-parity anchor for the interpret-mode kernel.

The V bias correction (``cache_verr``) is XLA-composition-only, mirroring
``kv_attention``: with ``backend=None`` it routes to "xla", an explicit
"pallas"/"interpret" raises.

``REPRO_FUSED_DECODE=0`` turns the op's model-layer routing off (the layers
fall back to the stepwise ops) — the switch the fused-vs-unfused parity
tests and benchmark delta ride on.
"""
from __future__ import annotations

import os
from typing import Optional

import jax.numpy as jnp

from ..dispatch import register_impl, register_spec, resolve
from ..kv_attention.ops import kv_attention_decode
from ..quantize_act.ops import quantize_act
from .kernel import fused_decode_pallas
from .ref import fused_decode_ref


def fusion_enabled() -> bool:
    """The ``REPRO_FUSED_DECODE`` routing flag (default: on)."""
    return os.environ.get("REPRO_FUSED_DECODE", "1").lower() not in (
        "0", "false", "off")


def _compose(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid, out_dtype,
             blk, quantize_out, backend, cache_verr=None):
    """The stepwise composition at one backend tier."""
    out, updated = kv_attention_decode(
        q, ck, cks, cv, cvs, k_new, v_new, idx, valid=valid,
        out_dtype=out_dtype, backend=backend, blk=blk,
        cache_verr=cache_verr)
    if quantize_out:
        B = out.shape[0]
        oq, os_ = quantize_act(out.astype(jnp.float32).reshape(B, -1),
                               backend=backend)
        return (out, oq, os_), updated
    return out, updated


def _pallas_impl(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid, out_dtype,
                 blk, quantize_out, interpret):
    from ..kv_attention.ref import pad_to_block

    B, S = ck.shape[:2]
    Hkv = cks.shape[-1]
    hd = q.shape[-1]
    # normalize the stepwise op's idx/valid conventions to kernel shapes
    idx_b = idx[:, 0] if idx.ndim == 2 else jnp.broadcast_to(
        idx.reshape(-1)[:1], (B,))
    if valid is None:
        vmask = jnp.ones((B, S), jnp.float32)
    else:
        vmask = jnp.broadcast_to(valid, (B, S)).astype(jnp.float32)
    ck_p, cks_p, cv_p, cvs_p, blk_e = pad_to_block(ck, cks, cv, cvs, blk)
    S_p = ck_p.shape[1]
    if S_p != S:
        vmask = jnp.pad(vmask, ((0, 0), (0, S_p - S)))
    res = fused_decode_pallas(
        q, ck_p, cks_p, cv_p, cvs_p,
        k_new.reshape(B, Hkv, hd), v_new.reshape(B, Hkv, hd),
        idx_b, vmask, blk=blk_e, out_dtype=out_dtype,
        quantize_out=quantize_out, interpret=interpret)
    out, kq_u, ks_u, vq_u, vs_u = res[:5]
    updated = (kq_u[:, :S], ks_u[:, :S], vq_u[:, :S], vs_u[:, :S])
    if quantize_out:
        return (out, res[5], res[6]), updated
    return out, updated


@register_impl("fused_decode", "pallas", pad="zero-scale")
def _fd_pallas(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid, out_dtype,
               blk, quantize_out):
    return _pallas_impl(q, ck, cks, cv, cvs, k_new, v_new, idx, valid=valid,
                        out_dtype=out_dtype, blk=blk,
                        quantize_out=quantize_out, interpret=False)


@register_impl("fused_decode", "interpret", pad="zero-scale")
def _fd_interpret(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid,
                  out_dtype, blk, quantize_out):
    return _pallas_impl(q, ck, cks, cv, cvs, k_new, v_new, idx, valid=valid,
                        out_dtype=out_dtype, blk=blk,
                        quantize_out=quantize_out, interpret=True)


@register_impl("fused_decode", "xla", pad="zero-scale")
def _fd_xla(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid, out_dtype,
            blk, quantize_out):
    return _compose(q, ck, cks, cv, cvs, k_new, v_new, idx, valid=valid,
                    out_dtype=out_dtype, blk=blk, quantize_out=quantize_out,
                    backend="xla")


@register_impl("fused_decode", "ref", pad="zero-scale")
def _fd_ref(q, ck, cks, cv, cvs, k_new, v_new, idx, *, valid, out_dtype,
            blk, quantize_out):
    return fused_decode_ref(q, ck, cks, cv, cvs, k_new, v_new, idx,
                            valid=valid, out_dtype=out_dtype, blk=blk,
                            quantize_out=quantize_out)


def fused_decode(q, cache_k, cache_ks, cache_v, cache_vs, k_new, v_new, idx,
                 *, valid=None, out_dtype=jnp.float32,
                 backend: Optional[str] = None,
                 blk: Optional[int] = None,
                 cache_verr=None, quantize_out: bool = False):
    """Fused decode step: append-quantize the new token, attend, and
    (optionally) re-quantize the output row for the W8A8 wo projection.

    q [B, Hq, hd]; cache leaves as in ``kv_attention_decode`` (lane-dense
    int8 payload [B, S, Hkv·hd], scales [B, S, Hkv]); k_new/v_new
    [B, 1, Hkv, hd]; idx [B, 1] per-slot ring offsets (or [1] shared);
    ``valid`` [B|1, S] marks live cache positions (must include the new
    token's). Returns ``(out, updated_leaves)``, where ``out`` becomes the
    triple ``(out, out_q [B, Hq·hd] int8, out_scale [B])`` under
    ``quantize_out=True``.
    """
    if cache_verr is not None:
        if backend not in (None, "xla"):
            raise ValueError(
                f"fused_decode: V bias correction (cache_verr) lives on the "
                f"XLA composition only, got backend={backend!r}; pass "
                f"backend='xla' or drop cache_verr")
        return _compose(q, cache_k, cache_ks, cache_v, cache_vs, k_new,
                        v_new, idx, valid=valid, out_dtype=out_dtype,
                        blk=blk, quantize_out=quantize_out, backend="xla",
                        cache_verr=cache_verr)
    impl = resolve("fused_decode", backend)
    return impl(q, cache_k, cache_ks, cache_v, cache_vs, k_new, v_new, idx,
                valid=valid, out_dtype=out_dtype, blk=blk,
                quantize_out=quantize_out)


@register_spec("fused_decode")
def _spec(*, head_dim: int = 16, n_kv_heads: int = 2, n_q_heads: int = 4,
          seq: int = 32, batch: int = 2, **_):
    B, S, Hq, Hkv, hd = batch, seq, n_q_heads, n_kv_heads, head_dim
    return (fused_decode,
            (jnp.zeros((B, Hq, hd), jnp.float32),        # q
             jnp.zeros((B, S, Hkv * hd), jnp.int8),      # cache_k
             jnp.ones((B, S, Hkv), jnp.float32),         # cache_ks
             jnp.zeros((B, S, Hkv * hd), jnp.int8),      # cache_v
             jnp.ones((B, S, Hkv), jnp.float32),         # cache_vs
             jnp.zeros((B, 1, Hkv, hd), jnp.float32),    # k_new
             jnp.zeros((B, 1, Hkv, hd), jnp.float32),    # v_new
             jnp.zeros((B, 1), jnp.int32)),              # idx
            {"valid": jnp.ones((B, S), bool),
             "quantize_out": True})
