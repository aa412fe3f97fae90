"""Reference implementations for int8-KV decode attention.

Two oracles with different jobs:

  * ``kv_attention_ref`` — mirrors the Pallas kernel **block for block**
    (same block order, the kernel's own per-head ``attend_block``, same
    zero-scale masking), so the interpret-mode kernel must match it
    *bit-exactly*: any divergence is a BlockSpec/grid/scratch bug, not
    numerics. The property tests pin this
    over ragged lengths, GQA ratios, and non-multiple-of-blk S.
  * ``kv_attention_xla`` — the production XLA backend for non-TPU serving:
    plain masked softmax with the per-token/per-head scales folded in at
    score granularity (``[B, S, Hkv]``), so neither a dequantized
    ``[B, S, H, hd]`` cache nor repeated GQA K/V is ever materialized.

Both treat scale == 0 as "position invalid" (ragged per-slot lengths,
padding); see kernel.py for why 0 is unambiguous.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30


def pad_to_block(k_q, k_s, v_q, v_s, blk: int):
    """Pad S up to a multiple of ``min(blk, S)`` with zero-scale (= masked)
    positions. One helper shared by the op and the ref — the bit-exact
    interpret==ref contract requires both to pad identically."""
    S = k_q.shape[1]
    blk_e = min(blk, S)
    pad = (-S) % blk_e
    if pad:
        k_q = jnp.pad(k_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_q = jnp.pad(v_q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_s = jnp.pad(k_s, ((0, 0), (0, pad), (0, 0)))
        v_s = jnp.pad(v_s, ((0, 0), (0, pad), (0, 0)))
    return k_q, k_s, v_q, v_s, blk_e


def kv_attention_ref(
    q: jnp.ndarray,        # [B, Hq, hd]
    k_q: jnp.ndarray,      # [B, S, Hkv, hd] int8
    k_s: jnp.ndarray,      # [B, S, Hkv] fp32 per-token, per-head scales
    v_q: jnp.ndarray,      # [B, S, Hkv, hd] int8
    v_s: jnp.ndarray,      # [B, S, Hkv]
    out_dtype=jnp.float32,
    *,
    blk: int = 512,
) -> jnp.ndarray:
    """Blocked online-softmax oracle — the kernel's math in pure jnp: the
    kernel's own ``attend_block`` per (batch row, kv head), scanned over the
    blocks in grid order, on the same operand shapes the kernel sees."""
    from .kernel import attend_block, finish, head_major, lane_scales

    B, S, Hkv, hd = k_q.shape
    Hq = q.shape[1]
    group = Hq // Hkv
    k_q, k_s, v_q, v_s, blk_e = pad_to_block(k_q, k_s, v_q, v_s, blk)
    n_blk = k_q.shape[1] // blk_e
    scale = 1.0 / (hd ** 0.5)

    def payload(a):     # [B, S, Hkv, hd] → [B·Hkv, n_blk, blk, hd]
        return a.transpose(0, 2, 1, 3).reshape(B * Hkv, n_blk, blk_e, hd)

    def scales(a):      # [B, S, Hkv] → [B·Hkv, n_blk, 1, blk]
        return lane_scales(a).reshape(B * Hkv, n_blk, 1, blk_e)

    def head(args):
        q1, kq1, ks1, vq1, vs1 = args

        def body(carry, blocks):
            return attend_block(q1, *blocks, *carry, scale=scale), None

        gp = q1.shape[0]
        init = (jnp.full((gp, 1), _NEG, jnp.float32),
                jnp.zeros((gp, 1), jnp.float32),
                jnp.zeros((gp, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init, (kq1, ks1, vq1, vs1))
        return finish(acc, l)

    qh = head_major(q.astype(jnp.float32), Hkv)
    out = jax.lax.map(head, (qh.reshape(B * Hkv, -1, hd), payload(k_q),
                             scales(k_s), payload(v_q), scales(v_s)))
    out = out.reshape(B, Hkv, -1, hd)[:, :, :group]
    return out.reshape(B, Hq, hd).astype(out_dtype)


def kv_attention_xla(
    q: jnp.ndarray,        # [B, Hq, hd]
    k_q: jnp.ndarray,      # [B, S, Hkv, hd] int8
    k_s: jnp.ndarray,      # [B, S, Hkv]
    v_q: jnp.ndarray,      # [B, S, Hkv, hd] int8
    v_s: jnp.ndarray,      # [B, S, Hkv]
    out_dtype=jnp.float32,
    v_err: jnp.ndarray = None,   # [B, S, Hkv] optional V dequant-error means
) -> jnp.ndarray:
    """Serving XLA path: scales (and the optional per-token V bias
    correction, paper §4.2 applied to the V dequant error) fold in at
    ``[B, S, Hkv]`` score/probability granularity — the per-token-per-head
    scale factors out of the head_dim dot product, so the int8 payload feeds
    the einsum directly."""
    B, S, Hkv, hd = k_q.shape
    Hq = q.shape[1]
    group = Hq // Hkv
    scale = 1.0 / (hd ** 0.5)
    qg = q.astype(jnp.float32).reshape(B, Hkv, group, hd)
    ks_t = k_s.astype(jnp.float32).transpose(0, 2, 1)       # [B, Hkv, S]
    s = jnp.einsum("bngd,bsnd->bngs", qg, k_q.astype(jnp.float32))
    s = s * (ks_t * scale)[:, :, None, :]
    s = jnp.where((ks_t > 0)[:, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)                          # [B, Hkv, G, S]
    vs_t = v_s.astype(jnp.float32).transpose(0, 2, 1)
    out = jnp.einsum("bngs,bsnd->bngd", p * vs_t[:, :, None, :],
                     v_q.astype(jnp.float32))
    if v_err is not None:
        # out_d -= sum_s p_s * E_d[dequant(v_s) - v_s]: removes the mean
        # (per-token, per-head) component of the V quantization error
        e = jnp.einsum("bngs,bsn->bng", p, v_err.astype(jnp.float32))
        out = out - e[..., None]
    return out.reshape(B, Hq, hd).astype(out_dtype)
