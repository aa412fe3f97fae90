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

Both take the lane-dense int8 payload ``[B, S, Hkv·hd]`` the kernel reads
(kv head ``h`` owns lanes ``[h·hd, (h+1)·hd)``) and treat scale == 0 as
"position invalid" (ragged per-slot lengths, padding); see kernel.py for
why 0 is unambiguous.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG = -1e30


# payload bytes of one K (or V) cache block the kernels stream: a grid step
# costs a fixed overhead besides its bytes, so blocks are as large as this
# allows — a whole ring of up to this many bytes is one block
BLOCK_BYTES = 512 * 1024


def block_rows(S: int, width: int) -> int:
    """Cache positions per block for a ring of S lane-dense rows of
    ``width`` int8: all of S when it fits ``BLOCK_BYTES``, else the fewest
    blocks that do, each a multiple of 128 rows (the scales' lane tile)."""
    n_blk = -(-S * width // BLOCK_BYTES)
    if n_blk <= 1:
        return S
    rows = -(-S // n_blk)
    return -(-rows // 128) * 128


def pad_to_block(k_q, k_s, v_q, v_s, blk: Optional[int]):
    """Pad S (axis 1) up to a multiple of ``min(blk, S)`` with zero-scale
    (= masked) positions; ``blk=None`` sizes the block by bytes
    (``block_rows``). One helper shared by the op and the ref — the
    bit-exact interpret==ref contract requires both to pad identically."""
    S = k_q.shape[1]
    if blk is None:
        blk = block_rows(S, k_q.shape[-1])
    blk_e = min(blk, S)
    pad = (-S) % blk_e
    if pad:
        k_q, k_s, v_q, v_s = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (k_q, k_s, v_q, v_s))
    return k_q, k_s, v_q, v_s, blk_e


def split_heads(a, n_kv: int):
    """Lane-dense ``[B, S, Hkv·hd]`` payload → ``[B, S, Hkv, hd]``."""
    return a.reshape(a.shape[:2] + (n_kv, -1))


def flat_heads(a):
    """Per-head ``[B, S, Hkv, hd]`` → the lane-dense ``[B, S, Hkv·hd]``
    payload (the inverse of ``split_heads``)."""
    return a.reshape(a.shape[:2] + (-1,))


def kv_attention_ref(
    q: jnp.ndarray,        # [B, Hq, hd]
    k_q: jnp.ndarray,      # [B, S, Hkv·hd] int8
    k_s: jnp.ndarray,      # [B, S, Hkv] fp32 per-token, per-head scales
    v_q: jnp.ndarray,      # [B, S, Hkv·hd] int8
    v_s: jnp.ndarray,      # [B, S, Hkv]
    out_dtype=jnp.float32,
    *,
    blk: Optional[int] = None,
) -> jnp.ndarray:
    """Blocked online-softmax oracle — the kernel's math in pure jnp: the
    kernel's own ``attend_block`` per (batch row, kv head), scanned over the
    blocks in grid order, on the same operand shapes the kernel sees."""
    from .kernel import attend_block, finish, head_major, lane_scales

    B, Hq, hd = q.shape
    Hkv = k_s.shape[-1]
    group = Hq // Hkv
    k_q, k_s, v_q, v_s, blk_e = pad_to_block(k_q, k_s, v_q, v_s, blk)
    n_blk = k_q.shape[1] // blk_e
    scale = 1.0 / (hd ** 0.5)

    def payload(a):     # [B, S, Hkv·hd] → [B·Hkv, n_blk, blk, hd]
        return split_heads(a, Hkv).transpose(0, 2, 1, 3).reshape(
            B * Hkv, n_blk, blk_e, hd)

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

    qh = head_major(q, Hkv)
    out = jax.lax.map(head, (qh.reshape(B * Hkv, -1, hd), payload(k_q),
                             scales(k_s), payload(v_q), scales(v_s)))
    out = out.reshape(B, Hkv, -1, hd)[:, :, :group]
    return out.reshape(B, Hq, hd).astype(out_dtype)


def kv_attention_xla(
    q: jnp.ndarray,        # [B, Hq, hd]
    k_q: jnp.ndarray,      # [B, S, Hkv·hd] int8
    k_s: jnp.ndarray,      # [B, S, Hkv]
    v_q: jnp.ndarray,      # [B, S, Hkv·hd] int8
    v_s: jnp.ndarray,      # [B, S, Hkv]
    out_dtype=jnp.float32,
    v_err: jnp.ndarray = None,   # [B, S, Hkv] optional V dequant-error means
) -> jnp.ndarray:
    """Serving XLA path: scales (and the optional per-token V bias
    correction, paper §4.2 applied to the V dequant error) fold in at
    ``[B, S, Hkv]`` score/probability granularity — the per-token-per-head
    scale factors out of the head_dim dot product, so the int8 payload feeds
    the einsum directly."""
    B, Hq, hd = q.shape
    Hkv = k_s.shape[-1]
    group = Hq // Hkv
    scale = 1.0 / (hd ** 0.5)
    k_q, v_q = split_heads(k_q, Hkv), split_heads(v_q, Hkv)
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
