"""Transformer building blocks — pure functions over explicit param pytrees.

Conventions:
  * linear weights are ``[d_in, d_out]`` applied as ``y = x @ w + b``,
  * attention projections are flat ``[D, n_heads*head_dim]`` (head-major),
  * every linear has an (often zero-initialized) bias slot — DFQ's bias
    correction folds ε·E[x] into it (paper §4.2),
  * blocks broadcast over a leading scan dim when params are stacked.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig


def scan_layers(body, carry, xs, unroll: bool = False):
    """lax.scan over stacked layer params, or an unrolled python loop when
    ``unroll`` (dry-run cost probes: XLA's cost_analysis counts while-loop
    bodies once, so probes unroll shallow variants and extrapolate)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys_stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys_stacked = ys[0] if ys else None
    return carry, ys_stacked


def linear(x, w, b=None, tp=None):
    """y = x @ w + b. Dispatches on weight type: an int8 ``QTensor`` routes
    through the Pallas W8A16/W8A8 kernels (repro.quantized) — the same model
    code serves fp and quantized. ``tp`` ("col" | "row") names the weight's
    tensor-parallel role, which the kernels need under a serve mesh."""
    if type(w).__name__ == "QTensor":
        from ..quantized.qtensor import qtensor_matmul

        return qtensor_matmul(x, w, b, tp=tp)
    y = x @ w
    if b is not None:
        y = y + b
    return y


def _all_w8a8(*ws) -> bool:
    return all(type(w).__name__ == "QTensor" and w.mode == "w8a8"
               for w in ws)


def _shared_linears(x, wbs, tps):
    """Several W8A8 projections reading the SAME activation (the qkv trio,
    the GLU gate/up pair) share one ``quantize_act`` dispatch. Per-row
    dynamic quantization depends only on the row, so each output is bitwise
    what its own ``linear``/``qtensor_matmul`` would have produced."""
    from ..quantized.qtensor import qtensor_matmul_prequant, quantize_input

    a_q, a_s, lead = quantize_input(x)
    return [qtensor_matmul_prequant(a_q, a_s, w, b, lead, out_dtype=x.dtype,
                                    tp=tp)
            for (w, b), tp in zip(wbs, tps)]


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6):
    # statistics in f32, data path in the compute dtype: keeping x itself
    # bf16 keeps its COTANGENT bf16, which halves every boundary psum the
    # backward pass emits (measured on mixtral train_4k — EXPERIMENTS §Perf)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * weight.astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x - mu.astype(x.dtype)) * inv * weight.astype(x.dtype) + bias.astype(x.dtype)


def apply_norm(x, p, kind: str):
    if kind == "rms":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


# --------------------------------------------------------------------------
# RoPE (rotate-half convention — matches core.cle.equalize_qk's pair layout)
# --------------------------------------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., T, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: [B, T, H, hd]; cos/sin: [T, hd/2] or [B, T, hd/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(
        x.dtype
    )


# --------------------------------------------------------------------------
# Sharding hints (set by launch.steps when tracing under a mesh): inside
# attention we steer GSPMD to either head-parallel (Megatron) or, when the
# head count doesn't divide the model axis, SEQUENCE-parallel (context
# parallelism) — measured on qwen2 train_4k: 16× less redundant compute than
# replication and ~50× fewer collective bytes than GSPMD's factored fallback.
# --------------------------------------------------------------------------

_SHARD_CTX = {"enabled": False, "dp": ("data",), "model": "model",
              "attn_seq": False, "kv_heads_ok": False}


def set_shard_ctx(*, enabled: bool, dp=("data",), model="model",
                  attn_seq=False, kv_heads_ok=False, mesh=None):
    _SHARD_CTX.update(enabled=enabled, dp=tuple(dp), model=model,
                      attn_seq=attn_seq, kv_heads_ok=kv_heads_ok, mesh=mesh)


def _wsc(x, *spec):
    if not _SHARD_CTX["enabled"]:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(*spec))


# Serving mesh context — SEPARATE from _SHARD_CTX (which arms the training
# constraints and the MoE shard_map). The serving engine sets this while
# tracing its jitted impls; the decode hot path then hand-partitions the
# fused attention kernel with shard_map over ("data", "model") — heads are
# model-local and slots data-local, so the kernel body runs with ZERO
# collectives and per-shard results concatenate bitwise.

_SERVE_MESH = {"mesh": None, "dp": ("data",), "model": "model"}


def set_serve_mesh(mesh=None, *, dp=("data",), model="model") -> dict:
    """Arm (or clear, mesh=None) the serve-mesh context. Returns the
    previous context so engine wrappers can restore it after tracing."""
    prev = dict(_SERVE_MESH)
    _SERVE_MESH.update(mesh=mesh, dp=tuple(dp), model=model)
    return prev


def serve_mesh_axes(rows: int):
    """(mesh, row_spec, model_axis, model_n) under an armed serve mesh, else
    None. ``row_spec`` shards a leading batch/slot axis of ``rows`` over the
    data axes, degrading to replication when they don't divide it."""
    mesh = _SERVE_MESH["mesh"]
    if mesh is None:
        return None
    sizes = dict(mesh.shape)
    mdl = _SERVE_MESH["model"]
    dp = tuple(a for a in _SERVE_MESH["dp"] if a in sizes)
    dp_n = 1
    for a in dp:
        dp_n *= sizes[a]
    row_spec = dp if (dp_n > 1 and rows % dp_n == 0) else None
    return mesh, row_spec, mdl, sizes.get(mdl, 1)


def _head_tp(n_heads: int, role: str):
    """A projection's TP role when the serve mesh's model axis splits its
    heads whole (the serve placement rule of ``sharding.partition``), else
    None (replicated)."""
    ax = serve_mesh_axes(1)
    return role if ax is None or n_heads % ax[3] == 0 else None


def _serve_decode_partition(nq: int, nkv: int, B: int, kernel: bool):
    """(mesh, dp_spec, model_axis) when the decode attention runs under
    shard_map — head-locally when the model axis divides BOTH head counts
    (a shard owns whole GQA groups). Otherwise the XLA tier leaves it to
    GSPMD (None), while a Pallas tier (``kernel``), which GSPMD cannot
    partition, still shard_maps over the slots with heads replicated
    (model_axis None). ``dp_spec`` degrades to replication when the slot
    count doesn't divide the data axis."""
    ax = serve_mesh_axes(B)
    if ax is None:
        return None
    mesh, dp_spec, mdl, m_n = ax
    if m_n > 1 and nq % m_n == 0 and nkv % m_n == 0:
        return mesh, dp_spec, mdl
    return (mesh, dp_spec, None) if kernel else None


def _fused_decode_tp(part, q1, cache, k_new, v_new, idx, valid, out_dtype,
                     backend, op):
    """shard_map the decode attention ``op`` (``fused_decode``, or the
    stepwise ``kv_attention_decode`` with fusion off) over (data, model): q
    heads and the KV cache's head axis live on "model", slots on "data".
    The int8 payload's lane-dense last axis (Hkv·hd) splits into whole
    heads, since the model axis divides Hkv here. Attention is head-local,
    so the body emits no collectives — the -tp
    serving contracts pin the decode collective budget at the same level as
    single-device."""
    from jax.sharding import PartitionSpec as P

    mesh, dp, mdl = part
    per_slot = idx.ndim == 2

    def local_fn(q1, ck, cks, cv, cvs, kn, vn, idx, valid):
        return op(q1, ck, cks, cv, cvs, kn, vn, idx, valid=valid,
                  out_dtype=out_dtype, backend=backend)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(dp, mdl, None),                    # q [B, Hq, hd]
            P(dp, None, mdl),                    # cache k [B, S, Hkv·hd]
            P(dp, None, mdl),                    # k_scale [B, S, Hkv]
            P(dp, None, mdl),                    # cache v
            P(dp, None, mdl),                    # v_scale
            P(dp, None, mdl, None),              # k_new [B, 1, Hkv, hd]
            P(dp, None, mdl, None),              # v_new
            P(dp, None) if per_slot else P(None),        # idx [B, 1] | [1]
            P(dp, None) if per_slot else P(None, None),  # valid [B|1, S]
        ),
        out_specs=(P(dp, mdl, None),
                   (P(dp, None, mdl),) * 4),
        check_vma=False,
    )
    return fn(q1, cache["k"], cache["k_scale"], cache["v"], cache["v_scale"],
              k_new, v_new, idx, valid)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

NEG_INF = -1e30


def _repeat_kv(x, group: int):
    if group == 1:
        return x
    return jnp.repeat(x, group, axis=2)


def attention_scores_softmax(
    q, k, v, mask, chunk_kv: Optional[int] = None, chunk_q: Optional[int] = None,
    unroll: bool = False, causal_segments: int = 1,
):
    """softmax(q·kᵀ)·v with optional two-level online-softmax chunking.

    q: [B, Tq, H, hd]; k, v: [B, Tk, H, hd]; mask is 2-D [Tq, Tk]
    (True = attend) or None. Chunking bounds the live score buffer to
    [B, H, chunk_q, chunk_kv] — the flash-attention dataflow expressed in
    pure JAX (XLA-fused on TPU) so 32k-token training fits HBM.

    ``causal_segments > 1`` splits the query range into static segments and
    bounds each segment's KV scan at its causal frontier — block skipping
    for the lower-triangular mask (8 segments ≈ 44 % of the quadratic
    FLOPs eliminated; EXPERIMENTS §Perf, yi-34b prefill iteration).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    B, Tk, H, hd = k.shape
    Tq = q.shape[1]

    if chunk_kv is None or Tk <= chunk_kv:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = s.astype(jnp.float32)
        if mask is not None:
            # 2-D [Tq, Tk] shared mask, or 3-D [B, Tq, Tk] per-slot mask
            # (continuous batching: each batch row is an independent request)
            m = mask[None, None] if mask.ndim == 2 else mask[:, None]
            s = jnp.where(m, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    if mask is not None and mask.ndim == 3:
        raise NotImplementedError(
            "per-slot (3-D) masks require the unchunked attention path — "
            "call without chunk_kv (serving decode/prefill-chunk shapes are "
            "small enough that chunking buys nothing)"
        )

    n_kv = Tk // chunk_kv
    k_b = k.reshape(B, n_kv, chunk_kv, H, hd).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(B, n_kv, chunk_kv, H, hd).transpose(1, 0, 2, 3, 4)

    # probe mode (unroll): match chunk_q to chunk_kv so the unrolled block
    # count stays tiny; production scans use finer q chunks for VMEM
    chunk_q = chunk_q or (min(Tq, chunk_kv) if unroll
                          else min(Tq, max(chunk_kv // 4, 256)))
    if Tq % chunk_q != 0:
        chunk_q = Tq
    n_q = Tq // chunk_q
    q_b = q.reshape(B, n_q, chunk_q, H, hd).transpose(1, 0, 2, 3, 4)
    mask_b = None
    if mask is not None:
        # [n_q, chunk_q, n_kv, chunk_kv] — tiny (no B/H dims)
        mask_b = mask.reshape(n_q, chunk_q, n_kv, chunk_kv)

    def run_block(q_part, mask_part, k_part, v_part):
        """Online-softmax over the given KV blocks for the given q chunks."""

        def q_body(_, q_blk_and_mask):
            if mask_part is not None:
                qb, mb_all = q_blk_and_mask
            else:
                qb = q_blk_and_mask
                mb_all = None

            m0 = jnp.full((B, H, chunk_q), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, H, chunk_q), jnp.float32)
            acc0 = jnp.zeros((B, chunk_q, H, hd), jnp.float32)

            @jax.checkpoint
            def kv_body(carry, blk):
                # remat: the bwd recomputes s/p per block instead of saving
                # the [B, H, cq, ckv] probabilities for every iteration
                m, l, acc = carry
                if mb_all is not None:
                    kb, vb, mb = blk
                else:
                    kb, vb = blk
                    mb = None
                s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
                if mb is not None:
                    s = jnp.where(mb[None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, -1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, -1)
                acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
                    "bhqk,bkhd->bqhd", p.astype(qb.dtype), vb
                ).astype(jnp.float32)
                return (m_new, l_new, acc_new), None

            xs = ((k_part, v_part, mb_all.transpose(1, 0, 2))
                  if mb_all is not None else (k_part, v_part))
            (m, l, acc), _ = scan_layers(kv_body, (m0, l0, acc0), xs, unroll)
            out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
            return None, out.astype(q.dtype)

        xs_q = (q_part, mask_part) if mask_part is not None else q_part
        _, out_b = scan_layers(q_body, None, xs_q, unroll)
        return out_b

    nseg = causal_segments
    if nseg > 1 and mask is not None and n_q % nseg == 0 and Tq == Tk:
        seg_q = n_q // nseg
        outs = []
        for si in range(nseg):
            q_hi = (si + 1) * seg_q * chunk_q
            n_kv_s = -(-q_hi // chunk_kv)                  # ceil
            outs.append(run_block(
                q_b[si * seg_q:(si + 1) * seg_q],
                mask_b[si * seg_q:(si + 1) * seg_q, :, :n_kv_s],
                k_b[:n_kv_s], v_b[:n_kv_s],
            ))
        out_b = jnp.concatenate(outs, axis=0)
    else:
        out_b = run_block(q_b, mask_b, k_b, v_b)
    return out_b.transpose(1, 0, 2, 3, 4).reshape(B, Tq, H, hd)


def causal_mask(Tq: int, Tk: int, q_offset, window: Optional[int] = None):
    """[Tq, Tk] boolean; query i (absolute pos q_offset+i) sees key j ≤ i,
    within the sliding window if given."""
    qpos = jnp.arange(Tq) + q_offset
    kpos = jnp.arange(Tk)
    m = kpos[None, :] <= qpos[:, None]
    if window is not None:
        m = m & (kpos[None, :] > qpos[:, None] - window)
    return m


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_q: int
    n_kv: int
    head_dim: int
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    window: Optional[int] = None
    causal_segments: int = 1


def attention_block(
    p: dict,
    x: jnp.ndarray,
    dims: AttnDims,
    *,
    positions: jnp.ndarray,
    mask,
    cache: Optional[dict] = None,
    kv_input: Optional[jnp.ndarray] = None,   # cross-attention source
    chunk_kv: Optional[int] = None,
    capture: bool = False,
    unroll: bool = False,
):
    """Full attention sub-block: qkv proj → rope → (cached) attention → out.

    cache (decode): {"k": [B, S, n_kv, hd], "v": ..., "pos": int32 scalar}
    written as a ring buffer of length S (S = min(seq, window) for SWA); an
    int8 cache holds lane-dense "k"/"v" [B, S, n_kv·hd] beside its
    "k_scale"/"v_scale" [B, S, n_kv].
    Returns (out, new_cache, stats).
    """
    B, T, D = x.shape
    nq, nkv, hd = dims.n_q, dims.n_kv, dims.head_dim
    stats = {}
    if capture:
        stats["attn_in"] = jnp.mean(x.reshape(-1, D), 0)

    src = x if kv_input is None else kv_input
    tp_q, tp_kv = _head_tp(nq, "col"), _head_tp(nkv, "col")
    if kv_input is None and _all_w8a8(p["wq"], p["wk"], p["wv"]):
        q, k, v = _shared_linears(
            x, [(p["wq"], p.get("bq")), (p["wk"], p.get("bk")),
                (p["wv"], p.get("bv"))], (tp_q, tp_kv, tp_kv))
    else:
        q = linear(x, p["wq"], p.get("bq"), tp=tp_q)
        k = linear(src, p["wk"], p.get("bk"), tp=tp_kv)
        v = linear(src, p["wv"], p.get("bv"), tp=tp_kv)
    Tk_in = src.shape[1]
    q = q.reshape(B, T, nq, hd)
    k = k.reshape(B, Tk_in, nkv, hd)
    v = v.reshape(B, Tk_in, nkv, hd)

    if dims.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    if dims.rope:
        cos_q, sin_q = rope_angles(positions, hd, dims.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        if kv_input is None:
            k = apply_rope(k, cos_q, sin_q)

    new_cache = None
    attn_fused = None        # set by the int8 decode fast path (kv_attention)
    attn_q8 = None           # (int8, scale) from the fused quantize-out epilogue
    if cache is not None and kv_input is None:
        # Ring-buffer KV cache with explicit absolute slot positions: length
        # S = min(context, window) for SWA. ``kpos`` holds each slot's
        # absolute token position (-1 = never written). With "k_scale" in the
        # cache the payload is INT8 (per-token, per-head absmax scales) —
        # DFQ's deployment story applied to the decode memory wall: the
        # cache-stream roofline term halves vs bf16. The int8 payload is
        # lane-dense, [B, S, n_kv·hd]: the layout the decode kernel reads
        # in place.
        S = cache["k"].shape[1]
        pos = cache["pos"]
        # pos may be a scalar (whole-batch serving: every row at the same
        # offset) or a [B] vector (continuous batching: per-slot offsets, with
        # kpos then [B, S]). The vector form scatters per row.
        per_slot = pos.ndim == 1
        if per_slot:
            qpos = pos[:, None] + jnp.arange(T)[None, :]           # [B, T]
            row = jnp.arange(B)[:, None]
        else:
            qpos = pos + jnp.arange(T)
        idx = qpos % S                       # ring write offset per new token
        # bookkeeping + attention mask from the POST-write slot positions
        if per_slot:
            kpos = cache["kpos"].at[row, idx].set(qpos)
            m = (kpos >= 0)[:, None, :] & (kpos[:, None, :] <= qpos[..., None])
            if dims.window is not None:
                m = m & (kpos[:, None, :] > qpos[..., None] - dims.window)
            mask = m  # 3-D [B, Tq, S]
        else:
            kpos = cache["kpos"].at[idx].set(qpos)
            m = (kpos >= 0)[None, :] & (kpos[None, :] <= qpos[:, None])
            if dims.window is not None:
                m = m & (kpos[None, :] > qpos[:, None] - dims.window)
            mask = m  # 2-D [Tq, S]
        if "k_scale" in cache:
            from ..kernels.kv_attention.ops import (
                append_quantize,
                kv_attention_decode,
            )
            from ..kernels.kv_attention.ref import split_heads

            valid = m[:, 0, :] if per_slot else m[0][None, :]     # [B|1, S]
            if T == 1:
                # decode hot path: ONE dispatch from roped q/k/v to the
                # attention out — the fused_decode megakernel quantizes the
                # new token's K/V in VMEM, appends it to the int8 cache in
                # place, and runs the online-softmax attention over the
                # updated block (Pallas on TPU, the exact stepwise
                # composition on the XLA tier — backend resolution lives in
                # kernels.dispatch). Masking rides on the scales: invalid
                # positions get scale 0, so no dequantized [B, S, H, hd]
                # cache is ever materialized. The V bias correction is
                # XLA-composition-only, so a v_err cache routes off Pallas.
                from ..kernels.dispatch import serving_backend
                from ..kernels.fused_decode.ops import (
                    fused_decode,
                    fusion_enabled,
                )

                verr = cache.get("v_err")
                backend = serving_backend(pallas_ok=verr is None)
                part = (None if verr is not None
                        else _serve_decode_partition(
                            nq, nkv, B, kernel=backend != "xla"))
                # the W8A8 wo projection reads the kernel's quantize-out
                # epilogue directly (int8 + per-row scale): the standalone
                # quantize_act dispatch between attention and wo is gone
                want_q8 = (_all_w8a8(p["wo"]) and verr is None
                           and part is None)
                if part is not None:
                    # TP: shard_map over (data, model) — head-local, zero
                    # collectives, no quantize-out (the row scale is a
                    # cross-head reduction)
                    out, leaves = _fused_decode_tp(
                        part, q[:, 0], cache, k, v, idx, valid, x.dtype,
                        backend, fused_decode if fusion_enabled()
                        else kv_attention_decode)
                elif not fusion_enabled():
                    out, leaves = kv_attention_decode(
                        q[:, 0], cache["k"], cache["k_scale"], cache["v"],
                        cache["v_scale"], k, v, idx, valid=valid,
                        out_dtype=x.dtype, backend=backend,
                        cache_verr=verr,
                    )
                else:
                    res, leaves = fused_decode(
                        q[:, 0], cache["k"], cache["k_scale"], cache["v"],
                        cache["v_scale"], k, v, idx, valid=valid,
                        out_dtype=x.dtype,
                        backend=None if verr is not None else backend,
                        cache_verr=verr, quantize_out=want_q8,
                    )
                    if want_q8:
                        out, attn_q8 = res[0], (res[1], res[2])
                    else:
                        out = res
                attn_fused = out[:, None]                   # [B, 1, Hq, hd]
            else:
                # chunked prefill: append-quantize once, then dequantize for
                # the batched attention (compute-bound regime; the kernel is
                # a single-token decode op)
                leaves = append_quantize(
                    cache["k"], cache["k_scale"], cache["v"],
                    cache["v_scale"], k, v, idx,
                    cache_verr=cache.get("v_err"),
                )
                ck, ks, cv, vs = leaves[:4]
                k = (split_heads(ck.astype(x.dtype), nkv)
                     * ks.astype(x.dtype)[..., None])
                v = (split_heads(cv.astype(x.dtype), nkv)
                     * vs.astype(x.dtype)[..., None])
                if "v_err" in cache:
                    # Σ p (ṽ − e) == Σ p ṽ − Σ p e: same correction as decode
                    v = v - leaves[4].astype(x.dtype)[..., None]
            new_cache = {"k": leaves[0], "k_scale": leaves[1],
                         "v": leaves[2], "v_scale": leaves[3],
                         "kpos": kpos, "pos": pos + T}
            if "v_err" in cache:
                new_cache["v_err"] = leaves[4]
        else:
            if per_slot:
                ck = cache["k"].at[row, idx].set(k.astype(cache["k"].dtype))
                cv = cache["v"].at[row, idx].set(v.astype(cache["v"].dtype))
            else:
                ck = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
                cv = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv, "kpos": kpos, "pos": pos + T}
            k, v = ck.astype(x.dtype), cv.astype(x.dtype)
    elif cache is not None and kv_input is not None:
        # cross-attention cache: static encoder K/V (computed at prefill)
        k = cache["k"].astype(x.dtype)
        v = cache["v"].astype(x.dtype)
        new_cache = cache

    if attn_fused is not None:
        attn = attn_fused.reshape(B, T, nq * hd)
        if capture:
            stats["o_in"] = jnp.mean(attn.reshape(-1, nq * hd), 0)
        if attn_q8 is not None:
            from ..quantized.qtensor import qtensor_matmul_prequant

            out = qtensor_matmul_prequant(
                attn_q8[0], attn_q8[1], p["wo"], p.get("bo"), (B, T),
                out_dtype=x.dtype)
        else:
            out = linear(attn, p["wo"], p.get("bo"), tp=_head_tp(nq, "row"))
        return out, new_cache, stats

    group = nq // nkv
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)
    if _SHARD_CTX["enabled"] and cache is None and kv_input is None:
        ctx = _SHARD_CTX
        if ctx["attn_seq"]:
            # context parallelism: q-sequence over the model axis; K/V
            # replicated (they are the small GQA tensors)
            q = _wsc(q, ctx["dp"], ctx["model"], None, None)
            k = _wsc(k, ctx["dp"], None, None, None)
            v = _wsc(v, ctx["dp"], None, None, None)
        else:
            # Megatron head parallelism
            q = _wsc(q, ctx["dp"], None, ctx["model"], None)
            k = _wsc(k, ctx["dp"], None, ctx["model"], None)
            v = _wsc(v, ctx["dp"], None, ctx["model"], None)
    attn = attention_scores_softmax(
        q, k, v, mask, chunk_kv=chunk_kv, unroll=unroll,
        causal_segments=(dims.causal_segments if kv_input is None else 1),
    )
    attn = attn.reshape(B, T, nq * hd)
    if _SHARD_CTX["enabled"] and cache is None and kv_input is None:
        ctx = _SHARD_CTX
        if ctx["attn_seq"]:
            attn = _wsc(attn, ctx["dp"], ctx["model"], None)
        else:
            attn = _wsc(attn, ctx["dp"], None, ctx["model"])
    if capture:
        stats["o_in"] = jnp.mean(attn.reshape(-1, nq * hd), 0)
    out = linear(attn, p["wo"], p.get("bo"), tp=_head_tp(nq, "row"))
    return out, new_cache, stats


# --------------------------------------------------------------------------
# MLP (dense / GLU) and MoE
# --------------------------------------------------------------------------

def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def mlp_block(p: dict, x: jnp.ndarray, act: str, capture: bool = False):
    """act ∈ {silu_glu, gelu_glu, gelu, relu}."""
    stats = {}
    if capture:
        stats["mlp_in"] = jnp.mean(x.reshape(-1, x.shape[-1]), 0)
    if act.endswith("_glu"):
        if _all_w8a8(p["wg"], p["wu"]):
            # gate and up read the same x: one shared quantize dispatch
            g, u = _shared_linears(x, [(p["wg"], p.get("bg")),
                                       (p["wu"], p.get("bu"))],
                                   ("col", "col"))
        else:
            g = linear(x, p["wg"], p.get("bg"), tp="col")
            u = linear(x, p["wu"], p.get("bu"), tp="col")
        h = _act(act[:-4])(g) * u
    else:
        h = _act(act)(linear(x, p["wu"], p.get("bu"), tp="col"))
    if capture:
        stats["down_in"] = jnp.mean(h.reshape(-1, h.shape[-1]), 0)
    return linear(h, p["wd"], p.get("bd"), tp="row"), stats


def moe_block(p: dict, x: jnp.ndarray, cfg: ModelConfig, capture: bool = False):
    """Top-k token-choice MoE with capacity. Expert params are stacked on a
    leading E axis.

    Under a mesh (shard-ctx armed) the block is HAND-PARTITIONED with
    shard_map: dispatch/combine gathers stay shard-local (GSPMD's generic
    scatter/gather partitioning replicated the batch — measured 100 GB/device
    of collectives on mixtral train_4k), expert FFNs are TP over d_ff, and a
    single [B,T,D] psum per layer closes the block. See EXPERIMENTS §Perf.
    """
    if _SHARD_CTX["enabled"] and _SHARD_CTX.get("mesh") is not None and not capture:
        return _moe_block_shardmap(p, x, cfg)
    return _moe_block_local(p, x, cfg, capture)


def _moe_block_local(p: dict, x: jnp.ndarray, cfg: ModelConfig, capture: bool = False):
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(T * K / E * cfg.capacity_factor))
    stats = {}
    if capture:
        stats["mlp_in"] = jnp.mean(x.reshape(-1, D), 0)

    router_logits = linear(x, p["router"], p.get("router_b"))  # [B, T, E]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)         # [B, T, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9
    )

    # --- gather/scatter dispatch --------------------------------------------
    # The classic one-hot dispatch einsum ('btec,btd->becd') costs
    # 2·B·T·E·C·D FLOPs — measured ~100× the expert FFN itself on mixtral
    # train_4k (EXPERIMENTS §Perf iteration 2). Instead we build an explicit
    # slot→token index map and move tokens with gathers (≈0 FLOPs; per-batch
    # gathers stay shard-local under the B=data sharding).
    slot_token = jnp.full((B, E, C + 1), T, jnp.int32)     # T = OOB sentinel
    slot_gate = jnp.zeros((B, E, C + 1), jnp.float32)
    token_pos = []
    used = jnp.zeros((B, E), jnp.int32)
    b_idx = jnp.arange(B)[:, None]
    t_idx = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    for slot in range(K):
        e = gate_idx[..., slot]                            # [B, T]
        onehot = jax.nn.one_hot(e, E, dtype=jnp.int32)     # [B, T, E]
        pos = jnp.cumsum(onehot, axis=1) - 1 + used[:, None, :]
        pos_sel = jnp.take_along_axis(pos, e[..., None], -1)[..., 0]  # [B, T]
        keep = pos_sel < C
        write_pos = jnp.where(keep, pos_sel, C)            # C = dropped bin
        slot_token = slot_token.at[b_idx, e, write_pos].set(t_idx)
        slot_gate = slot_gate.at[b_idx, e, write_pos].set(
            jnp.where(keep, gate_vals[..., slot], 0.0))
        token_pos.append((e, write_pos, keep))
        used = used + jnp.sum(onehot * (pos < C), axis=1)

    slot_token = slot_token[..., :C]                       # [B, E, C]
    slot_gate = slot_gate[..., :C]
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)
    # single flat gather along T — indexing via a [B, E, T+1, D] broadcast
    # operand was measured to 6× the collective bytes (EXPERIMENTS §Perf)
    ex_in = jnp.take_along_axis(
        x_pad, slot_token.reshape(B, E * C)[..., None], axis=1
    ).reshape(B, E, C, D)

    def expert_ffn(w, xin):
        # linear() so int8 QTensor expert weights dispatch through the kernels
        if cfg.act.endswith("_glu"):
            h = _act(cfg.act[:-4])(linear(xin, w["wg"])) * linear(xin, w["wu"])
        else:
            h = _act(cfg.act)(linear(xin, w["wu"]))
        return h, linear(h, w["wd"])

    h_pre, ex_out = jax.vmap(
        lambda w, xin: expert_ffn(w, xin), in_axes=(0, 1), out_axes=(0, 1)
    )(p["experts"], ex_in)

    # combine: per-slot gather from the expert outputs, weighted by the gate
    y = jnp.zeros((B, T, D), x.dtype)
    for slot in range(K):
        e, write_pos, keep = token_pos[slot]
        flat = e * C + jnp.minimum(write_pos, C - 1)       # [B, T]
        ex_flat = ex_out.reshape(B, E * C, D)
        picked = jnp.take_along_axis(ex_flat, flat[..., None], axis=1)
        w_k = jnp.where(keep, gate_vals[..., slot], 0.0).astype(x.dtype)
        y = y + picked * w_k[..., None]

    if cfg.n_shared_experts:
        shared, _ = mlp_block(p["shared"], x, cfg.act)
        y = y + shared
    if capture:
        stats["down_in_moe"] = jnp.mean(h_pre, axis=(1, 2))  # [E, F] per expert
        stats["router_probs"] = jnp.mean(probs.reshape(-1, E), 0)
    # load-balancing auxiliary loss (Switch/GShard) for training
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0].reshape(-1), E, dtype=jnp.float32), axis=0
    )
    aux = E * jnp.sum(me * ce)
    return y, aux, stats


def _moe_specs(p: dict, dp, mdl):
    """shard_map in_specs for the MoE param subtree, by leaf name. QTensor
    children flatten with index keys: index 0 = int8 payload (follows the
    parent weight's spec), index 1 = scale (replicated)."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        nd = leaf.ndim if hasattr(leaf, "ndim") else 0
        name = None
        is_scale = False
        for entry in reversed(path):
            if hasattr(entry, "key"):
                name = entry.key
                break
            if hasattr(entry, "idx") or hasattr(entry, "index"):
                idx = getattr(entry, "idx", getattr(entry, "index", None))
                is_scale = is_scale or idx == 1
        if is_scale or name is None:
            return P(*([None] * nd))
        if name in ("wu", "wg"):
            return P(*([None] * (nd - 1)), mdl)       # F sharded (col-parallel)
        if name == "wd":
            return P(*([None] * (nd - 2)), mdl, None)  # F sharded (row-parallel)
        return P(*([None] * nd))                       # router / biases replicate

    return jax.tree_util.tree_map_with_path(spec, p)


def _moe_block_shardmap(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    from jax.sharding import PartitionSpec as P

    ctx = _SHARD_CTX
    dp, mdl, mesh = ctx["dp"], ctx["model"], ctx["mesh"]
    dp_n = 1
    for a in dp:
        dp_n *= dict(mesh.shape)[a]
    # batch=1 long-context decode can't shard over data — replicate instead
    x_batch_spec = dp if x.shape[0] % dp_n == 0 else None

    def local_fn(p, x):
        if cfg.n_shared_experts:
            # the shared expert's output bias is replicated across the model
            # axis but the block output is psum'd — pre-scale to keep it exact
            n = jax.lax.psum(1.0, mdl)
            p = {**p, "shared": {**p["shared"], "bd": p["shared"]["bd"] / n}}
        y, aux, _ = _moe_block_local(p, x, cfg, capture=False)
        # wd was applied on a d_ff shard → partial sums; one psum closes it
        y = jax.lax.psum(y, mdl)
        aux = jax.lax.pmean(jax.lax.pmean(aux, mdl), dp)
        return y, aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(_moe_specs(p, dp, mdl), P(x_batch_spec, None, None)),
        out_specs=(P(x_batch_spec, None, None), P()),
        check_vma=False,
    )(p, x)
    return y, aux, {}
