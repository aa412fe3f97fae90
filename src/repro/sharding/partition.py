"""Divisibility-aware partition planner.

Assigns each parameter tensor a PartitionSpec over the production mesh
(("pod",) "data", "model"):

  * **TP** ("model") on the last (output-feature) dim — Megatron pattern:
    column-parallel qkv/gate/up, row-parallel o/down emerge automatically
    because each weight's *output* dim is sharded and GSPMD propagates,
  * **FSDP/ZeRO** ("data") on the first suitable non-scan dim — parameters,
    gradients and AdamW moments are all sharded over the data axis and
    all-gathered just-in-time by GSPMD,
  * anything non-divisible **replicates** (graceful degradation — e.g.
    qwen2's 14 heads never block compilation),
  * scan-stacked leading dims ([L] layers, and the [E] expert dim when not
    divisible) are never sharded,
  * the "pod" axis holds pure DP: params replicate across pods (keeps weight
    collectives on intra-pod ICI), batch shards over pod × data.

Embeddings / lm_head special-case: vocab on "model" (vocab-parallel logits +
sharded softmax), d_model on "data".
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


MIN_SHARD_DIM = 128  # don't shard tiny dims — collective overhead dominates


def _divisible(dim: int, size: int) -> bool:
    return dim >= MIN_SHARD_DIM and dim % size == 0


_ROW_PARALLEL = ("wo", "wd", "out_proj")   # consume a TP-sharded activation


def _leaf_spec(path: str, shape, mesh: Mesh, n_stacked: int,
               heads: Optional[dict] = None, mode: str = "train") -> P:
    """Megatron-pattern placement:

      * column-parallel (wq/wk/wv/wg/wu/router/in_proj): in=data (FSDP),
        out=model — but attention projections only when the HEAD COUNT
        divides the model axis (a flat-dim shard that splits heads makes
        GSPMD factor the axis through the [B,T,H,hd] reshape and all-reduce
        score tensors — measured 30 GB/layer on qwen2),
      * row-parallel (wo/wd/out_proj): in=model, out=data — the activation
        stays f-sharded through the pair and one all-reduce of [B,T,D]
        partial sums closes the block,
      * non-divisible dims replicate (graceful degradation).

    ``mode="decode"`` drops the FSDP factor (resident serving weights);
    ``mode="serve"`` is decode placement PLUS co-sharded quantized leaves:
    a per-channel QTensor scale lands on the same "model" shard as its int8
    payload's out-feature columns, so a TP shard dequantizes locally without
    gathering foreign scales.
    """
    axes: list = [None] * len(shape)
    if len(shape) == 0:
        return P()
    model_n = mesh.shape.get("model", 1)
    data_n = mesh.shape.get("data", 1)
    if mode in ("decode", "serve"):
        data_n = 10 ** 9  # nothing divides this → no FSDP factor on weights
    heads = heads or {}
    n_q, n_kv = heads.get("n_q", 0), heads.get("n_kv", 0)

    def head_ok(n):
        return n > 0 and n % model_n == 0

    is_attn = "/attn/" in path or "/cross/" in path
    name = path.rsplit("/", 1)[-1]
    if name in ("q", "scale"):           # QTensor children: rules key off the
        parent = path.rsplit("/", 3)[-2]  # parent weight's name (wq/wd/...)
        if name == "scale":
            # The scale's channel dim mirrors the parent weight's OUT-feature
            # dim. Serve mode co-shards it with the int8 payload: a
            # column-parallel weight's scale follows its columns onto "model";
            # row-parallel weights shard the IN dim, so their scales (and all
            # per-tensor size-1 scales — never divisible) replicate.
            if mode != "serve":
                return P()
            out = len(shape) - 1
            tp_ok = _divisible(shape[out], model_n) and parent not in _ROW_PARALLEL
            if is_attn and parent == "wq":
                tp_ok = tp_ok and head_ok(n_q)
            elif is_attn and parent in ("wk", "wv"):
                tp_ok = tp_ok and head_ok(n_kv)
            elif parent == "in_proj":
                tp_ok = False
            if tp_ok:
                axes[out] = "model"
            return P(*axes)
        name = parent

    is_embed = path.endswith("embed") or path.endswith("lm_head") or path.endswith("dec_pos")
    if is_embed and len(shape) == 2:
        spec = [None, None]
        if _divisible(shape[0], model_n):
            spec[0] = "model"          # vocab-parallel
        if _divisible(shape[1], data_n):
            spec[1] = "data"
        if path.endswith("lm_head"):   # [D, V]: vocab is the LAST dim
            spec = [None, None]
            if _divisible(shape[1], model_n):
                spec[1] = "model"
            if _divisible(shape[0], data_n):
                spec[0] = "data"
        return P(*spec)

    free = list(range(n_stacked, len(shape)))
    if len(free) < 2:
        return P()  # 1-D (biases/norm scales): replicate — sharding is noise

    in_dim, out_dim = free[-2], free[-1]
    if name in _ROW_PARALLEL:
        tp_ok = _divisible(shape[in_dim], model_n)
        if name == "wo":
            tp_ok = tp_ok and head_ok(n_q)
        if tp_ok:
            axes[in_dim] = "model"
        if _divisible(shape[out_dim], data_n):
            axes[out_dim] = "data"
        return P(*axes)

    # column-parallel default
    tp_ok = _divisible(shape[out_dim], model_n)
    if is_attn and name == "wq":
        tp_ok = tp_ok and head_ok(n_q)
    elif is_attn and name in ("wk", "wv"):
        tp_ok = tp_ok and head_ok(n_kv)
    elif name == "in_proj":
        tp_ok = False  # mamba: mixed z/x/B/C/dt segments — replicate out
    if tp_ok:
        axes[out_dim] = "model"
    if _divisible(shape[in_dim], data_n):
        axes[in_dim] = "data"
    return P(*axes)


def _n_stacked(path: str, cfg=None) -> int:
    n = 0
    if "blocks" in path:  # scan-stacked layers (and shared_blocks)
        n += 1
    if "experts" in path:
        n += 1
    return n


def _walk(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _walk(v, f"{prefix}/{i}")
    elif type(tree).__name__ == "QTensor":  # int8 serving weights: q + scale
        yield from _walk(tree.q, f"{prefix}/q")
        yield from _walk(tree.scale, f"{prefix}/scale")
    else:
        yield prefix, tree


def _rebuild(tree, flat: dict, prefix: str = ""):
    """Re-nest a {path: spec} mapping into the shape tree's structure (the
    inverse of ``_walk`` — one implementation for every *_pspecs builder)."""
    if isinstance(tree, dict):
        return {k: _rebuild(v, flat, f"{prefix}/{k}") for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_rebuild(v, flat, f"{prefix}/{i}") for i, v in enumerate(tree)]
        return type(tree)(t) if not hasattr(tree, "_fields") else type(tree)(*t)
    if type(tree).__name__ == "QTensor":
        from ..quantized.qtensor import QTensor

        return QTensor(_rebuild(tree.q, flat, f"{prefix}/q"),
                       _rebuild(tree.scale, flat, f"{prefix}/scale"), tree.mode)
    return flat[prefix]


def _dp_world(mesh: Mesh):
    """(dp_axes, dp_n): the data-parallel axis spec (with the leading "pod"
    when present) and its total world size."""
    dp_axes = ("pod", "data") if "pod" in mesh.shape else "data"
    dp_n = int(np.prod([mesh.shape[a] for a in
                        ((dp_axes,) if isinstance(dp_axes, str) else dp_axes)]))
    return dp_axes, dp_n


def params_pspecs(params_shapes: Any, mesh: Mesh, heads: Optional[dict] = None,
                  mode: str = "train") -> Any:
    """PartitionSpec pytree matching a params (or optimizer-state) pytree of
    arrays / ShapeDtypeStructs. ``heads`` = {"n_q", "n_kv"} enables the
    head-divisibility constraint on attention projections. ``mode="decode"``
    drops the FSDP ("data") factor: serving weights stay device-resident."""

    def spec_of(path, leaf):
        return _leaf_spec(path, leaf.shape, mesh, _n_stacked(path), heads, mode)

    paths = dict(_walk(params_shapes))
    flat_specs = {p: spec_of(p, l) for p, l in paths.items()}
    return _rebuild(params_shapes, flat_specs)


def batch_pspec(mesh: Mesh, ndim: int = 2, batch: Optional[int] = None) -> P:
    """Batch dim over (pod, data); replicate when the global batch doesn't
    divide the DP world (the long-context batch=1 decode cells)."""
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    if batch is not None and batch % dp_n != 0:
        return P(*([None] * ndim))
    return P(dp, *([None] * (ndim - 1)))


def _payload_heads(paths: dict) -> dict:
    """{path of an int8 cache payload "k"/"v": its kv head count}, read off
    the "k_scale"/"v_scale" sibling ([..., H]). The lane-dense payload's
    last axis is H·hd, and a "model" shard of it must hold whole heads, so
    it shards only where H divides — not wherever H·hd does."""
    return {p[:-len("_scale")]: leaf.shape[-1] for p, leaf in paths.items()
            if p.endswith(("/k_scale", "/v_scale"))}


def cache_pspecs(cache_shapes: Any, mesh: Mesh, batch: int) -> Any:
    """KV/SSM cache sharding: batch over (pod, data) when divisible, else
    sequence over "data" (the long-context B=1 case); heads over "model"."""
    dp_axes, dp_n = _dp_world(mesh)
    model_n = mesh.shape.get("model", 1)
    paths = dict(_walk(cache_shapes))
    heads = _payload_heads(paths)

    def spec_of(path, leaf):
        shape = leaf.shape
        if len(shape) <= 1:
            return P()
        axes: list = [None] * len(shape)
        # layouts: k/v [L, B, S, H, hd] (int8: [L, B, S, H·hd]);
        # ssm [L, B, H, P, S]; conv [L, B, W, C]
        if len(shape) >= 3:
            B_dim = 1
            if shape[B_dim] % dp_n == 0 and shape[B_dim] >= dp_n:
                axes[B_dim] = dp_axes
            elif (path.endswith("/k") or path.endswith("/v")
                  or path.endswith("_scale") or path.endswith("/v_err")):
                S_dim = 2
                if shape[S_dim] % dp_n == 0:
                    axes[S_dim] = dp_axes
            if ((path.endswith("_scale") or path.endswith("/v_err"))
                    and len(shape) == 4):
                # [L, B, S, H] int8-cache scales (and the optional V
                # dequant-error means): follow the payload sharding
                if shape[2] % model_n == 0 and shape[2] >= model_n:
                    axes[2] = "model"
            if (path.endswith("/k") or path.endswith("/v")) and len(shape) >= 4:
                # Prefer SEQUENCE sharding of the cache over "model": the
                # pv contraction then psums a tiny [B,H,1,hd] partial per
                # layer. Sharding heads/head_dim instead psums [B,H,1,S]
                # score rows — measured 22.6 GB/device/step on yi-34b
                # decode_32k (EXPERIMENTS §Perf iteration C2).
                H = heads.get(path, shape[3])
                if axes[2] is None and shape[2] % model_n == 0 and shape[2] >= model_n:
                    axes[2] = "model"
                elif H % model_n == 0 and H >= model_n:
                    axes[3] = "model"
                elif (len(shape) == 5 and shape[4] % model_n == 0
                      and shape[4] >= model_n):
                    axes[4] = "model"
            if path.endswith("/ssm") and len(shape) == 5:
                if shape[2] % model_n == 0:
                    axes[2] = "model"
        return P(*axes)

    flat = {p: spec_of(p, l) for p, l in paths.items()}
    return _rebuild(cache_shapes, flat)


def serve_cache_pspecs(cache_shapes: Any, mesh: Mesh) -> Any:
    """Serving (per-slot pooled) cache sharding for the continuous-batching
    engine: the SLOT axis shards over "data" and KV heads over "model".

    Layouts: k/v [L, B, S, H, hd] (fp) or lane-dense [L, B, S, H·hd]
    (int8); k_scale/v_scale/v_err [L, B, S, H]; kpos [B, S]; pos [B] — B is
    the slot axis. Rules:

      * slots over ("pod",) "data" when the pool size divides the DP world —
        no MIN_SHARD_DIM floor here: slot pools are inherently small and
        every slot's computation is row-independent, so slot sharding is
        exact (it never changes a reduction order),
      * KV heads over "model" when divisible (head-parallel attention — each
        head's softmax·V stays device-local); the int8 payload's lane-dense
        H·hd axis splits over "model" when H does, so each shard holds
        contiguous whole heads,
      * the int8-cache scale leaves (k_scale/v_scale) and the V dequant-error
        means (v_err) FOLLOW their payload tensor: same slot axis, same head
        axis, so a shard dequantizes its own cache columns locally,
      * anything non-divisible replicates (graceful degradation).

    **Paged pools** (a ``page_table`` leaf is present; payload leaves are
    [L, NP, pg, ...]) shard KV heads (axis 3) over "model" exactly like
    the contiguous layout, but the PAGE axis — and the page tables and
    dense kpos/pos bookkeeping — replicate. Sharding pages over "data"
    looks symmetric to slot-sharding, but the paged jits address pages
    through data-dependent table lookups, so GSPMD would have to all-gather
    whole pool leaves around every page gather/scatter: new full-pool
    collectives, exactly what the lint contracts' collective budget pins at
    zero. Head sharding keeps the capacity win (each shard holds 1/TP of
    every page) without any cross-shard addressing; slot-parallel paged
    serving (shard_map over per-shard page pools) is the ROADMAP follow-on.
    """
    dp_axes, dp_n = _dp_world(mesh)
    model_n = mesh.shape.get("model", 1)
    paths = dict(_walk(cache_shapes))
    paged = any(p.rsplit("/", 1)[-1] == "page_table" for p in paths)
    heads = _payload_heads(paths)

    def spec_of(path, leaf):
        shape = leaf.shape
        axes: list = [None] * len(shape)
        name = path.rsplit("/", 1)[-1]
        if name in ("kpos", "pos"):                     # [B, S] / [B]
            if (not paged and shape and shape[0] % dp_n == 0
                    and shape[0] >= dp_n):
                axes[0] = dp_axes
            return P(*axes)
        if name in ("k", "v", "k_scale", "v_scale", "v_err") and len(shape) >= 4:
            if (not paged and shape[1] % dp_n == 0 and shape[1] >= dp_n):
                axes[1] = dp_axes                       # slot axis
            H_dim = 3                                   # heads (payload + scales)
            H = heads.get(path, shape[H_dim])
            if H % model_n == 0 and H >= model_n:
                axes[H_dim] = "model"
            return P(*axes)
        return P(*axes)

    flat = {p: spec_of(p, l) for p, l in paths.items()}
    return _rebuild(cache_shapes, flat)


def named_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def payload_scale_pairs(tree: Any, prefix: str = "") -> list:
    """Every (q_path, scale_path) pair of QTensor leaves in a params pytree,
    in ``_walk`` path notation — the scale-coupling lint rule checks each
    pair shares its out-feature sharding axis."""
    pairs: list = []
    if type(tree).__name__ == "QTensor":
        pairs.append((f"{prefix}/q", f"{prefix}/scale"))
    elif isinstance(tree, dict):
        for k, v in tree.items():
            pairs.extend(payload_scale_pairs(v, f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            pairs.extend(payload_scale_pairs(v, f"{prefix}/{i}"))
    return pairs


def spec_paths(spec_tree: Any, prefix: str = ""):
    """Yield (path, PartitionSpec) pairs from a spec pytree. A dedicated
    walker: PartitionSpec subclasses tuple on some jax versions, so the
    generic ``_walk`` would iterate INTO the spec instead of yielding it."""
    if isinstance(spec_tree, P):
        yield prefix, spec_tree
    elif isinstance(spec_tree, dict):
        for k, v in spec_tree.items():
            yield from spec_paths(v, f"{prefix}/{k}")
    elif isinstance(spec_tree, (list, tuple)):
        for i, v in enumerate(spec_tree):
            yield from spec_paths(v, f"{prefix}/{i}")
    elif type(spec_tree).__name__ == "QTensor":
        yield from spec_paths(spec_tree.q, f"{prefix}/q")
        yield from spec_paths(spec_tree.scale, f"{prefix}/scale")
    else:
        yield prefix, spec_tree
