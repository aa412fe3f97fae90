"""Decoder-only LM covering the dense / moe / vlm / ssm / hybrid families.

Layers are scan-stacked (params carry a leading [L] dim) to bound HLO size at
production depth; the hybrid (zamba2) family scans homogeneous Mamba segments
and interleaves the *shared* attention blocks between segments.

The class exposes:  init / apply (train fwd) / loss / init_cache / prefill /
decode_step / dfq_plan / calibration_stats — everything the launcher, the
dry-run, and the DFQ pipeline need.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core.graph import (
    DFQPlan,
    DensePairOp,
    NormFoldOp,
    QKPairOp,
    VBiasAbsorbOp,
    VOPairOp,
    WeightSite,
)
from .config import ModelConfig
from .layers import (
    AttnDims,
    apply_norm,
    attention_block,
    causal_mask,
    linear,
    mlp_block,
    moe_block,
    scan_layers,
)
from .mamba import init_mamba_params, mamba_block, ssm_dims


def _init_linear(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / (d_in ** 0.5)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def _norm_params(cfg: ModelConfig, d: int, dtype):
    p = {"w": jnp.ones((d,), dtype)}
    if cfg.norm == "ln":
        p["b"] = jnp.zeros((d,), dtype)
    return p


class LMModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _init_attn(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p = {
            "wq": _init_linear(ks[0], cfg.d_model, cfg.attn_dim, dtype),
            "wk": _init_linear(ks[1], cfg.d_model, cfg.kv_dim, dtype),
            "wv": _init_linear(ks[2], cfg.d_model, cfg.kv_dim, dtype),
            "wo": _init_linear(ks[3], cfg.attn_dim, cfg.d_model, dtype),
            "bo": jnp.zeros((cfg.d_model,), dtype),
        }
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((cfg.attn_dim,), dtype)
            p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
            p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
            p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
        return p

    def _init_mlp(self, key, dtype, d_ff=None):
        cfg = self.cfg
        f = d_ff or cfg.d_ff
        ks = jax.random.split(key, 3)
        p = {
            "wu": _init_linear(ks[0], cfg.d_model, f, dtype),
            "wd": _init_linear(ks[1], f, cfg.d_model, dtype),
            "bd": jnp.zeros((cfg.d_model,), dtype),
        }
        if cfg.act.endswith("_glu"):
            p["wg"] = _init_linear(ks[2], cfg.d_model, f, dtype)
        return p

    def _init_moe(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
        experts = {
            "wu": (jax.random.normal(ks[0], (E, D, F)) / D ** 0.5).astype(dtype),
            "wd": (jax.random.normal(ks[1], (E, F, D)) / F ** 0.5).astype(dtype),
        }
        if cfg.act.endswith("_glu"):
            experts["wg"] = (jax.random.normal(ks[2], (E, D, F)) / D ** 0.5).astype(dtype)
        p = {"router": _init_linear(ks[3], D, E, dtype), "experts": experts}
        if cfg.n_shared_experts:
            p["shared"] = self._init_mlp(ks[4], dtype, cfg.d_ff * cfg.n_shared_experts)
        return p

    def _init_block(self, key, dtype):
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        block = {
            "attn_norm": _norm_params(cfg, cfg.d_model, dtype),
            "attn": self._init_attn(ks[0], dtype),
            "mlp_norm": _norm_params(cfg, cfg.d_model, dtype),
        }
        block["mlp"] = (
            self._init_moe(ks[1], dtype) if cfg.n_experts else self._init_mlp(ks[1], dtype)
        )
        return block

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        ks = jax.random.split(key, 4)
        params: dict = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
            "final_norm": _norm_params(cfg, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = _init_linear(ks[3], cfg.d_model, cfg.vocab_size, dtype)

        if cfg.family == "ssm":
            params["blocks"] = self._stack_init(
                lambda k: {
                    "norm": _norm_params(cfg, cfg.d_model, dtype),
                    "mixer": init_mamba_params(k, cfg, dtype),
                },
                ks[1],
                cfg.n_layers,
            )
        elif cfg.family == "hybrid":
            params["blocks"] = self._stack_init(
                lambda k: {
                    "norm": _norm_params(cfg, cfg.d_model, dtype),
                    "mixer": init_mamba_params(k, cfg, dtype),
                },
                ks[1],
                cfg.n_layers,
            )
            params["shared_blocks"] = self._stack_init(
                lambda k: self._init_block(k, dtype),
                ks[2],
                cfg.hybrid_n_shared_blocks,
            )
        else:
            params["blocks"] = self._stack_init(
                lambda k: self._init_block(k, dtype), ks[1], cfg.n_layers
            )
        return params

    @staticmethod
    def _stack_init(fn, key, n):
        keys = jax.random.split(key, n)
        trees = [fn(k) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    # -------------------------------------------------------------- forward
    def _attn_dims(self) -> AttnDims:
        cfg = self.cfg
        return AttnDims(
            n_q=cfg.n_heads,
            n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm,
            rope=cfg.rope,
            rope_theta=cfg.rope_theta,
            window=cfg.sliding_window,
            causal_segments=cfg.attn_causal_segments,
        )

    def _transformer_block(
        self, p, x, *, positions, mask, cache=None, chunk_kv=None, capture=False
    ):
        cfg = self.cfg
        h = apply_norm(x, p["attn_norm"], cfg.norm)
        attn_out, new_cache, s1 = attention_block(
            p["attn"], h, self._attn_dims(),
            positions=positions, mask=mask, cache=cache,
            chunk_kv=chunk_kv, capture=capture, unroll=cfg.unroll_layers,
        )
        x = x + attn_out
        h = apply_norm(x, p["mlp_norm"], cfg.norm)
        aux = 0.0
        if cfg.n_experts:
            mlp_out, aux, s2 = moe_block(p["mlp"], h, cfg, capture=capture)
        else:
            mlp_out, s2 = mlp_block(p["mlp"], h, cfg.act, capture=capture)
        x = x + mlp_out
        stats = {**s1, **s2} if capture else {}
        return x, new_cache, aux, stats

    def _mamba_layer(self, p, x, *, state=None, capture=False):
        h = apply_norm(x, p["norm"], self.cfg.norm)
        out, new_state, stats = mamba_block(
            p["mixer"], h, self.cfg, state=state, capture=capture
        )
        return x + out, new_state, stats

    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
        return x

    def _unembed(self, params, h):
        from .layers import _SHARD_CTX, _wsc

        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        # seq-sharded hidden (context parallelism) meets a vocab-sharded
        # head: without boundary constraints GSPMD replicates the full
        # [B, C, V] logits (measured 2×40 GB collectives). Re-shard h to
        # batch-only and pin logits to vocab-parallel.
        if _SHARD_CTX["enabled"]:
            h = _wsc(h, _SHARD_CTX["dp"], *([None] * (h.ndim - 1)))
        logits = h @ w.astype(h.dtype)
        if _SHARD_CTX["enabled"]:
            logits = _wsc(logits, _SHARD_CTX["dp"],
                          *([None] * (h.ndim - 2)), _SHARD_CTX["model"])
        return logits

    def apply(
        self,
        params,
        tokens,
        *,
        capture: bool = False,
        chunk_kv: Optional[int] = None,
        return_hidden: bool = False,
    ):
        """Training/eval forward: causal, no cache. Returns logits (or hidden)
        and (aux_loss, stats)."""
        cfg = self.cfg
        compute = jnp.dtype(cfg.dtype)
        params = jax.tree.map(
            lambda a: a.astype(compute) if a.dtype == jnp.float32 and compute != jnp.float32 else a,
            params,
        )
        B, T = tokens.shape
        x = self._embed(params, tokens)
        positions = jnp.arange(T)
        mask = causal_mask(T, T, 0, cfg.sliding_window)

        aux_total = 0.0
        stats_all: dict = {}

        if cfg.family in ("ssm", "hybrid"):
            def ssm_body(carry, p):
                x = carry
                x, _, stats = self._mamba_layer(p, x, capture=capture)
                return x, stats

            body = jax.checkpoint(ssm_body) if cfg.remat else ssm_body
            if cfg.family == "ssm":
                x, stats = scan_layers(body, x, params["blocks"], cfg.unroll_layers)
                stats_all.update(stats if capture else {})
            else:
                every = cfg.hybrid_attn_every
                n_seg = cfg.n_layers // every
                seg_params = jax.tree.map(
                    lambda a: a.reshape(n_seg, every, *a.shape[1:]), params["blocks"]
                )
                mamba_stats = []
                for seg in range(n_seg):
                    p_seg = jax.tree.map(lambda a: a[seg], seg_params)
                    x, stats = scan_layers(body, x, p_seg, cfg.unroll_layers)
                    if capture:
                        mamba_stats.append(stats)
                    shared = jax.tree.map(
                        lambda a: a[seg % cfg.hybrid_n_shared_blocks],
                        params["shared_blocks"],
                    )
                    x, _, aux, s = self._transformer_block(
                        shared, x, positions=positions, mask=mask,
                        chunk_kv=chunk_kv, capture=capture,
                    )
                    aux_total = aux_total + aux
                    if capture:
                        stats_all[f"shared_{seg}"] = s
                if capture and mamba_stats:
                    stats_all["mamba"] = jax.tree.map(
                        lambda *xs: jnp.concatenate(xs), *mamba_stats
                    )
        else:
            def block_body(carry, p):
                x, aux = carry
                x, _, a, stats = self._transformer_block(
                    p, x, positions=positions, mask=mask,
                    chunk_kv=chunk_kv, capture=capture,
                )
                return (x, aux + a), stats

            body = jax.checkpoint(block_body) if cfg.remat else block_body
            (x, aux_total), stats = scan_layers(body, (x, 0.0), params["blocks"],
                                                cfg.unroll_layers)
            if capture:
                stats_all = stats

        x = apply_norm(x, params["final_norm"], cfg.norm)
        if capture:
            stats_all["final_h"] = jnp.mean(x.reshape(-1, cfg.d_model), 0)
        if return_hidden:
            return x, (aux_total, stats_all)
        return self._unembed(params, x), (aux_total, stats_all)

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch, *, chunk_kv: Optional[int] = None):
        """Chunked-over-sequence cross entropy (bounds the [B, c, V] logits
        buffer); adds the MoE load-balance aux loss."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        h, (aux, _) = self.apply(
            params, tokens, chunk_kv=chunk_kv, return_hidden=True
        )
        B, T, D = h.shape
        C = min(cfg.logit_chunk, T)
        n = T // C
        h_c = h.reshape(B, n, C, D).transpose(1, 0, 2, 3)
        l_c = labels.reshape(B, n, C).transpose(1, 0, 2)

        def chunk_loss(carry, inp):
            hc, lc = inp
            logits = self._unembed(params, hc).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, -1)
            # gold logit via a masked reduce (NOT take_along_axis): the iota
            # compare propagates through a vocab-sharded logits tensor, while
            # a gather forces GSPMD to replicate the full [B,C,V] logits
            # (measured: 2x40 GB per-device collectives on qwen2 train_4k).
            iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
            gold = jnp.sum(jnp.where(iota == lc[..., None], logits, 0.0), axis=-1)
            return carry + jnp.sum(logz - gold), None

        total, _ = jax.lax.scan(chunk_loss, jnp.float32(0.0), (h_c, l_c))
        loss = total / (B * T)
        if cfg.n_experts:
            loss = loss + 0.01 * aux
        return loss

    # ---------------------------------------------------------------- cache
    def cache_len(self, seq_len: int) -> int:
        if self.cfg.sliding_window is not None:
            return min(seq_len, self.cfg.sliding_window)
        return seq_len

    def init_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16,
                   per_slot: bool = False, kv_bits: Optional[int] = None) -> dict:
        """``per_slot=True`` builds the continuous-batching variant: each
        batch row is an independent serving slot with its own write offset
        (``pos`` [B]) and absolute slot positions (``kpos`` [B, S]), so the
        engine can prefill/retire rows at different sequence positions.
        ``kv_bits`` overrides ``cfg.kv_cache_bits`` (8 → lane-dense int8
        payload [L, B, S, Hkv·hd] + per-token/per-head scales [L, B, S,
        Hkv]; 16 → fp payload [L, B, S, Hkv, hd] in ``dtype``)."""
        cfg = self.cfg
        kv_bits = cfg.kv_cache_bits if kv_bits is None else int(kv_bits)
        if kv_bits not in (8, 16):
            raise ValueError(f"kv_bits must be 8 or 16, got {kv_bits}")
        S = self.cache_len(seq_len)
        if per_slot and cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                f"per-slot caches are only supported for attention-family "
                f"models (got family={cfg.family!r}); SSM state handoff is "
                f"position-free but needs dedicated plumbing"
            )
        if cfg.family == "ssm":
            _, H, G, St, _, d_conv = ssm_dims(cfg)
            return {
                "ssm": jnp.zeros((cfg.n_layers, batch, H, cfg.ssm_head_dim, St), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv_width - 1, d_conv), dtype),
                "pos": jnp.zeros((), jnp.int32),
            }
        # the int8 payload is lane-dense, [L, B, S, Hkv·hd]: one cache
        # position of every kv head is one row, the layout the decode
        # kernel reads in place; the fp payload keeps [L, B, S, Hkv, hd]
        kv_shape = ((cfg.n_layers, batch, S, cfg.n_kv_heads * cfg.head_dim)
                    if kv_bits == 8 else
                    (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim))
        kv_dtype = jnp.int8 if kv_bits == 8 else dtype
        kv = {
            "k": jnp.zeros(kv_shape, kv_dtype),
            "v": jnp.zeros(kv_shape, kv_dtype),
            "kpos": (jnp.full((batch, S), -1, jnp.int32) if per_slot
                     else jnp.full((S,), -1, jnp.int32)),
            "pos": (jnp.zeros((batch,), jnp.int32) if per_slot
                    else jnp.zeros((), jnp.int32)),
        }
        if kv_bits == 8:
            # scale 0 == "position invalid" (the kv_attention masking
            # contract): an unwritten cache position is masked by
            # construction, not just by the kpos bookkeeping
            kv["k_scale"] = jnp.zeros((cfg.n_layers, batch, S, cfg.n_kv_heads), jnp.float32)
            kv["v_scale"] = jnp.zeros((cfg.n_layers, batch, S, cfg.n_kv_heads), jnp.float32)
            if cfg.kv_bias_correct:
                kv["v_err"] = jnp.zeros(
                    (cfg.n_layers, batch, S, cfg.n_kv_heads), jnp.float32)
        if cfg.family == "hybrid":
            _, H, G, St, _, d_conv = ssm_dims(cfg)
            n_app = cfg.n_layers // cfg.hybrid_attn_every
            return {
                "ssm": jnp.zeros((cfg.n_layers, batch, H, cfg.ssm_head_dim, St), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv_width - 1, d_conv), dtype),
                "k": jnp.zeros((n_app, batch, S, cfg.n_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((n_app, batch, S, cfg.n_kv_heads, cfg.head_dim), dtype),
                "kpos": jnp.full((S,), -1, jnp.int32),
                "pos": jnp.zeros((), jnp.int32),
            }
        return kv

    def _forward_cached(self, params, tokens, cache, *, chunk_kv=None,
                        logits_at=None):
        """Shared prefill/decode path: runs T tokens starting at cache['pos']
        (scalar, or [B] for per-slot caches). ``logits_at`` selects which
        position's logits to return (default: the last — chunked-prefill
        callers pass the final *valid* offset of a padded chunk, either a
        shared scalar or a per-row [B] vector)."""
        cfg = self.cfg
        compute = jnp.dtype(cfg.dtype)
        params = jax.tree.map(
            lambda a: a.astype(compute) if a.dtype == jnp.float32 and compute != jnp.float32 else a,
            params,
        )
        B, T = tokens.shape
        pos = cache["pos"]
        if pos.ndim == 1:
            positions = pos[:, None] + jnp.arange(T)[None, :]   # [B, T]
        else:
            positions = pos + jnp.arange(T)
        x = self._embed(params, tokens)

        if cfg.family == "ssm":
            def body(carry, inp):
                x = carry
                p, st = inp
                x, new_st, _ = self._mamba_layer(p, x, state=st)
                return x, new_st

            states = {"ssm": cache["ssm"], "conv": cache["conv"]}
            x, new_states = scan_layers(body, x, (params["blocks"], states),
                                        cfg.unroll_layers)
            new_cache = {**new_states, "pos": pos + T}
        elif cfg.family == "hybrid":
            every = cfg.hybrid_attn_every
            n_seg = cfg.n_layers // every
            seg_params = jax.tree.map(
                lambda a: a.reshape(n_seg, every, *a.shape[1:]), params["blocks"]
            )
            seg_states = jax.tree.map(
                lambda a: a.reshape(n_seg, every, *a.shape[1:]),
                {"ssm": cache["ssm"], "conv": cache["conv"]},
            )
            new_ssm, new_conv, new_k, new_v = [], [], [], []
            kpos = cache["kpos"]
            for seg in range(n_seg):
                p_seg = jax.tree.map(lambda a: a[seg], seg_params)
                st_seg = jax.tree.map(lambda a: a[seg], seg_states)

                def body(carry, inp):
                    x = carry
                    p, st = inp
                    x, new_st, _ = self._mamba_layer(p, x, state=st)
                    return x, new_st

                x, st_new = scan_layers(body, x, (p_seg, st_seg), cfg.unroll_layers)
                new_ssm.append(st_new["ssm"])
                new_conv.append(st_new["conv"])
                shared = jax.tree.map(
                    lambda a: a[seg % cfg.hybrid_n_shared_blocks],
                    params["shared_blocks"],
                )
                layer_cache = {
                    "k": cache["k"][seg], "v": cache["v"][seg],
                    "kpos": kpos, "pos": pos,
                }
                x, lc, _, _ = self._transformer_block(
                    shared, x, positions=positions, mask=None,
                    cache=layer_cache, chunk_kv=chunk_kv,
                )
                new_k.append(lc["k"])
                new_v.append(lc["v"])
                new_kpos = lc["kpos"]
            new_cache = {
                "ssm": jnp.concatenate(new_ssm),
                "conv": jnp.concatenate(new_conv),
                "k": jnp.stack(new_k),
                "v": jnp.stack(new_v),
                "kpos": new_kpos,
                "pos": pos + T,
            }
        else:
            kv_keys = [k for k in ("k", "v", "k_scale", "v_scale", "v_err")
                       if k in cache]

            def body(carry, inp):
                x = carry
                p, kv = inp
                layer_cache = {**kv, "kpos": cache["kpos"], "pos": pos}
                x, lc, _, _ = self._transformer_block(
                    p, x, positions=positions, mask=None,
                    cache=layer_cache, chunk_kv=chunk_kv,
                )
                return x, {**{k: lc[k] for k in kv_keys}, "kpos": lc["kpos"]}

            x, new_kv = scan_layers(
                body, x, (params["blocks"], {k: cache[k] for k in kv_keys}),
                cfg.unroll_layers,
            )
            new_cache = {
                **{k: new_kv[k] for k in kv_keys},
                "kpos": new_kv["kpos"][0],
                "pos": pos + T,
            }

        x = apply_norm(x, params["final_norm"], cfg.norm)
        if logits_at is None:
            h_last = x[:, -1:, :]
        elif jnp.ndim(logits_at) == 1:
            # per-row offsets [B] (batched multi-slot prefill: each row's
            # final valid position differs when chunks are zero-padded)
            h_last = jnp.take_along_axis(
                x, logits_at.astype(jnp.int32)[:, None, None], axis=1
            )
        else:
            h_last = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        logits = self._unembed(params, h_last)[:, 0]
        return logits, new_cache

    def prefill(self, params, tokens, cache, *, chunk_kv: Optional[int] = None,
                logits_at=None):
        return self._forward_cached(params, tokens, cache, chunk_kv=chunk_kv,
                                    logits_at=logits_at)

    def decode_step(self, params, token, cache):
        """token: [B, 1] int32 → (logits [B, V], cache)."""
        return self._forward_cached(params, token, cache)

    # ------------------------------------------------------------- DFQ plan
    def dfq_plan(self) -> DFQPlan:
        cfg = self.cfg
        ops: list = []
        sites: list = []
        if cfg.family in ("ssm", "hybrid"):
            # Mamba mixers: norm-fold only; CLE pairs are blocked by the
            # grouped RMSNorm before out_proj (DESIGN.md §Arch-applicability).
            ops.append(NormFoldOp(
                norm_w=("blocks", "norm", "w"),
                consumers=[("blocks", "mixer", "in_proj")],
                consumer_biases=[("blocks", "mixer", "in_bias")],
            ))
            sites += [
                WeightSite("ssm_in_proj", ("blocks", "mixer", "in_proj"),
                           ("blocks", "mixer", "in_bias"), "dense", "ssm_in"),
                WeightSite("ssm_out_proj", ("blocks", "mixer", "out_proj"),
                           ("blocks", "mixer", "out_bias"), "dense", "ssm_out_in"),
            ]
        if cfg.family == "ssm":
            return DFQPlan(tuple(ops), tuple(sites), cfg.name)

        prefix = ("shared_blocks",) if cfg.family == "hybrid" else ("blocks",)

        def P(*rest):
            return prefix + rest

        attn_bias = (P("attn", "bq"), P("attn", "bk"), P("attn", "bv")) if cfg.qkv_bias else (None, None, None)
        ops.append(NormFoldOp(
            norm_w=P("attn_norm", "w"),
            norm_b=P("attn_norm", "b") if cfg.norm == "ln" else None,
            consumers=[P("attn", "wq"), P("attn", "wk"), P("attn", "wv")],
            consumer_biases=list(attn_bias),
        ))
        mlp_consumers = [P("mlp", "router")] if cfg.n_experts else []
        mlp_cons_biases: list = [None] if cfg.n_experts else []
        if cfg.n_experts:
            # expert weights [L, E, D, F] fold over D with broadcast γ [L, 1, D]
            pass  # handled by a dedicated fold below (needs reshape) — skip γ
        else:
            if cfg.act.endswith("_glu"):
                mlp_consumers += [P("mlp", "wg"), P("mlp", "wu")]
                mlp_cons_biases += [None, None]
            else:
                mlp_consumers += [P("mlp", "wu")]
                mlp_cons_biases += [None]
        if mlp_consumers and not cfg.n_experts:
            ops.append(NormFoldOp(
                norm_w=P("mlp_norm", "w"),
                norm_b=P("mlp_norm", "b") if cfg.norm == "ln" else None,
                consumers=mlp_consumers,
                consumer_biases=mlp_cons_biases,
            ))

        # exact CLE pairs
        ops.append(VOPairOp(
            wv=P("attn", "wv"), wo=P("attn", "wo"),
            bv=P("attn", "bv") if cfg.qkv_bias else None,
            n_q=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        ))
        if not cfg.qk_norm:
            ops.append(QKPairOp(
                wq=P("attn", "wq"), wk=P("attn", "wk"),
                bq=P("attn", "bq") if cfg.qkv_bias else None,
                bk=P("attn", "bk") if cfg.qkv_bias else None,
                n_q=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope=cfg.rope,
            ))
        if cfg.n_experts:
            ops.append(DensePairOp(
                w1=P("mlp", "experts", "wu"), w2=P("mlp", "experts", "wd"),
                exact=cfg.act.endswith("_glu"),
            ))
            if cfg.n_shared_experts:
                ops.append(DensePairOp(
                    w1=P("mlp", "shared", "wu"), w2=P("mlp", "shared", "wd"),
                    exact=cfg.act.endswith("_glu"),
                ))
        else:
            ops.append(DensePairOp(
                w1=P("mlp", "wu"), w2=P("mlp", "wd"),
                b1=P("mlp", "bu") if cfg.mlp_bias else None,
                exact=cfg.act.endswith("_glu") or cfg.act == "relu",
            ))
        if cfg.qkv_bias:
            ops.append(VBiasAbsorbOp(
                bv=P("attn", "bv"), wo=P("attn", "wo"), bo=P("attn", "bo"),
                n_q=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
            ))

        sites += [
            WeightSite("wq", P("attn", "wq"), P("attn", "bq"), "dense", "attn_in"),
            WeightSite("wk", P("attn", "wk"), P("attn", "bk"), "dense", "attn_in"),
            WeightSite("wv", P("attn", "wv"), P("attn", "bv"), "dense", "attn_in"),
            WeightSite("wo", P("attn", "wo"), P("attn", "bo"), "dense", "o_in"),
        ]
        if cfg.n_experts:
            sites += [
                WeightSite("router", P("mlp", "router"), P("mlp", "router_b"),
                           "dense", "mlp_in"),
                WeightSite("experts_wu", P("mlp", "experts", "wu"), None, "dense", None),
                WeightSite("experts_wd", P("mlp", "experts", "wd"), None, "dense", None),
            ]
            if cfg.act.endswith("_glu"):
                sites.append(WeightSite("experts_wg", P("mlp", "experts", "wg"),
                                        None, "dense", None))
        else:
            sites += [
                WeightSite("wu", P("mlp", "wu"), P("mlp", "bu"), "dense", "mlp_in"),
                WeightSite("wd", P("mlp", "wd"), P("mlp", "bd"), "dense", "down_in"),
            ]
            if cfg.act.endswith("_glu"):
                sites.append(WeightSite("wg", P("mlp", "wg"), P("mlp", "bg"),
                                        "dense", "mlp_in"))
        return DFQPlan(tuple(ops), tuple(sites), cfg.name)

    # -------------------------------------------------- calibration (BC/BA)
    def calibration_stats(self, params, tokens):
        """Synthetic-calibration E[x] per stat_key (data-free — tokens are
        random ids). Returns a flat dict keyed like WeightSite.stat_key with
        [L, ...]-stacked means."""
        _, (_, stats) = self.apply(params, tokens, capture=True)
        return stats
