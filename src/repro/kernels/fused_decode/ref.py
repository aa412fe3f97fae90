"""Oracle for the fused decode megakernel: the stepwise composition.

The fused kernel's contract is that fusing changes NOTHING numerically —
so its oracle is literally the three-step path it replaces (append-quantize
→ zero-scale masking → blocked-oracle attention → quantize_act), each step
already bit-pinned by its own package. The interpret-mode megakernel must
match this composition bit for bit.
"""
from __future__ import annotations

import jax.numpy as jnp


def fused_decode_ref(q, cache_k, cache_ks, cache_v, cache_vs, k_new, v_new,
                    idx, *, valid=None, out_dtype=jnp.float32, blk=None,
                    quantize_out=False):
    from ..kv_attention.ops import kv_attention_decode
    from ..quantize_act.ref import quantize_act_ref

    out, updated = kv_attention_decode(
        q, cache_k, cache_ks, cache_v, cache_vs, k_new, v_new, idx,
        valid=valid, out_dtype=out_dtype, backend="ref", blk=blk)
    if quantize_out:
        B = out.shape[0]
        oq, os = quantize_act_ref(out.astype(jnp.float32).reshape(B, -1))
        return (out, oq, os), updated
    return out, updated
