"""The plain reference of a qwen2 configuration, and its control.

The reference is the published forward pass in float32 ``jax.numpy`` at
``highest`` matmul precision: no kernels, no cache, no batching, no
quantization. It reads the float weights that ``model.make_params`` makes
from the seed, and nothing the program made.

    x = embed[tokens]
    per layer:  h = rms(x) * w_attn_norm
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (GQA)
                q, k = rope(q), rope(k)          (rotate-half, theta)
                x = x + softmax(q k^T / sqrt(hd) + causal) v Wo + bo
                h = rms(x) * w_mlp_norm
                x = x + (silu(h Wg) * (h Wu)) Wd + bd
    logits = (rms(x) * w_final_norm) embed^T                  (tied head)

The control is the same pass with the configuration's stated precisions
stepped one down (``control`` in the configuration file): weights quantized
per output channel, activations per row before every projection, keys and
values per position and head, symmetric, to the stated bits; the head in
the stated float type. It stands in for the program to show that the
comparison fails a lower precision.
"""
from __future__ import annotations

import functools

from . import model as model_lib

BLOCK = 512          # logit rows computed at a time


def _fq(x, bits, axis):
    """Symmetric fake quantization of x to ``bits``, one scale per slice
    along ``axis`` (absmax)."""
    import jax.numpy as jnp

    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _forward(params, tokens, *, m, prec):
    """tokens [T] -> final normed hidden [T, D] (float32)."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    act_bits = prec.get("activations")
    kv_bits = prec.get("kv_cache")

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m["eps"]) * w

    def lin(x, w, b=None):
        if isinstance(act_bits, int):
            x = _fq(x, act_bits, -1)
        y = jnp.dot(x, w, precision=hp)
        return y if b is None else y + b

    T = tokens.shape[0]
    Hq, Hkv, hd = m["Hq"], m["Hkv"], m["hd"]
    half = hd // 2
    freqs = 1.0 / (m["theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                  / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                   # [T, H, hd]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        a = p["attn"]
        h = rms(x, p["attn_norm"]["w"])
        q = rope(lin(h, a["wq"], a["bq"]).reshape(T, Hq, hd))
        k = rope(lin(h, a["wk"], a["bk"]).reshape(T, Hkv, hd))
        v = lin(h, a["wv"], a["bv"]).reshape(T, Hkv, hd)
        if isinstance(kv_bits, int):
            k, v = _fq(k, kv_bits, -1), _fq(v, kv_bits, -1)
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=hp) / jnp.sqrt(
            jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                       precision=hp).reshape(T, Hq * hd)
        x = x + lin(o, a["wo"], a["bo"])
        f = p["mlp"]
        h = rms(x, p["mlp_norm"]["w"])
        g = jax.nn.silu(lin(h, f["wg"])) * lin(h, f["wu"])
        return x + lin(g, f["wd"], f["bd"]), None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rms(x, params["final_norm"]["w"])


def _head(params):
    w = params.get("lm_head")
    return params["embed"].T if w is None else w


def _quantize_weights(params, bits):
    """The projection weights fake-quantized per output channel."""
    import jax

    def q(path, leaf):
        name = path[-1].key
        if len(path) > 1 and name.startswith("w") and leaf.ndim == 3:
            return _fq(leaf, bits, -2)          # [L, in, out]: per column
        return leaf

    return jax.tree_util.tree_map_with_path(q, params)


@functools.lru_cache(maxsize=None)
def _compiled(m_items: tuple, prec_items: tuple, with_control: bool):
    """jit of (params, tokens [T], targets [T]) -> per position:
    reference best logit, reference logit of the target, and (with the
    control) the reference logit of the control's first choice."""
    import jax
    import jax.numpy as jnp

    m, ctl = dict(m_items), dict(prec_items)
    hp = jax.lax.Precision.HIGHEST

    def fn(params, tokens, targets):
        h = _forward(params, tokens, m=m, prec={})
        head = _head(params)
        if with_control:
            cparams = params
            if isinstance(ctl.get("weights"), int):
                cparams = _quantize_weights(params, ctl["weights"])
            hc = _forward(cparams, tokens, m=m, prec=ctl)
            cdt = jnp.dtype(ctl.get("head", "float32"))
            chead = _head(cparams).astype(cdt)
        T = tokens.shape[0]
        best, at_t, at_c = [], [], []
        for lo in range(0, T, BLOCK):
            lg = jnp.dot(h[lo:lo + BLOCK], head, precision=hp)
            best.append(jnp.max(lg, -1))
            at_t.append(jnp.take_along_axis(
                lg, targets[lo:lo + BLOCK, None], -1)[:, 0])
            if with_control:
                lc = jnp.dot(hc[lo:lo + BLOCK].astype(cdt), chead,
                             preferred_element_type=jnp.float32)
                pick = jnp.argmax(lc, -1)
                at_c.append(jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])
        out = (jnp.concatenate(best), jnp.concatenate(at_t))
        return out + ((jnp.concatenate(at_c),) if with_control else ())

    return jax.jit(fn)


def logit_gaps(params, config: dict, seqs: list, pad_to: int,
               with_control: bool = False) -> list:
    """For each (prompt, served) pair: the gaps, at every served position,
    by which the served token's reference logit lies below the reference's
    best (and, with the control, the gap of the control's first choice).
    Sequences are padded to ``pad_to`` so that one program serves all."""
    import numpy as np

    m = model_lib.dims(config)
    fn = _compiled(tuple(sorted(m.items())),
                   tuple(sorted(config.get("control", {}).items())),
                   with_control)
    out = []
    for prompt, served in seqs:
        P, G = len(prompt), len(served)
        seq = np.zeros(pad_to, np.int32)
        seq[:P] = prompt
        seq[P:P + G - 1] = served[:-1]
        tgt = np.zeros(pad_to, np.int32)
        tgt[P - 1:P - 1 + G] = served
        res = [np.asarray(a)[P - 1:P - 1 + G] for a in fn(params, seq, tgt)]
        gaps = {"served": res[0] - res[1]}
        if with_control:
            gaps["control"] = res[0] - res[2]
        out.append(gaps)
    return out
