"""A dense GQA decoder, the block that the architecture files of this
harness share: RMSNorm, grouped-query attention with rotate-half RoPE,
a SiLU-gated MLP, optional q/k/v biases, a tied or untied head.

    x = embed[tokens]
    per layer:  h = rms(x) * w_attn_norm
                q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)     (GQA)
                q, k = rope(q), rope(k)          (rotate-half, theta)
                x = x + softmax(q k^T / sqrt(hd) + causal) v Wo + bo
                h = rms(x) * w_mlp_norm
                x = x + (silu(h Wg) * (h Wu)) Wd + bd
    logits = (rms(x) * w_final_norm) head          (head = embed^T if tied)

The program's ``LMModel`` carries ``bo`` and ``bd`` in every dense block;
the weights make them 0, and the reference adds them all the same.
"""
from __future__ import annotations

import numpy as np


def dims(config: dict) -> dict:
    """The shapes the configuration fixes, under short names."""
    if config.get("hidden_act") != "silu":
        raise ValueError(f"hidden_act {config.get('hidden_act')!r}: only the "
                         "SiLU-gated MLP is described here")
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return {
        "L": config["num_hidden_layers"], "D": d, "Hq": hq,
        "Hkv": config["num_key_value_heads"],
        "hd": config.get("head_dim", d // hq),
        "F": config["intermediate_size"], "V": config["vocab_size"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config["tie_word_embeddings"]),
    }


def program_config(config: dict, ModelConfig, *, qkv_bias: bool):
    """The program's ``ModelConfig`` for this file. The file's RMSNorm
    epsilon goes to the program's ``norm_eps`` where the program has one;
    a program without it normalizes at 1e-6, and serves no other."""
    import dataclasses

    from .layout import LayoutError

    m = dims(config)
    eps = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(ModelConfig)}:
        eps["norm_eps"] = m["eps"]
    elif m["eps"] != 1e-6:
        raise LayoutError(
            f"{config['name']}: rms_norm_eps {m['eps']}, but the program's "
            "ModelConfig has no norm_eps and normalizes at 1e-6")
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["L"], d_model=m["D"],
        n_heads=m["Hq"], n_kv_heads=m["Hkv"], head_dim=m["hd"], d_ff=m["F"],
        vocab_size=m["V"], act="silu_glu", norm="rms", qkv_bias=qkv_bias,
        rope_theta=m["theta"], tie_embeddings=m["tied"],
        max_seq=config["max_position_embeddings"],
        dtype=config["serving"]["compute_dtype"], param_dtype="float32",
        **eps)


def param_shapes(config: dict, *, qkv_bias: bool) -> dict:
    """{leaf path: shape} of the float weights, layers stacked on a leading
    axis, as the program's ``LMModel.init`` lays them out. The order is the
    order in which the weights are drawn from the seed."""
    m = dims(config)
    L, D, Hq, Hkv, hd, F = m["L"], m["D"], m["Hq"], m["Hkv"], m["hd"], m["F"]
    q, kv = Hq * hd, Hkv * hd
    shapes = {
        "embed": (m["V"], D),
        "final_norm/w": (D,),
        "blocks/attn_norm/w": (L, D),
        "blocks/mlp_norm/w": (L, D),
        "blocks/attn/wq": (L, D, q), "blocks/attn/bq": (L, q),
        "blocks/attn/wk": (L, D, kv), "blocks/attn/bk": (L, kv),
        "blocks/attn/wv": (L, D, kv), "blocks/attn/bv": (L, kv),
        "blocks/attn/wo": (L, q, D), "blocks/attn/bo": (L, D),
        "blocks/mlp/wg": (L, D, F), "blocks/mlp/wu": (L, D, F),
        "blocks/mlp/wd": (L, F, D), "blocks/mlp/bd": (L, D),
    }
    if not qkv_bias:
        for b in ("bq", "bk", "bv"):
            del shapes[f"blocks/attn/{b}"]
    if not m["tied"]:
        shapes["lm_head"] = (D, m["V"])
    return shapes


def init_leaf(path: str, z, weights: dict):
    """A leaf's value from ``z``, a standard normal draw of its shape:
    projections N(0, 1/fan_in), embedding N(0, embed_std^2), norm weights
    1 + N(0, norm_std^2), q/k/v biases N(0, qkv_bias_std^2), o and down
    biases 0."""
    import jax.numpy as jnp

    leaf = path.rsplit("/", 1)[-1]
    if path == "embed":
        return z * weights["embed_std"]
    if leaf == "w":                                    # norm weights
        return 1.0 + z * weights["norm_std"]
    if leaf in ("bq", "bk", "bv"):
        return z * weights["qkv_bias_std"]
    if leaf.startswith("b"):                           # o and down biases
        return jnp.zeros(z.shape, jnp.float32)
    return z / np.sqrt(z.shape[-2])                    # [.., fan_in, out]


def layer(x, p, f):
    """One block of the reference; ``f`` holds the reference's operations
    (``reference.Ops``) and the sequence's shapes."""
    import jax

    m, T = f.m, f.T
    a = p["attn"]
    h = f.rms(x, p["attn_norm"]["w"])
    q = f.rope(f.lin(h, a["wq"], a.get("bq")).reshape(T, m["Hq"], m["hd"]))
    k = f.rope(f.lin(h, a["wk"], a.get("bk")).reshape(T, m["Hkv"], m["hd"]))
    v = f.lin(h, a["wv"], a.get("bv")).reshape(T, m["Hkv"], m["hd"])
    x = x + f.lin(f.attend(q, k, v), a["wo"], a["bo"])
    g = p["mlp"]
    h = f.rms(x, p["mlp_norm"]["w"])
    u = jax.nn.silu(f.lin(h, g["wg"])) * f.lin(h, g["wu"])
    return x + f.lin(u, g["wd"], g["bd"])


def head(params):
    """The head's [D, V] weights."""
    w = params.get("lm_head")
    return params["embed"].T if w is None else w
