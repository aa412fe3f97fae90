"""A configuration file read as a model: the program's config built from it,
and the weights made from ``--seed``.

The file holds the published ``config.json`` numbers (Hugging Face key
names) and a ``serving`` group. Weights are random, made on the device in
one jitted call, in float32 (what the quantizer takes), in the parameter
layout the program's ``LMModel`` consumes. The reference (``reference.py``)
reads the same arrays; neither takes anything the program has made.
"""
from __future__ import annotations

import functools

import numpy as np

SUPPORTED = ("qwen2",)


def dims(config: dict) -> dict:
    """The shapes the configuration fixes, under short names."""
    if config.get("model_type") not in SUPPORTED:
        raise ValueError(f"model_type {config.get('model_type')!r} is not "
                         f"one of {SUPPORTED}")
    if config.get("hidden_act") != "silu":
        raise ValueError(f"hidden_act {config.get('hidden_act')!r}: only the "
                         "SiLU-gated MLP of qwen2 is described here")
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


def program_config(config: dict, ModelConfig):
    """The program's ``ModelConfig`` for this file (qwen2: GQA, q/k/v
    biases, RMSNorm, rotate-half RoPE, SiLU-gated MLP)."""
    m = dims(config)
    if m["eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6; the file "
                         f"states {m['eps']}")
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["L"], d_model=m["D"],
        n_heads=m["Hq"], n_kv_heads=m["Hkv"], head_dim=m["hd"], d_ff=m["F"],
        vocab_size=m["V"], act="silu_glu", norm="rms", qkv_bias=True,
        rope_theta=m["theta"], tie_embeddings=m["tied"],
        max_seq=config["max_position_embeddings"],
        dtype=config["serving"]["compute_dtype"], param_dtype="float32")


def param_shapes(config: dict) -> dict:
    """{leaf path: shape} of the float weights, layers stacked on a leading
    axis, as the program's ``LMModel.init`` lays them out."""
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
    if not m["tied"]:
        shapes["lm_head"] = (D, m["V"])
    return shapes


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def key_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (seeds may exceed 32
    bits), so that every seed gives its own weights."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


@functools.lru_cache(maxsize=None)
def _maker(spec: tuple):
    import jax
    import jax.numpy as jnp

    init = dict(spec)

    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        flat = {}
        for i, (path, shape) in enumerate(init["shapes"]):
            k = jax.random.fold_in(key, i)
            leaf = path.rsplit("/", 1)[-1]
            z = jax.random.normal(k, shape, jnp.float32)
            if path == "embed":
                v = z * init["embed_std"]
            elif leaf == "w":                          # norm weights
                v = 1.0 + z * init["norm_std"]
            elif leaf in ("bq", "bk", "bv"):
                v = z * init["qkv_bias_std"]
            elif leaf.startswith("b"):                 # o and down biases
                v = jnp.zeros(shape, jnp.float32)
            else:                                      # [.., fan_in, out]
                v = z / np.sqrt(shape[-2])
            flat[path] = v
        return _nest(flat)

    return jax.jit(make)


def make_params(config: dict, seed: int):
    """float32 weights from ``seed``, on the default device, in one call."""
    import jax.numpy as jnp

    w = config["weights"]
    spec = (("shapes", tuple(param_shapes(config).items())),
            ("embed_std", float(w["embed_std"])),
            ("norm_std", float(w["norm_std"])),
            ("qkv_bias_std", float(w["qkv_bias_std"])))
    return _maker(spec)(jnp.asarray(key_words(seed)))
