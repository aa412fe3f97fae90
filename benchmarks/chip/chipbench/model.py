"""A configuration file read as a model: the program's config built from it,
and the weights made from ``--seed``.

The file holds the published ``config.json`` numbers (Hugging Face key
names), a ``weights`` group and a ``serving`` group. Its ``model_type``
names the architecture file ``chipbench/archs/<model_type>.py``, which
gives:

    dims(config)                    the shapes, under short names (L, D, Hq,
                                    Hkv, hd, F, V, theta, eps, tied, ...)
    program_config(config, ModelConfig)   the program's config
    param_shapes(config)            {leaf path: shape}, layers stacked on a
                                    leading axis, in the order of the draws
    init_leaf(path, z, weights)     a leaf's value from a standard normal z
    layer(x, p, ops)                one block of the plain reference
    head(params)                    the reference's [D, V] head

and, where the harness's dense-GQA defaults do not hold,
``decode_step_calls`` and ``model_flops`` (``flops.py``) and
``pool_bytes_per_position`` (``run.py``).

Weights are random, made on the device in float32 (what the quantizer
takes), in the layout the program's ``LMModel`` consumes. Without
``weights.by_layer`` they are made in one jitted call, each leaf from the
key ``fold_in(key, leaf)``. With ``"by_layer": true``, each layer of a
stacked leaf is drawn from its own key, ``fold_in(fold_in(key, leaf),
layer)``, so that set-up and the reference can make one layer at a time
and never hold every float32 layer at once. The reference
(``reference.py``) reads the same arrays; neither takes anything the
program has made.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from . import layout


def arch_of(config: dict, arch=None):
    return arch if arch is not None else layout.load_arch(
        config.get("model_type"))


def dims(config: dict, arch=None) -> dict:
    """The shapes the configuration fixes, under short names."""
    return arch_of(config, arch).dims(config)


def program_config(config: dict, ModelConfig, arch=None):
    return arch_of(config, arch).program_config(config, ModelConfig)


def param_shapes(config: dict, arch=None) -> dict:
    return arch_of(config, arch).param_shapes(config)


def by_layer(config: dict) -> bool:
    """Whether set-up and the reference make the layers one at a time."""
    return bool(config.get("weights", {}).get("by_layer"))


def nest(flat: dict) -> dict:
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


def _spec(config: dict, arch) -> tuple:
    w = config["weights"]
    return (tuple(param_shapes(config, arch).items()),
            tuple(sorted((k, float(v)) for k, v in w.items()
                         if isinstance(v, (int, float)))))


def _jit(make, shardings):
    import jax

    if shardings is None:
        return jax.jit(make)
    return jax.jit(make, out_shardings=nest(dict(shardings)))


def layered(path: str) -> bool:
    return path.startswith("blocks/")


@functools.lru_cache(maxsize=None)
def _whole(arch, spec: tuple, shardings: Optional[tuple], outer: bool):
    """jit of words -> every leaf, each from ``fold_in(key, leaf)``; with
    ``outer``, only the leaves outside the layers."""
    import jax
    import jax.numpy as jnp

    shapes, w = spec[0], dict(spec[1])

    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        flat = {}
        for i, (path, shape) in enumerate(shapes):
            if not (outer and layered(path)):
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                flat[path] = arch.init_leaf(path, z, w)
        return nest(flat)

    return _jit(make, shardings)


@functools.lru_cache(maxsize=None)
def _layers(arch, spec: tuple, n: int, shardings: Optional[tuple]):
    import jax
    import jax.numpy as jnp

    shapes, w = spec[0], dict(spec[1])

    def make(words, lo):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        flat = {}
        for i, (path, shape) in enumerate(shapes):
            if layered(path):
                k = jax.random.fold_in(key, i)
                z = jax.vmap(lambda j: jax.random.normal(
                    jax.random.fold_in(k, j), shape[1:], jnp.float32))(
                        lo + jnp.arange(n, dtype=jnp.uint32))
                flat[path] = arch.init_leaf(path, z, w)
        return nest(flat)["blocks"]

    return _jit(make, shardings)


def _frozen(shardings: Optional[dict]) -> Optional[tuple]:
    return None if shardings is None else tuple(sorted(shardings.items()))


def split_shardings(shardings: Optional[dict]) -> tuple:
    """{leaf path: sharding} as (``make_outer``'s, ``make_layers``'s), each
    None where it places nothing."""
    shardings = shardings or {}
    outer = {p: s for p, s in shardings.items() if not layered(p)}
    inner = {p[len("blocks/"):]: s for p, s in shardings.items()
             if layered(p)}
    return outer or None, inner or None


def make_outer(config: dict, seed: int, arch=None, shardings=None):
    """The leaves outside the layers (embedding, final norm, head) of a
    configuration made layer by layer; ``shardings`` maps their paths to
    where each is made."""
    import jax.numpy as jnp

    arch = arch_of(config, arch)
    fn = _whole(arch, _spec(config, arch), _frozen(shardings), True)
    return fn(jnp.asarray(key_words(seed)))


def make_layers(config: dict, seed: int, lo: int, n: int, arch=None,
                shardings=None):
    """Layers lo .. lo + n - 1 of a configuration made layer by layer,
    stacked as ``params["blocks"]`` is; ``shardings`` maps their paths
    (without ``blocks/``) to where each is made."""
    import jax.numpy as jnp

    arch = arch_of(config, arch)
    fn = _layers(arch, _spec(config, arch), int(n), _frozen(shardings))
    return fn(jnp.asarray(key_words(seed)), jnp.uint32(lo))


def make_params(config: dict, seed: int, arch=None, shardings=None):
    """float32 weights from ``seed``, on the default device (or where
    ``shardings`` maps each leaf path). With ``weights.by_layer`` every
    layer is drawn from its own key, as set-up and the reference draw it
    one at a time, but all are made here at once."""
    import jax.numpy as jnp

    arch = arch_of(config, arch)
    if not by_layer(config):
        fn = _whole(arch, _spec(config, arch), _frozen(shardings), False)
        return fn(jnp.asarray(key_words(seed)))
    outer, inner = split_shardings(shardings)
    params = make_outer(config, seed, arch, outer)
    params["blocks"] = make_layers(config, seed, 0, dims(config, arch)["L"],
                                   arch, inner)
    return params
