"""The plain reference of a configuration, and its control.

The reference is the published forward pass in float32 ``jax.numpy`` at
``highest`` matmul precision: no kernels, no cache, no batching, no
quantization. Its block is the architecture file's ``layer`` and its head
the file's ``head`` (``model.py``); this module gives them the operations
(``Ops``): RMSNorm, projections, rotate-half RoPE and causal GQA attention.

    x = embed[tokens]
    x = layer(x, block) for every block
    logits = (rms(x) * w_final_norm) head

It reads the float weights that ``model.py`` makes from the seed, and
nothing the program made: all at once, or, for a configuration made
layer by layer, one layer at a time, every sampled sequence through a
layer before the next layer is made.

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


class Ops:
    """The reference's operations over one sequence of ``T`` positions, at
    the precisions ``prec`` (empty for the reference, ``control`` for the
    control)."""

    def __init__(self, m: dict, prec: dict, T: int):
        import jax
        import jax.numpy as jnp

        self.m, self.T = m, T
        self.hp = jax.lax.Precision.HIGHEST
        self.act_bits = prec.get("activations")
        self.kv_bits = prec.get("kv_cache")
        half = m["hd"] // 2
        freqs = 1.0 / (m["theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                      / half))
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
        self.cos, self.sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        self.causal = jnp.tril(jnp.ones((T, T), bool))

    def rms(self, x, w):
        import jax
        import jax.numpy as jnp

        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + self.m["eps"]) * w

    def lin(self, x, w, b=None):
        import jax.numpy as jnp

        if isinstance(self.act_bits, int):
            x = _fq(x, self.act_bits, -1)
        y = jnp.dot(x, w, precision=self.hp)
        return y if b is None else y + b

    def rope(self, x):                              # [T, H, hd]
        import jax.numpy as jnp

        half = self.m["hd"] // 2
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * self.cos - x2 * self.sin,
                                x2 * self.cos + x1 * self.sin], -1)

    def attend(self, q, k, v):
        """Causal GQA attention of q [T, Hq, hd] over k, v [T, Hkv, hd]
        (fake-quantized per position and head at ``kv_cache`` bits), as
        [T, Hq * hd]."""
        import jax
        import jax.numpy as jnp

        m, T = self.m, self.T
        Hq, Hkv, hd = m["Hq"], m["Hkv"], m["hd"]
        if isinstance(self.kv_bits, int):
            k, v = _fq(k, self.kv_bits, -1), _fq(v, self.kv_bits, -1)
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=self.hp) / jnp.sqrt(
            jnp.float32(hd))
        s = jnp.where(self.causal[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                          precision=self.hp).reshape(T, Hq * hd)


def _layers(arch, f: Ops, x, blocks):
    import jax

    def body(x, p):
        return arch.layer(x, p, f), None

    x, _ = jax.lax.scan(body, x, blocks)
    return x


def _forward(arch, params, tokens, *, m, prec):
    """tokens [T] -> final normed hidden [T, D] (float32)."""
    f = Ops(m, prec, tokens.shape[0])
    x = _layers(arch, f, params["embed"][tokens], params["blocks"])
    return f.rms(x, params["final_norm"]["w"])


def _quantize_weights(params, bits):
    """The projection weights fake-quantized per output channel."""
    import jax

    def q(path, leaf):
        name = path[-1].key
        if len(path) > 1 and name.startswith("w") and leaf.ndim == 3:
            return _fq(leaf, bits, -2)          # [L, in, out]: per column
        return leaf

    return jax.tree_util.tree_map_with_path(q, params)


def _logits(arch, params, cparams, h, hc, targets, ctl):
    """Per position: the reference's best logit, the reference logit of the
    target, and (with the control's final hiddens ``hc``) the reference
    logit of the control's first choice."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    head = arch.head(params)
    if hc is not None:
        cdt = jnp.dtype(ctl.get("head", "float32"))
        chead = arch.head(cparams).astype(cdt)
    T = h.shape[0]
    best, at_t, at_c = [], [], []
    for lo in range(0, T, BLOCK):
        lg = jnp.dot(h[lo:lo + BLOCK], head, precision=hp)
        best.append(jnp.max(lg, -1))
        at_t.append(jnp.take_along_axis(
            lg, targets[lo:lo + BLOCK, None], -1)[:, 0])
        if hc is not None:
            lc = jnp.dot(hc[lo:lo + BLOCK].astype(cdt), chead,
                         preferred_element_type=jnp.float32)
            pick = jnp.argmax(lc, -1)
            at_c.append(jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])
    out = (jnp.concatenate(best), jnp.concatenate(at_t))
    return out + ((jnp.concatenate(at_c),) if hc is not None else ())


def _control_params(params, ctl):
    if isinstance(ctl.get("weights"), int):
        return _quantize_weights(params, ctl["weights"])
    return params


@functools.lru_cache(maxsize=None)
def _compiled(arch, m_items: tuple, prec_items: tuple, with_control: bool):
    """jit of (params, tokens [T], targets [T]) -> ``_logits``'s rows, over
    the whole model at once."""
    import jax

    m, ctl = dict(m_items), dict(prec_items)

    def fn(params, tokens, targets):
        h = _forward(arch, params, tokens, m=m, prec={})
        cparams, hc = params, None
        if with_control:
            cparams = _control_params(params, ctl)
            hc = _forward(arch, cparams, tokens, m=m, prec=ctl)
        return _logits(arch, params, cparams, h, hc, targets, ctl)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _staged(arch, m_items: tuple, prec_items: tuple, T: int):
    """The jits of a configuration made layer by layer: the embedding, a
    layer (the reference's and the control's), and the head."""
    import jax

    m, ctl = dict(m_items), dict(prec_items)

    def layers(blocks, x, control):
        f = Ops(m, ctl if control else {}, T)
        if control:
            blocks = _control_params(blocks, ctl)
        return _layers(arch, f, x, blocks)

    def head(outer, x, xc, targets):
        f = Ops(m, {}, T)
        h = f.rms(x, outer["final_norm"]["w"])
        hc = None if xc is None else f.rms(xc, outer["final_norm"]["w"])
        return _logits(arch, outer, _control_params(outer, ctl), h, hc,
                       targets, ctl)

    return (jax.jit(lambda outer, tokens: outer["embed"][tokens]),
            jax.jit(layers, static_argnames="control"), jax.jit(head))


def _padded(seqs: list, pad_to: int):
    """(prompt + served[:-1], served at the positions that predict it)
    padded to ``pad_to``, and the served span of each."""
    import numpy as np

    out = []
    for prompt, served in seqs:
        P, G = len(prompt), len(served)
        seq = np.zeros(pad_to, np.int32)
        seq[:P] = prompt
        seq[P:P + G - 1] = served[:-1]
        tgt = np.zeros(pad_to, np.int32)
        tgt[P - 1:P - 1 + G] = served
        out.append((seq, tgt, slice(P - 1, P - 1 + G)))
    return out


def _as_gaps(res, span, with_control) -> dict:
    import numpy as np

    res = [np.asarray(a)[span] for a in res]
    gaps = {"served": res[0] - res[1]}
    if with_control:
        gaps["control"] = res[0] - res[2]
    return gaps


def logit_gaps(params, config: dict, seqs: list, pad_to: int,
               with_control: bool = False, arch=None) -> list:
    """For each (prompt, served) pair: the gaps, at every served position,
    by which the served token's reference logit lies below the reference's
    best (and, with the control, the gap of the control's first choice).
    Sequences are padded to ``pad_to`` so that one program serves all."""
    arch = model_lib.arch_of(config, arch)
    m = model_lib.dims(config, arch)
    fn = _compiled(arch, tuple(sorted(m.items())),
                   tuple(sorted(config.get("control", {}).items())),
                   with_control)
    return [_as_gaps(fn(params, seq, tgt), span, with_control)
            for seq, tgt, span in _padded(seqs, pad_to)]


def seeded_logit_gaps(config: dict, seed: int, seqs: list, pad_to: int,
                      with_control: bool = False, arch=None) -> list:
    """``logit_gaps`` over the weights that ``seed`` makes: made whole, or,
    for a configuration made layer by layer, one layer at a time."""
    arch = model_lib.arch_of(config, arch)
    if not model_lib.by_layer(config):
        return logit_gaps(model_lib.make_params(config, seed, arch), config,
                          seqs, pad_to, with_control, arch)
    m = model_lib.dims(config, arch)
    embed, layers, head = _staged(
        arch, tuple(sorted(m.items())),
        tuple(sorted(config.get("control", {}).items())), pad_to)
    outer = model_lib.make_outer(config, seed, arch)
    rows = _padded(seqs, pad_to)
    xs = [embed(outer, seq) for seq, _, _ in rows]
    xcs = list(xs) if with_control else [None] * len(xs)
    for i in range(m["L"]):
        blocks = model_lib.make_layers(config, seed, i, 1, arch)
        xs = [layers(blocks, x, control=False) for x in xs]
        if with_control:
            xcs = [layers(blocks, x, control=True) for x in xcs]
        del blocks
    return [_as_gaps(head(outer, x, xc, tgt), span, with_control)
            for x, xc, (_, tgt, span) in zip(xs, xcs, rows)]
