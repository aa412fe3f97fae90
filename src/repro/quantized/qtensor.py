"""QTensor: int8 weight container that drops into the model unchanged.

Registered as a pytree node, so scan-stacked quantized weights slice per
layer like ordinary arrays, the sharding planner sees q/scale as leaves, and
``models.layers.linear`` dispatches on the type:

    y = x @ W          (jnp.ndarray)
    y = w8a16(x, W)    (QTensor, mode="w8a16": dequant-in-kernel)
    y = w8a8(q(x), W)  (QTensor, mode="w8a8":  dynamic act quant + int8 MXU)

so the SAME transformer code serves fp and INT8 — the paper's "simple API
call" deployment story.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    q: jnp.ndarray                 # int8 payload [..., K, N]
    scale: jnp.ndarray             # [..., N] or [..., 1] fp32 (symmetric)
    mode: str = "w8a16"            # w8a16 | w8a8

    def tree_flatten(self):
        return (self.q, self.scale), (self.mode,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    def astype(self, dtype):  # models cast params wholesale; int8 stays int8
        return self

    def dequant(self, dtype=jnp.float32):
        return self.q.astype(jnp.float32) * self.scale[..., None, :]


def quantize_param(w: jnp.ndarray, *, per_channel: bool = True,
                   mode: str = "w8a16") -> QTensor:
    """Symmetric int8 quantization of a [..., K, N] weight (per-out-channel
    or per-tensor scale). CLE makes symmetric ≈ asymmetric (paper Table 7)."""
    if per_channel:
        amax = jnp.max(jnp.abs(w), axis=-2)            # [..., N]
    else:
        amax = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)[..., 0]
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return QTensor(q, scale.astype(jnp.float32), mode)


def _kernel_mesh(backend: str, rows: int):
    """The armed serve mesh's (mesh, row_spec, model_axis, model_n) when
    this call must run per shard: GSPMD cannot partition a Mosaic custom
    call, so under a serve mesh the Pallas tiers run inside ``shard_map``.
    The XLA tier's dots partition under GSPMD as they are (None)."""
    if backend == "xla":
        return None
    from ..models.layers import serve_mesh_axes

    return serve_mesh_axes(rows)


def quantize_input(x: jnp.ndarray):
    """Dynamic-quantize an activation once for SHARED use across every W8A8
    projection reading it (the qkv trio, the GLU gate/up pair): returns
    (x_q int8 [M, K], x_scale fp32 [M], lead shape). One quantize dispatch
    replaces one-per-consumer — the values are bitwise what each consumer's
    own ``quantize_act`` would have produced, since per-row quantization
    depends only on the row. Under a serve mesh the row is gathered whole
    first: its absmax spans every shard."""
    from ..kernels.dispatch import serving_backend
    from ..kernels.quantize_act.ops import quantize_act

    backend = serving_backend()
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    ax = _kernel_mesh(backend, x2.shape[0])
    fn = functools.partial(quantize_act, backend=backend)
    if ax is not None:
        mesh, rows, _, _ = ax
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(rows, None),),
                           out_specs=(P(rows, None), P(rows)),
                           check_vma=False)
    a_q, a_s = fn(x2)
    return a_q, a_s, lead


def qtensor_matmul(x: jnp.ndarray, w: QTensor, bias: Optional[jnp.ndarray],
                   tp: Optional[str] = None):
    """Route an activation through a quantized weight. x: [..., K].
    ``tp`` ("col" | "row") is the weight's tensor-parallel role under a
    serve mesh (see ``_gemm``)."""
    assert w.q.ndim == 2, "stacked QTensors must be sliced (scan) before use"
    if w.mode == "w8a8":
        a_q, a_s, lead = quantize_input(x)
        return qtensor_matmul_prequant(a_q, a_s, w, bias, lead,
                                       out_dtype=x.dtype, tp=tp)
    lead = x.shape[:-1]
    y = _gemm(x.reshape(-1, x.shape[-1]), None, w, bias, tp, x.dtype)
    return y.reshape(*lead, w.q.shape[-1])


def qtensor_matmul_prequant(a_q: jnp.ndarray, a_s: jnp.ndarray, w: QTensor,
                            bias: Optional[jnp.ndarray], lead: tuple,
                            *, out_dtype=jnp.float32,
                            tp: Optional[str] = None):
    """W8A8 matmul over an already-quantized activation (from
    ``quantize_input`` or a kernel's quantize-out epilogue). a_q [M, K]
    int8, a_s [M] fp32; returns [*lead, N] in ``out_dtype``."""
    assert w.mode == "w8a8", "prequantized inputs feed W8A8 weights"
    y = _gemm(a_q, a_s, w, bias, tp, out_dtype)
    return y.reshape(*lead, w.q.shape[-1])


def _gemm(a, a_s, w: QTensor, bias, tp, out_dtype):
    """The W8A8 (``a_s`` given) or W8A16 GEMM at the serving backend.

    Under a serve mesh on a Pallas tier the kernel runs per shard, by the
    weight's Megatron role as ``sharding.partition`` places it: "col"
    shards N (each shard owns whole output columns — exact), "row" shards K
    (each shard contracts its K slice with unit scales; the fp32 partial
    sums — exact integers for W8A8 — are psum'd and the scale/bias epilogue
    applied once), anything else runs replicated. A role whose dim does not
    divide the model axis falls back to replicated."""
    from ..kernels.dispatch import serving_backend
    from ..kernels.qmatmul_w8a8.ops import qmatmul_w8a8
    from ..kernels.qmatmul_w8a16.ops import qmatmul_w8a16
    from ..sharding.partition import MIN_SHARD_DIM

    backend = serving_backend()
    K, N = w.q.shape
    f32 = jnp.float32
    w_s = jnp.broadcast_to(w.scale.astype(f32), (N,))
    bias = jnp.zeros((N,), f32) if bias is None else bias.astype(f32)
    w8a8 = a_s is not None

    def call(a, a_s, w_q, w_s, bias, dtype):
        if w8a8:
            return qmatmul_w8a8(a, w_q, a_s, w_s, bias, backend=backend,
                                out_dtype=dtype)
        return qmatmul_w8a16(a, w_q, w_s, bias, backend=backend,
                             out_dtype=dtype)

    ax = _kernel_mesh(backend, a.shape[0])
    if ax is None:
        return call(a, a_s, w.q, w_s, bias, out_dtype)
    mesh, rows, mdl, m_n = ax
    dim = {"col": N, "row": K}.get(tp, 0)
    role = tp if dim >= MIN_SHARD_DIM and dim % m_n == 0 else None

    if role == "row":
        def local(a, a_s, w_q, w_s, bias):
            part = call(a, jnp.ones_like(a_s), w_q, jnp.ones_like(w_s),
                        jnp.zeros_like(bias), f32)
            acc = jax.lax.psum(part, mdl)
            out = acc * a_s[:, None] * w_s[None, :] if w8a8 else acc * w_s
            return (out + bias[None, :]).astype(out_dtype)

        specs = (P(rows, mdl), P(rows), P(mdl, None), P(None), P(None))
        out_spec = P(rows, None)
    else:
        def local(a, a_s, w_q, w_s, bias):
            return call(a, a_s, w_q, w_s, bias, out_dtype)

        n_ax = mdl if role == "col" else None
        specs = (P(rows, None), P(rows), P(None, n_ax), P(n_ax), P(n_ax))
        out_spec = P(rows, n_ax)
    if not w8a8:        # W8A16 reads no activation scale: a placeholder
        a_s = jnp.zeros(a.shape[:1], f32)
    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=out_spec, check_vma=False)(
        a, a_s, w.q, w_s, bias)
