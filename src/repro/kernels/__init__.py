"""Pallas TPU kernels for the INT8 deployment path DFQ enables.

Five ops (taxonomy B.12 — W8A8 / weight-only / dynamic-quant):

  * ``qmatmul_w8a8``  — int8×int8 → int32 MXU GEMM, dequant epilogue fused
                        with the DFQ bias-correction term (compute-bound
                        prefill path; int8 doubles v5e MXU peak vs bf16).
                        ``quantize_out=True`` re-quantizes the output row in
                        the epilogue (int8 + per-row scale out).
  * ``qmatmul_w8a16`` — bf16 activations × int8 weights dequantized in VMEM
                        (memory-bound decode path; halves HBM weight bytes),
                        same ``quantize_out`` epilogue variant.
  * ``quantize_act``  — fused per-row absmax reduce + scale + round
                        (dynamic activation quantization),
  * ``kv_attention``  — single-token decode attention with the int8 KV cache
                        dequantized in VMEM (one HBM pass over the cache —
                        the EXPERIMENTS §Perf C5 roofline term, fused).
                        Handles GQA (each kv head's q-head group as one
                        2-D matmul) and ragged per-slot lengths through
                        zero-scale masking.
  * ``fused_decode``  — the decode megakernel: append-quantize + int8
                        attention (+ optional W8A8 quantize-out epilogue)
                        in ONE ``pallas_call`` with the cache leaves
                        aliased in place — the ``kv_attention_decode``
                        composition collapsed to a single dispatch.

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (public
wrapper), ref.py (pure-jnp oracle). Kernels VALIDATE in interpret mode on
CPU, compile for a described TPU v5e in tests/test_tpu_compile.py, and run
on the chip in chip_smoke.py.

Backend selection, padding policy, and the op registry all live in
``dispatch.py`` — every op registers its pallas/xla/interpret/ref tiers
with ``@register_impl`` and resolves through ``dispatch.resolve`` (env
override ``REPRO_KERNEL_BACKEND``). ``serving_kernel_specs`` /
``lower_serving_kernels`` enumerate the registry's ``@register_spec``
entries, so the graph linter (analysis/lint) traces every registered
serving op without a hand-maintained list.
"""
from __future__ import annotations

from . import dispatch


def _import_ops():
    """Importing the op packages populates the dispatch registry."""
    from .fused_decode import ops as _fd          # noqa: F401
    from .kv_attention import ops as _kv          # noqa: F401
    from .qmatmul_w8a8 import ops as _w8a8        # noqa: F401
    from .qmatmul_w8a16 import ops as _w8a16      # noqa: F401
    from .quantize_act import ops as _qa          # noqa: F401


def serving_kernel_specs(*, head_dim: int = 16, n_kv_heads: int = 2,
                         n_q_heads: int = 4, seq: int = 32, batch: int = 2,
                         d_in: int = 64, d_out: int = 128) -> dict:
    """{name: (fn, args, kwargs)} for each registered serving op at a
    representative smoke shape — everything the lint layer needs to trace
    (``jax.make_jaxpr``) or lower (``jax.jit(...).lower``) the kernels
    without running them. Shapes default to the smoke-config attention
    geometry so kernel contracts line up with the engine contracts."""
    _import_ops()
    return dispatch.iter_specs(
        head_dim=head_dim, n_kv_heads=n_kv_heads, n_q_heads=n_q_heads,
        seq=seq, batch=batch, d_in=d_in, d_out=d_out)


def lower_serving_kernels(**shape_kw) -> dict:
    """{name: jax.stages.Lowered} for every registered serving op —
    traced and lowered (StableHLO), NOT compiled or run."""
    import jax

    out = {}
    for name, (fn, args, kw) in serving_kernel_specs(**shape_kw).items():
        out[name] = jax.jit(lambda *a, _fn=fn, _kw=kw: _fn(*a, **_kw)
                            ).lower(*args)
    return out
