"""Kernel micro-benchmarks.

Wall-time on this CPU container is NOT a TPU signal, so each kernel reports:
  * us_per_call of the XLA reference path on CPU (sanity/regression number),
  * derived TPU-roofline quantities: bytes moved, ideal v5e time at HBM bw,
    MXU-bound time at int8/bf16 peak, and the VMEM working set implied by
    the BlockSpec tiling (must be ≪ 16 MiB).

Results persist to ``BENCH_kernels.json`` (CI uploads it from the
bench-smoke job) so the kernel-perf trajectory is tracked across PRs:

    PYTHONPATH=src python benchmarks/kernels_bench.py [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import HW_V5E
from repro.kernels.dispatch import count_pallas_calls
from repro.kernels.kv_attention.ref import kv_attention_ref, kv_attention_xla
from repro.kernels.qmatmul_w8a8.ref import qmatmul_w8a8_ref
from repro.kernels.qmatmul_w8a16.ref import qmatmul_w8a16_ref
from repro.kernels.quantize_act.ref import quantize_act_ref

DEFAULT_JSON = pathlib.Path(__file__).resolve().parent / "BENCH_kernels.json"


def _time(fn, *args, iters=5):
    out = fn(*args)                      # one warmup call, result reused
    (out[0] if isinstance(out, tuple) else out).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def kernel_rows(smoke: bool = False):
    """``smoke`` shrinks every timed shape to CI-runner scale (seconds, tens
    of MB) while keeping identical code paths; the derived roofline rows
    always describe the production shapes."""
    rows = []
    # --- W8A8 prefill-shape GEMM: M=4096 tokens, K=N=4096 -----------------
    M, K, N = (512, 512, 512) if smoke else (4096, 4096, 4096)
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    a_q = jax.random.randint(ks[0], (M, K), -127, 128, dtype=jnp.int8)
    w_q = jax.random.randint(ks[1], (K, N), -127, 128, dtype=jnp.int8)
    f = jax.jit(lambda a, w: qmatmul_w8a8_ref(a, w, 0.01, 0.01))
    rows.append((f"w8a8_{M}x{K}x{N}.cpu_us", _time(f, a_q, w_q)))
    flops = 2 * 4096 ** 3
    rows.append(("w8a8.v5e_int8_mxu_bound_us",
                 flops / HW_V5E["peak_flops_int8"] * 1e6))
    rows.append(("w8a8.v5e_bf16_equiv_us",
                 flops / HW_V5E["peak_flops_bf16"] * 1e6))
    vmem = (128 * 512 + 512 * 128) * 1 + 128 * 128 * (4 + 4)
    rows.append(("w8a8.vmem_working_set_kib", vmem / 1024))

    # --- W8A16 decode-shape GEMM: M=8 (batch), big K,N ---------------------
    M, K, N = (8, 1024, 1024) if smoke else (8, 8192, 8192)
    a = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    w_q = jax.random.randint(ks[1], (K, N), -127, 128, dtype=jnp.int8)
    f = jax.jit(lambda a, w: qmatmul_w8a16_ref(a, w, 0.01))
    rows.append((f"w8a16_{M}x{K}x{N}.cpu_us", _time(f, a, w_q)))
    hbm_int8 = 8192 * 8192 * 1
    hbm_bf16 = 8192 * 8192 * 2
    rows.append(("w8a16.v5e_hbm_bound_us_int8_weights",
                 hbm_int8 / HW_V5E["hbm_bw"] * 1e6))
    rows.append(("w8a16.v5e_hbm_bound_us_bf16_weights",
                 hbm_bf16 / HW_V5E["hbm_bw"] * 1e6))
    rows.append(("w8a16.decode_weight_bytes_speedup", hbm_bf16 / hbm_int8))
    vmem = 8 * 1024 * 2 + 1024 * 512 * 1 + 8 * 512 * (4 + 2)
    rows.append(("w8a16.vmem_working_set_kib", vmem / 1024))

    # --- dynamic activation quantize ---------------------------------------
    M, K = (512, 1024) if smoke else (4096, 8192)
    x = jax.random.normal(ks[0], (M, K))
    f = jax.jit(lambda x: quantize_act_ref(x)[0])
    rows.append((f"quantize_act_{M}x{K}.cpu_us", _time(f, x)))
    rows.append(("quantize_act.v5e_hbm_bound_us",
                 (4096 * 8192 * 4 + 4096 * 8192 * 1) / HW_V5E["hbm_bw"] * 1e6))

    # --- int8-KV decode attention (one 32k-context token, 8 kv heads) ------
    B, S, H, hd = (2, 2048, 4, 64) if smoke else (8, 32768, 8, 128)
    kq = jax.random.randint(ks[0], (B, S, H * hd), -127, 128, dtype=jnp.int8)
    ksc = jax.random.uniform(ks[1], (B, S, H), minval=0.01, maxval=0.05)
    qv = jax.random.normal(ks[0], (B, H, hd))
    f = jax.jit(lambda q, kq, ksc: kv_attention_ref(q, kq, ksc, kq, ksc))
    rows.append((f"kv_attention_{B}x{S // 1024}k.cpu_us",
                 _time(f, qv, kq, ksc)))
    # the serving XLA path (scale folding at score granularity) with GQA:
    # 32 q heads read the same 8 kv heads without repeat-materialization
    qg = jax.random.normal(ks[0], (B, 4 * H, hd))
    f = jax.jit(lambda q, kq, ksc: kv_attention_xla(q, kq, ksc, kq, ksc))
    rows.append((f"kv_attention_gqa4_{B}x{S // 1024}k_xla.cpu_us",
                 _time(f, qg, kq, ksc)))
    B, S, H, hd = 8, 32768, 8, 128           # roofline: production shape
    cache_int8 = 2 * B * S * H * (hd * 1 + 4)
    cache_bf16 = 2 * B * S * H * hd * 2
    cache_fp32 = 2 * B * S * H * hd * 4
    rows.append(("kv_attention.v5e_cache_stream_us_int8",
                 cache_int8 / HW_V5E["hbm_bw"] * 1e6))
    rows.append(("kv_attention.v5e_cache_stream_us_bf16",
                 cache_bf16 / HW_V5E["hbm_bw"] * 1e6))
    rows.append(("kv_attention.cache_bytes_speedup_vs_bf16",
                 cache_bf16 / cache_int8))
    rows.append(("kv_attention.cache_bytes_speedup_vs_fp32",
                 cache_fp32 / cache_int8))
    vmem = 2 * 512 * H * hd * 1 + 2 * 512 * H * 4 + H * hd * 4
    rows.append(("kv_attention.vmem_working_set_kib", vmem / 1024))

    # --- fused decode megakernel: append-quantize + attention + q8-out -----
    # dispatch counts come from the traced jaxprs of the Pallas tier (exact
    # on CPU); wall time regresses the XLA composition the CPU path serves
    from repro.kernels.fused_decode.ops import fused_decode
    from repro.kernels.kv_attention.ops import kv_attention_decode, quantize_kv
    from repro.kernels.kv_attention.ref import flat_heads
    from repro.kernels.quantize_act.ops import quantize_act

    B, S, Hq, Hkv, hd = ((2, 512, 4, 2, 64) if smoke
                         else (8, 4096, 32, 8, 128))
    kk = jax.random.split(jax.random.PRNGKey(1), 4)
    qv = jax.random.normal(kk[0], (B, Hq, hd))
    kq, ksc = quantize_kv(jax.random.normal(kk[1], (B, S, Hkv, hd)))
    vq, vsc = quantize_kv(jax.random.normal(kk[2], (B, S, Hkv, hd)))
    kq, vq = flat_heads(kq), flat_heads(vq)              # lane-dense pool
    k_new = jax.random.normal(kk[3], (B, 1, Hkv, hd))
    v_new = jax.random.normal(kk[0], (B, 1, Hkv, hd))
    idx = jnp.full((B, 1), S // 2, jnp.int32)
    valid = jnp.arange(S)[None, :] <= (S // 2)
    valid = jnp.broadcast_to(valid, (B, S))
    fused_n = count_pallas_calls(
        fused_decode, qv, kq, ksc, vq, vsc, k_new, v_new, idx,
        valid=valid, blk=min(512, S), backend="interpret", quantize_out=True)

    def stepwise(q, kq, ksc, vq, vsc, kn, vn, idx):
        out, upd = kv_attention_decode(q, kq, ksc, vq, vsc, kn, vn, idx,
                                       valid=valid, blk=min(512, S),
                                       backend="interpret")
        oq, os_ = quantize_act(out.reshape(out.shape[0], -1),
                               backend="interpret")
        return out, oq, os_, upd

    unfused_n = count_pallas_calls(stepwise, qv, kq, ksc, vq, vsc,
                                   k_new, v_new, idx)
    rows.append(("fused_decode.dispatches_per_step_fused", fused_n))
    rows.append(("fused_decode.dispatches_per_step_unfused", unfused_n))
    rows.append(("fused_decode.decode_dispatch_reduction",
                 unfused_n / fused_n))
    # q8 GEMM epilogue: the standalone quantize_act between a W8A8 GEMM and
    # its consumer folds into the GEMM's own launch
    rows.append(("qmatmul_q8_epilogue.dispatch_reduction", 2.0 / 1.0))
    f = jax.jit(lambda *a: fused_decode(*a, valid=valid, blk=min(512, S),
                                        backend="xla",
                                        quantize_out=True)[0][0])
    rows.append((f"fused_decode_{B}x{S}.xla_cpu_us",
                 _time(f, qv, kq, ksc, vq, vsc, k_new, v_new, idx)))
    return rows


def write_bench_json(path, rows, smoke: bool = False) -> None:
    payload = {
        "benchmark": "kernels",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "smoke": smoke,
        "rows": {name: float(value) for name, value in rows},
    }
    p = pathlib.Path(path)
    p.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    print(f"wrote {p}")


def kernel_rows_persisted(json_path=None, smoke: bool = False):
    """benchmarks.run adapter: compute the rows AND persist them."""
    rows = kernel_rows(smoke=smoke)
    write_bench_json(json_path or DEFAULT_JSON, rows, smoke=smoke)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=str(DEFAULT_JSON), metavar="PATH",
                    help="where to persist machine-readable results")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny timed shapes for the CI smoke-benchmark job")
    args = ap.parse_args(argv)
    for name, value in kernel_rows_persisted(args.json, smoke=args.smoke):
        print(f"{name},{value}")


if __name__ == "__main__":
    main()
