#!/usr/bin/env python3
"""Smoke run of the int8 serving path on a TPU — the quickest proof that the
system still starts on the chip. Not a benchmark: the times it prints are
one cold run each.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # 2x2 tensor-parallel serving

One chip: every Pallas op at qwen2-0.5b widths against its pure-jnp oracle
(decode M=8 and prefill M=256), then ``repro.serve`` with the
serve-w8a8-kv8 and serve-w8a16 recipes at the published widths (8 requests
through 8 slots, 256-token prompts, 32 new tokens each), and a check that
the compiled decode program holds the Mosaic kernels (``tpu_custom_call``).
``--chips 4``: serve-w8a8-kv8-tp on a 2x2 (data, model) mesh against the
same requests on one chip, token for token, both compiled with XLA's
excess precision off (see ``main``), and nothing else.

Any failed phase exits non-zero. The last line of stdout, printed only when
every phase passed, is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# qwen2-0.5b (configs/qwen2_0_5b.py) and the serving shapes used here
D, F, HQ, HKV, HD = 896, 4864, 14, 2, 64
SLOTS, RING, PREFILL_M = 8, 2048, 256
PROMPT, GEN = 256, 32


class PhaseError(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseError(what)


def _device_check(chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    _check(dev.platform == "tpu",
           f"no TPU: JAX found {dev.platform!r} devices only")
    _check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    _check(not os.environ.get("REPRO_KERNEL_BACKEND"),
           "REPRO_KERNEL_BACKEND is set: it would move serving off the "
           "Pallas kernels")
    from repro.kernels.fused_decode.ops import fusion_enabled

    _check(fusion_enabled(), "REPRO_FUSED_DECODE is off: decode would skip "
                             "the fused_decode kernel")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    return dev, len(devs)


# ------------------------------------------------------------ kernel parity

def _rel_err(out, ref) -> float:
    """max |out - ref| over max |ref| (fp32)."""
    import numpy as np

    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _exact(name, out, ref) -> None:
    import numpy as np

    diff = int(np.sum(np.asarray(out) != np.asarray(ref)))
    print(f"  {name}: {diff} elements differ (must be 0)")
    _check(diff == 0, f"{name}: {diff} elements differ from the oracle")


def _close(name, out, ref, tol) -> None:
    err = _rel_err(out, ref)
    print(f"  {name}: max|d|/max|ref| = {err!r} (tol {tol:g})")
    _check(err <= tol, f"{name}: relative error {err!r} > {tol:g}")


def _int8_close(name, out, ref) -> None:
    """An int8 payload re-quantized from two fp computations that agree to
    an ulp may round a value sitting on a .5 boundary either way: allow a
    difference of 1 on at most 1 in 10^4 elements, never more than 1."""
    import numpy as np

    d = np.abs(np.asarray(out, np.int32) - np.asarray(ref, np.int32))
    n_off = int(np.sum(d > 0))
    print(f"  {name}: max|d| = {int(d.max())}, {n_off} of {d.size} elements "
          f"differ (tol: 1, on <= 1e-4 of them)")
    _check(d.max() <= 1 and n_off <= d.size * 1e-4,
           f"{name}: int8 payload differs beyond one rounding step")


def kernel_parity(seed: int) -> None:
    """Each op at backend="pallas" against backend="ref" on seeded inputs,
    the oracles run at fp32 matmul precision (the TPU default would round
    their fp32 dots through bf16)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.fused_decode.ops import fused_decode
    from repro.kernels.kv_attention.ops import kv_attention, quantize_kv
    from repro.kernels.qmatmul_w8a8.ops import qmatmul_w8a8
    from repro.kernels.qmatmul_w8a16.ops import qmatmul_w8a16
    from repro.kernels.quantize_act.ops import quantize_act

    key = jax.random.PRNGKey(seed)

    def both(fn, *args, **kw):
        out = fn(*args, backend="pallas", **kw)
        with jax.default_matmul_precision("highest"):
            ref = fn(*args, backend="ref", **kw)
        return out, ref

    print("phase: kernel parity, pallas vs ref (qwen2-0.5b widths)")
    for M in (SLOTS, PREFILL_M):
        for K, N in ((D, F), (F, D)):
            ks = jax.random.split(jax.random.fold_in(key, M * K + N), 5)
            x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
            w_q = jax.random.randint(ks[1], (K, N), -127, 128, jnp.int8)
            w_s = jax.random.uniform(ks[2], (N,), minval=1e-3, maxval=2e-2)
            bias = jax.random.normal(ks[3], (N,))
            shape = f"M={M} K={K} N={N}"
            # fp32 accumulation of exact bf16 x int8 products in another
            # order (and the oracle scales the weights before its dot):
            # ~sqrt(K) ulps of the row magnitude
            out, ref = both(qmatmul_w8a16, x, w_q, w_s, bias,
                            out_dtype=jnp.float32)
            _close(f"qmatmul_w8a16 {shape}", out, ref, 1e-4)
            a_q, a_s = quantize_act(x, backend="ref")
            # unit scales and zero bias leave the int32 accumulator as is
            # (every |sum| < 2^24 converts to fp32 exactly): bit-exact
            ones_m, ones_n = jnp.ones((M,)), jnp.ones((N,))
            out, ref = both(qmatmul_w8a8, a_q, w_q, ones_m, ones_n,
                            jnp.zeros((N,)), out_dtype=jnp.float32)
            _exact(f"qmatmul_w8a8 accumulator {shape}", out, ref)
            # the scale/bias epilogue is the same fp32 expression, but either
            # compiler may contract its multiply-add into one FMA: an ulp
            out, ref = both(qmatmul_w8a8, a_q, w_q, a_s, w_s, bias,
                            out_dtype=jnp.float32)
            _close(f"qmatmul_w8a8 {shape}", out, ref, 1e-6)
            if N == D:          # the q8 epilogue keeps the whole row in VMEM
                (oq, os_), (rq, rs) = both(qmatmul_w8a8, a_q, w_q, a_s, w_s,
                                           bias, quantize_out=True)
                _int8_close(f"qmatmul_w8a8 q8 {shape} int8", oq, rq)
                _close(f"qmatmul_w8a8 q8 {shape} scale", os_, rs, 1e-6)
        x = jax.random.normal(jax.random.fold_in(key, M), (M, F)) * 3.0
        (q, s), (rq, rs) = both(quantize_act, x)
        _int8_close(f"quantize_act M={M} K={F} int8", q, rq)
        _close(f"quantize_act M={M} K={F} scale", s, rs, 1e-6)

    # decode attention over a RING-position int8 cache with ragged per-slot
    # lengths (positions past a slot's length carry scale 0 = masked)
    ks = jax.random.split(jax.random.fold_in(key, RING), 6)
    lengths = jnp.asarray([5, 300, 1000, RING - 1, 64, 512, 1500, 2000])
    q = jax.random.normal(ks[0], (SLOTS, HQ, HD), jnp.bfloat16)
    ck, cks = quantize_kv(jax.random.normal(ks[1], (SLOTS, RING, HKV, HD)))
    cv, cvs = quantize_kv(jax.random.normal(ks[2], (SLOTS, RING, HKV, HD)))
    live = (jnp.arange(RING)[None, :] < lengths[:, None])[..., None]
    cks, cvs = jnp.where(live, cks, 0.0), jnp.where(live, cvs, 0.0)
    # exp and fp32 sums in another order on the VPU: a few ulps
    out, ref = both(kv_attention, q, ck, cks, cv, cvs)
    _close("kv_attention B=8 S=2048", out, ref, 1e-5)

    k_new = jax.random.normal(ks[3], (SLOTS, 1, HKV, HD), jnp.bfloat16)
    v_new = jax.random.normal(ks[4], (SLOTS, 1, HKV, HD), jnp.bfloat16)
    idx = lengths[:, None].astype(jnp.int32)
    valid = jnp.arange(RING)[None, :] <= lengths[:, None]
    for q8 in (False, True):
        name = f"fused_decode{' q8' if q8 else ''} B=8 S=2048"
        (res, upd), (rres, rupd) = both(
            fused_decode, q, ck, cks, cv, cvs, k_new, v_new, idx,
            valid=valid, quantize_out=q8)
        for leaf, a, b in zip(("k", "k_scale", "v", "v_scale"), upd, rupd):
            if leaf.endswith("scale"):
                _close(f"{name} cache {leaf}", a, b, 1e-6)
            else:
                _int8_close(f"{name} cache {leaf}", a, b)
        if q8:
            _close(f"{name} out", res[0], rres[0], 1e-5)
            _int8_close(f"{name} out int8", res[1], rres[1])
            _close(f"{name} out scale", res[2], rres[2], 1e-5)
        else:
            _close(f"{name} out", res, rres, 1e-5)


# ----------------------------------------------------------------- serving

def _serve(recipe: str, mesh=None):
    import repro

    cfg = repro.ServeConfig(
        arch="qwen2-0.5b", recipe=recipe, batch=SLOTS, slots=SLOTS,
        prompt_len=PROMPT, gen_len=GEN, warmup=True, mesh=mesh)
    res = repro.serve(cfg)
    _check(len(res) == SLOTS, f"{recipe}: {len(res)} of {SLOTS} results")
    for r in res.values():
        _check(r.status == "ok", f"{recipe}: rid {r.rid} ended {r.status!r}")
        _check(len(r.tokens) == GEN,
               f"{recipe}: rid {r.rid} got {len(r.tokens)} of {GEN} tokens")
    return res


def _decode_program(engine) -> str:
    """The compiled text of the engine's widest decode program."""
    fn, _, args, kw = engine.serve_jit_specs()["decode_horizon"]
    return fn.lower(*args, **kw).compile().as_text()


def serving(dev) -> None:
    for recipe in ("serve-w8a8-kv8", "serve-w8a16"):
        print(f"phase: serve {recipe}, qwen2-0.5b published widths "
              f"(smoke run, not a benchmark)")
        res = _serve(recipe)
        gen = sum(len(r.tokens) for r in res.values())
        peak = dev.memory_stats().get("peak_bytes_in_use")
        print(f"  {recipe}: {len(res)}/{SLOTS} requests ok, {gen} generated "
              f"tokens; set-up (quantize + compile) {res.setup_seconds:.1f} "
              f"s, serve {res.serve_seconds:.2f} s; process peak device "
              f"memory {peak} bytes")
        n = _decode_program(res.engine).count("tpu_custom_call")
        print(f"  {recipe}: compiled decode program holds {n} "
              f"tpu_custom_call ops")
        _check(n > 0, f"{recipe}: no Mosaic kernel in the decode program")
        del res


def tp_serving(devs) -> None:
    print("phase: serve-w8a8-kv8-tp on a 2x2 mesh vs one chip, token for "
          "token (smoke run, not a benchmark)")
    print(f"  XLA_FLAGS={os.environ.get('XLA_FLAGS')}")
    sharded = _serve("serve-w8a8-kv8-tp", mesh=(2, 2))
    _check(sharded.engine.mesh is not None, "the -tp run built no mesh")
    in_use = [d.memory_stats().get("bytes_in_use") for d in devs[:4]]
    print(f"  bytes_in_use per device (2x2 engine live): {in_use}")
    _check(min(in_use) > 0.2 * max(in_use),
           "device memory is not spread over the 4 chips")
    tp_tokens = {rid: r.tokens for rid, r in sharded.items()}
    n = _decode_program(sharded.engine).count("tpu_custom_call")
    print(f"  2x2 compiled decode program holds {n} tpu_custom_call ops")
    _check(n > 0, "no Mosaic kernel in the 2x2 decode program")
    del sharded
    single = _serve("serve-w8a8-kv8")
    same = sum(tp_tokens[rid] == r.tokens for rid, r in single.items())
    first = min(single)
    print(f"  rid {first}: 2x2 {tp_tokens[first][:8]} one chip "
          f"{single[first].tokens[:8]}")
    print(f"  {same}/{len(single)} requests token-identical")
    _check(same == len(single) and len(tp_tokens) == len(single),
           "2x2 tensor-parallel tokens differ from the one-chip engine")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernel-parity inputs")
    args = ap.parse_args(argv)
    if args.chips == 4:
        # XLA may keep a bf16 intermediate at fp32 where it fuses ("excess
        # precision"), and the 2x2 and one-chip programs fuse differently:
        # with it on, their greedy tokens part within a few steps on random
        # weights (the XLA tier as well as the kernels). The phase checks
        # the partitioning, so both engines round where the program casts.
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"), "--xla_allow_excess_precision=false")))

    import jax

    from repro.launch.serve import use_compile_cache

    cache = use_compile_cache()
    dev, count = _device_check(args.chips)
    print(f"compile cache: {cache}")
    t0 = time.time()
    if args.chips == 4:
        tp_serving(jax.devices())
    else:
        kernel_parity(args.seed)
        serving(dev)
    print(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
