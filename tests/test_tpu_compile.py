"""Every Pallas op, AOT-compiled for a TPU v5e at qwen2-0.5b widths.

The TPU compiler is installed on CPU hosts too: it compiles for a chip that
is described (a ``v5e:2x2`` topology), not attached, and refuses what Mosaic
would refuse on the chip — block shapes whose tiling does not match XLA's
layout, in-kernel transposes it cannot legalize, kernels over the VMEM
budget. Interpret mode checks none of that. Nothing runs, so these cases
say nothing about results or speed; the interpret-vs-oracle batteries and
``chip_smoke.py`` on the chip cover those.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_decode.ops import fused_decode
from repro.kernels.kv_attention.ops import kv_attention
from repro.kernels.qmatmul_w8a8.ops import qmatmul_w8a8
from repro.kernels.qmatmul_w8a16.ops import qmatmul_w8a16
from repro.kernels.quantize_act.ops import quantize_act

# qwen2-0.5b: d_model 896, d_ff 4864, 14 q / 2 kv heads of 64; 8 serving
# slots over a 2048-position ring; M = 8 (decode) or 256 (prefill) rows
D, F, HQ, HKV, HD, B, S = 896, 4864, 14, 2, 64, 8, 2048
f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs in /tmp
        # a described chip's compile can be written to the persistent cache
        # but never read back here: keep the cache out of these tests
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies

            try:
                yield topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler on this host
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _gemm_w8a16(M, K, N, q8=False):
    args = [((M, K), bf16), ((K, N), i8), ((N,), f32), ((N,), f32)]

    def fn(a, w, s, b):
        return qmatmul_w8a16(a, w, s, b, backend="pallas", quantize_out=q8)

    return fn, args


def _gemm_w8a8(M, K, N, q8=False):
    args = [((M, K), i8), ((K, N), i8), ((M,), f32), ((N,), f32), ((N,), f32)]

    def fn(a, w, sa, sw, b):
        return qmatmul_w8a8(a, w, sa, sw, b, backend="pallas", out_dtype=bf16,
                            quantize_out=q8)

    return fn, args


def _quantize_act(M, K):
    return (lambda x: quantize_act(x, backend="pallas")), [((M, K), bf16)]


def _kv_attention():
    cache = [((B, S, HKV * HD), i8), ((B, S, HKV), f32)]

    def fn(q, kq, ks, vq, vs):
        return kv_attention(q, kq, ks, vq, vs, backend="pallas")

    return fn, [((B, HQ, HD), bf16), *cache, *cache]


def _fused_decode(q8):
    cache = [((B, S, HKV * HD), i8), ((B, S, HKV), f32)]
    new = ((B, 1, HKV, HD), bf16)

    def fn(q, kq, ks, vq, vs, kn, vn, idx, valid):
        return fused_decode(q, kq, ks, vq, vs, kn, vn, idx, valid=valid,
                            out_dtype=bf16, backend="pallas", quantize_out=q8)

    return fn, [((B, HQ, HD), bf16), *cache, *cache, new, new,
                ((B, 1), jnp.int32), ((B, S), jnp.bool_)]


CASES = {
    "w8a16-decode-up": lambda: _gemm_w8a16(8, D, F),
    "w8a16-prefill-down": lambda: _gemm_w8a16(256, F, D),
    "w8a16-q8-decode": lambda: _gemm_w8a16(8, D, D, q8=True),
    "w8a8-decode-up": lambda: _gemm_w8a8(8, D, F),
    "w8a8-prefill-down": lambda: _gemm_w8a8(256, F, D),
    "w8a8-q8-decode": lambda: _gemm_w8a8(8, D, D, q8=True),
    "w8a8-q8-prefill": lambda: _gemm_w8a8(256, D, D, q8=True),
    "quantize_act-decode": lambda: _quantize_act(8, F),
    "quantize_act-prefill": lambda: _quantize_act(256, D),
    "kv_attention": _kv_attention,
    "fused_decode": lambda: _fused_decode(False),
    "fused_decode-q8": lambda: _fused_decode(True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_op_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{case}: no Mosaic kernel in the compiled program")


# ------------------------------------------------ the engine's decode step

@pytest.fixture(scope="module")
def w8a8_engine():
    """A one-layer serve-w8a8-kv8 engine at qwen2-0.5b widths (the scanned
    layer body compiles the same at any depth)."""
    import dataclasses

    import repro
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=1)
    qm = repro.quantize(build_model(cfg), recipe="serve-w8a8-kv8")
    return ServingEngine(qm.model, qm.params, qm.cfg, num_slots=B,
                         max_len=288, prefill_chunk=32)


def _abstract(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding``: one sharding
    for every leaf, or a matching tree of them."""
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)], ids=["1chip", "2x2"])
def test_engine_decode_compiles_for_v5e(mesh_shape, topo, w8a8_engine,
                                        monkeypatch):
    """The fused decode horizon with every kernel on the Pallas tier — on
    one chip, and on a 2x2 (data, model) mesh where each kernel must run
    per shard (GSPMD refuses to partition a Mosaic call)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.models.layers import set_serve_mesh
    from repro.sharding import (
        named_shardings,
        params_pspecs,
        serve_cache_pspecs,
    )

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    eng = w8a8_engine
    _, impl, (params, tokens, cache, remaining), kw = (
        eng.serve_jit_specs()["decode_horizon"])
    if mesh_shape is None:
        mesh = None
        rep = p_sh = c_sh = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(mesh_shape),
                    ("data", "model"))
        heads = {"n_q": eng.cfg.n_heads, "n_kv": eng.cfg.n_kv_heads}
        rep = NamedSharding(mesh, PartitionSpec())
        p_sh = named_shardings(
            params_pspecs(params, mesh, heads, mode="serve"), mesh)
        c_sh = named_shardings(serve_cache_pspecs(cache, mesh), mesh)
    args = (_abstract(params, p_sh), _abstract(tokens, rep),
            _abstract(cache, c_sh), _abstract(remaining, rep))
    prev = set_serve_mesh(mesh)
    try:
        compiled = jax.jit(impl, static_argnames=("k",),
                           donate_argnums=(2,)).lower(*args, **kw).compile()
    finally:
        set_serve_mesh(prev["mesh"], dp=prev["dp"], model=prev["model"])
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------- the names the chip benchmark reads by

@pytest.fixture(scope="module")
def w8a16_engine():
    """``w8a8_engine``'s twin on the serve-w8a16 recipe."""
    import dataclasses

    import repro
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_config("qwen2-0.5b"), n_layers=1)
    qm = repro.quantize(build_model(cfg), recipe="serve-w8a16")
    return ServingEngine(qm.model, qm.params, qm.cfg, num_slots=B,
                         max_len=288, prefill_chunk=32)


@pytest.fixture(scope="module")
def served_text(one_chip, w8a8_engine, w8a16_engine):
    """``served_text(recipe, jit)``: the compiled text of one of the
    engine's served programs for one v5e, every kernel on the Pallas tier,
    compiled once for the module."""
    engines = {"w8a8-kv8": w8a8_engine, "w8a16": w8a16_engine}
    texts = {}

    def get(recipe, jit):
        if (recipe, jit) not in texts:
            fn, _, args, kw = engines[recipe].serve_jit_specs()[jit]
            with pytest.MonkeyPatch.context() as mp:
                mp.setenv("REPRO_KERNEL_BACKEND", "pallas")
                texts[(recipe, jit)] = fn.lower(
                    *_abstract(args, one_chip), **kw).compile().as_text()
        return texts[(recipe, jit)]

    return get


def _ring_copies(text: str, ring: int) -> list:
    """The s8 ``copy`` instructions of a compiled program with a dim equal
    to the ring length: relayouts of the int8 KV pool (or of a layer's
    slice of it) from one layout to another."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= s8\[([0-9,]*)\]\{[^}]*\} copy\(", line)
        if m and str(ring) in m.group(1).split(","):
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("jit", ["decode_horizon", "prefill_multi"])
def test_int8_pool_is_read_in_place(jit, served_text, w8a8_engine):
    """The lane-dense int8 pool ([L, B, S, Hkv·hd]) is in the layout the
    decode kernel's blocks read, and prefill writes and reads its flat rows:
    neither program relayouts a layer's slice of the pool. The
    ``[L, B, S, Hkv, hd]`` pool this replaced cost 4 such copies in each
    program at this one-layer fixture. (From two layers on, the layer
    scan's copies of the whole pool appear besides; they are not pinned
    here.)"""
    copies = _ring_copies(served_text("w8a8-kv8", jit), w8a8_engine.max_len)
    assert copies == [], f"{jit}: s8 relayout copies of the KV ring: {copies}"


# the Pallas kernels each served program calls, by recipe
PROGRAM_KERNELS = {
    ("w8a8-kv8", "decode_horizon"): {"fused_decode", "qmatmul_w8a8",
                                     "quantize_act"},
    ("w8a8-kv8", "prefill_multi"): {"qmatmul_w8a8", "quantize_act"},
    ("w8a16", "decode_horizon"): {"qmatmul_w8a16"},
    ("w8a16", "prefill_multi"): {"qmatmul_w8a16"},
}


@pytest.mark.parametrize("recipe,jit", sorted(PROGRAM_KERNELS))
def test_served_programs_keep_the_names_the_chip_benchmark_reads(
        recipe, jit, served_text):
    """The chip benchmark's trace reduction (``benchmarks/chip/chipbench/
    kernels.py``) finds the decode and prefill programs by their module
    names and each kernel by its op name, ``<kernel>_pallas.<n>``. Compiled
    through the engine's own jits for a v5e, both still read as it expects."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks" / "chip"))
    from chipbench import kernels, trace

    text = served_text(recipe, jit)
    module = text.split(None, 2)[1].rstrip(",")
    assert module == f"jit__{jit}_impl"
    assert kernels.is_decode(module) == (jit == "decode_horizon")
    assert kernels.is_prefill(module) == (jit == "prefill_multi")
    calls = [trace.Event(line.strip().removeprefix("ROOT "), 0, 0)
             for line in text.splitlines() if "tpu_custom_call" in line
             and " = " in line]
    assert calls
    found = {k for k in kernels.RATE
             if any(kernels.matches(e, k) for e in calls)}
    assert found == PROGRAM_KERNELS[(recipe, jit)]
    assert all(trace.op_name(e).split(".")[0].endswith("_pallas")
               for e in calls)
