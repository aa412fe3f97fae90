"""Decode megakernel (fused append-quantize + int8 attention + quantize-out
epilogue): interpret-mode bit parity against the composed oracles, the q8
GEMM epilogue parity, dispatch-count reduction, and the engine-level
fused-vs-unfused token battery (fp32 + w8a16 + w8a8-kv8, contiguous and
paged) behind the REPRO_FUSED_DECODE routing flag."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_decode.ops import fused_decode, fusion_enabled
from repro.kernels.kv_attention.ops import kv_attention_decode, quantize_kv
from repro.kernels.kv_attention.ref import flat_heads
from repro.kernels.quantize_act.ops import quantize_act


def _decode_inputs(B=2, S=64, Hq=4, Hkv=2, hd=16, seed=3):
    """Mid-generation ragged cache state: row i holds lengths[i] live tokens,
    the new token appends at offset lengths[i] (= the ring position)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    # the lane-dense [B, S, Hkv·hd] payload the cache pool holds
    k_q, v_q = flat_heads(k_q), flat_heads(v_q)
    lengths = jnp.asarray([5, S - 7][:B])
    live = jnp.arange(S)[None, :] < lengths[:, None]
    k_s = jnp.where(live[..., None], k_s, 0.0)
    v_s = jnp.where(live[..., None], v_s, 0.0)
    k_new = jax.random.normal(ks[3], (B, 1, Hkv, hd))
    v_new = jax.random.normal(ks[4], (B, 1, Hkv, hd))
    idx = lengths[:, None].astype(jnp.int32)
    valid = jnp.arange(S)[None, :] <= lengths[:, None]   # incl. the new token
    return q, k_q, k_s, v_q, v_s, k_new, v_new, idx, valid


@pytest.mark.parametrize("quantize_out", [False, True])
def test_fused_interpret_bitexact_vs_ref(quantize_out):
    """The TPU lowering's interpret-mode twin == the composed blocked
    oracles, bit for bit — out, epilogue outputs, AND every cache leaf."""
    args = _decode_inputs()
    q, kq, ksc, vq, vsc, kn, vn, idx, valid = args
    res_i = fused_decode(q, kq, ksc, vq, vsc, kn, vn, idx, valid=valid,
                         blk=32, backend="interpret",
                         quantize_out=quantize_out)
    res_r = fused_decode(q, kq, ksc, vq, vsc, kn, vn, idx, valid=valid,
                         blk=32, backend="ref", quantize_out=quantize_out)
    outs_i = res_i[0] if quantize_out else (res_i[0],)
    outs_r = res_r[0] if quantize_out else (res_r[0],)
    for a, b in zip(outs_i, outs_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(res_i[1], res_r[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_xla_is_the_stepwise_composition():
    """The xla tier IS the pre-megakernel serving graph: CPU serving (and
    its lint contracts) are unchanged by construction."""
    q, kq, ksc, vq, vsc, kn, vn, idx, valid = _decode_inputs(seed=9)
    (out, oq, os_), upd = fused_decode(
        q, kq, ksc, vq, vsc, kn, vn, idx, valid=valid, blk=32,
        backend="xla", quantize_out=True)
    out2, upd2 = kv_attention_decode(q, kq, ksc, vq, vsc, kn, vn, idx,
                                     valid=valid, blk=32, backend="xla")
    oq2, os2 = quantize_act(out2.astype(jnp.float32).reshape(out2.shape[0], -1),
                            backend="xla")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(oq), np.asarray(oq2))
    np.testing.assert_array_equal(np.asarray(os_), np.asarray(os2))
    for a, b in zip(upd, upd2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_cache_verr_requires_xla():
    q, kq, ksc, vq, vsc, kn, vn, idx, valid = _decode_inputs()
    verr = jnp.zeros(ksc.shape, jnp.float32)
    with pytest.raises(ValueError, match="XLA composition"):
        fused_decode(q, kq, ksc, vq, vsc, kn, vn, idx, valid=valid,
                     backend="interpret", cache_verr=verr)


def test_fused_decode_is_one_dispatch():
    """The megakernel's reason to exist: append-quantize + attention +
    quantize-out collapse from 2 kernel launches to 1."""
    from repro.kernels.dispatch import count_pallas_calls

    q, kq, ksc, vq, vsc, kn, vn, idx, valid = _decode_inputs()
    fused = count_pallas_calls(
        fused_decode, q, kq, ksc, vq, vsc, kn, vn, idx,
        valid=valid, blk=32, backend="interpret", quantize_out=True)
    def stepwise(*a):
        out, upd = kv_attention_decode(*a, valid=valid, blk=32,
                                       backend="interpret")
        oq, os_ = quantize_act(out.reshape(out.shape[0], -1),
                               backend="interpret")
        return out, oq, os_, upd
    unfused = count_pallas_calls(stepwise, q, kq, ksc, vq, vsc, kn, vn, idx)
    assert fused == 1
    assert unfused == 2


def test_q8_gemm_epilogue_bitexact():
    """quantize_out on the GEMMs: (int8, row scale) out of the epilogue ==
    the GEMM's fp32 accumulator followed by a standalone quantize_act. The
    w8a8 path is int32-exact so every tier matches bit for bit; for w8a16
    the interpret kernel matches its own fp32 output bit for bit, while the
    blocked ref accumulates in K-block order (equal int8 payload, scale to
    fp32 rounding)."""
    from repro.kernels.qmatmul_w8a8.ops import qmatmul_w8a8
    from repro.kernels.qmatmul_w8a16.ops import qmatmul_w8a16

    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    M, K, N = 24, 96, 80
    a_q = jax.random.randint(ks[0], (M, K), -127, 128, dtype=jnp.int8)
    a_s = jax.random.uniform(ks[1], (M,), minval=0.005, maxval=0.05)
    w_q = jax.random.randint(ks[2], (K, N), -127, 128, dtype=jnp.int8)
    w_s = jax.random.uniform(ks[3], (N,), minval=0.005, maxval=0.05)
    bias = jax.random.normal(ks[0], (N,))

    for backend in ("interpret", "ref"):
        y = qmatmul_w8a8(a_q, w_q, a_s, w_s, bias, backend=backend)
        yq, ysc = qmatmul_w8a8(a_q, w_q, a_s, w_s, bias, backend=backend,
                               quantize_out=True)
        rq, rsc = quantize_act(y.astype(jnp.float32), backend=backend)
        np.testing.assert_array_equal(np.asarray(yq), np.asarray(rq))
        np.testing.assert_array_equal(np.asarray(ysc), np.asarray(rsc))

    a = jax.random.normal(ks[1], (8, K))
    for backend in ("interpret", "ref"):
        y = qmatmul_w8a16(a, w_q, w_s, bias, backend=backend,
                          out_dtype=jnp.float32)
        yq, ysc = qmatmul_w8a16(a, w_q, w_s, bias, backend=backend,
                                quantize_out=True)
        rq, rsc = quantize_act(y, backend=backend)
        np.testing.assert_array_equal(np.asarray(yq), np.asarray(rq))
        if backend == "interpret":
            np.testing.assert_array_equal(np.asarray(ysc), np.asarray(rsc))
        else:
            np.testing.assert_allclose(np.asarray(ysc), np.asarray(rsc),
                                       rtol=1e-5)


# ------------------------------------------- engine fused-vs-unfused battery

def test_fusion_flag(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED_DECODE", raising=False)
    assert fusion_enabled()
    monkeypatch.setenv("REPRO_FUSED_DECODE", "0")
    assert not fusion_enabled()


@pytest.fixture(scope="module")
def _setups():
    """{name: (model, params, cfg, kv_bits)} for the three serving modes."""
    import repro
    from repro.configs import get_config
    from repro.models import build_model

    out = {}
    cfg = get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg)
    out["fp32"] = (model, model.init(jax.random.PRNGKey(0)), cfg, None)
    for recipe in ("serve-w8a16", "serve-w8a8-kv8"):
        qm = repro.quantize(build_model(cfg), recipe=recipe)
        out[recipe] = (qm.model, qm.params, qm.cfg,
                       qm.cfg.kv_cache_bits if "kv8" in recipe else None)
    return out


def _serve_tokens(setup, monkeypatch, fused, paged):
    from repro.serving import Request, ServingEngine

    model, params, cfg, kv_bits = setup
    monkeypatch.setenv("REPRO_FUSED_DECODE", "1" if fused else "0")
    rng = np.random.RandomState(5)
    trace = [Request(rid=i,
                     prompt=rng.randint(0, cfg.vocab_size, size=p)
                     .astype(np.int32),
                     max_new_tokens=g)
             for i, (p, g) in enumerate([(5, 6), (12, 3), (9, 8)])]
    kw = dict(num_slots=2, max_len=32, prefill_chunk=8, kv_bits=kv_bits)
    if paged:
        kw.update(page_size=8)
    eng = ServingEngine(model, params, cfg, **kw)
    res = eng.run([dataclasses.replace(r) for r in trace])
    return {r.rid: (res[r.rid].tokens, res[r.rid].admitted_at,
                    res[r.rid].finished_at) for r in trace}


@pytest.mark.parametrize("mode", ["fp32", "serve-w8a16", "serve-w8a8-kv8"])
@pytest.mark.parametrize("paged", [False, True], ids=["contig", "paged"])
def test_engine_fused_matches_unfused(_setups, monkeypatch, mode, paged):
    """The acceptance pin: REPRO_FUSED_DECODE=1 serves bit-identical tokens
    (and admission timeline) to the stepwise =0 path, across fp32 / w8a16 /
    w8a8-kv8, contiguous and paged pools."""
    fused = _serve_tokens(_setups[mode], monkeypatch, fused=True, paged=paged)
    unfused = _serve_tokens(_setups[mode], monkeypatch, fused=False,
                            paged=paged)
    assert fused == unfused
