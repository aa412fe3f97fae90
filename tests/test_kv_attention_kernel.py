"""int8-KV decode-attention kernel: bit-exact interpret-vs-ref property
sweeps (ragged lengths, GQA, non-multiple-of-blk S), accuracy vs an fp
cache, and the fused append-quantize decode op."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import example, given, settings, st
from repro.kernels.kv_attention.ops import (
    append_quantize,
    kv_attention,
    kv_attention_decode,
    quantize_kv,
)
from repro.kernels.kv_attention.ref import kv_attention_ref, kv_attention_xla


def _inputs(B, S, Hkv, hd, seed=0, Hq=None, lengths=None):
    """Random fp K/V quantized per-token/per-head; positions at or past each
    row's ragged ``length`` get scale 0 (= masked, the op contract)."""
    Hq = Hq or Hkv
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    if lengths is not None:
        valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
        k_s = jnp.where(valid[..., None], k_s, 0.0)
        v_s = jnp.where(valid[..., None], v_s, 0.0)
    return q, k, v, k_q, k_s, v_q, v_s


@pytest.mark.parametrize("B,S,H,hd", [
    (2, 256, 4, 64),
    (1, 1024, 8, 128),
    (4, 512, 2, 32),
])
def test_kernel_matches_ref(B, S, H, hd):
    q, k, v, k_q, k_s, v_q, v_s = _inputs(B, S, H, hd, seed=B + S)
    ref = kv_attention_ref(q, k_q, k_s, v_q, v_s, blk=min(256, S))
    out = kv_attention(q, k_q, k_s, v_q, v_s, blk=min(256, S),
                       backend="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_block_size_invariance():
    q, k, v, k_q, k_s, v_q, v_s = _inputs(2, 512, 4, 64, seed=7)
    outs = [np.asarray(kv_attention(q, k_q, k_s, v_q, v_s, blk=blk,
                                    backend="interpret"))
            for blk in (128, 256, 512)]
    for out in outs[1:]:
        np.testing.assert_allclose(out, outs[0], rtol=2e-5, atol=2e-5)


def _fp_oracle(q, k, v, lengths=None):
    """Plain masked softmax over the UNquantized cache — the accuracy
    anchor (GQA by explicit repeat)."""
    B, S, Hkv, hd = k.shape
    group = q.shape[1] // Hkv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q, k) / (hd ** 0.5)
    if lengths is not None:
        valid = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhs,bshd->bhd", p, v)


def test_int8_noise_vs_fp_cache():
    """Quantized cache attention ≈ fp attention within int8 noise."""
    q, k, v, k_q, k_s, v_q, v_s = _inputs(2, 512, 4, 64, seed=9)
    fp = _fp_oracle(q, k, v)
    out = kv_attention(q, k_q, k_s, v_q, v_s, backend="interpret", blk=256)
    rel = float(jnp.linalg.norm(out - fp) / jnp.linalg.norm(fp))
    assert rel < 0.02


def test_gqa_matches_fp_oracle():
    """4 q heads over 1 kv head: the in-kernel reshape must agree with the
    explicit repeat-kv oracle (and the xla serving path with both)."""
    q, k, v, k_q, k_s, v_q, v_s = _inputs(2, 128, 1, 32, seed=11, Hq=4,
                                          lengths=[128, 40])
    fp = _fp_oracle(q, k, v, lengths=[128, 40])
    out = kv_attention(q, k_q, k_s, v_q, v_s, backend="interpret", blk=64)
    xla = kv_attention(q, k_q, k_s, v_q, v_s, backend="xla")
    rel = float(jnp.linalg.norm(out - fp) / jnp.linalg.norm(fp))
    assert rel < 0.02
    np.testing.assert_allclose(np.asarray(xla), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_non_divisible_seq_padded():
    """S % blk != 0 no longer raises: the op pads with zero-scale (masked)
    positions and stays bit-exact with the ref."""
    q, k, v, k_q, k_s, v_q, v_s = _inputs(1, 300, 2, 32, seed=3)
    ref = kv_attention_ref(q, k_q, k_s, v_q, v_s, blk=256)
    out = kv_attention(q, k_q, k_s, v_q, v_s, blk=256, backend="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    fp = _fp_oracle(q, k, v)
    rel = float(jnp.linalg.norm(out - fp) / jnp.linalg.norm(fp))
    assert rel < 0.02


@settings(max_examples=12, deadline=None)
@given(
    B=st.integers(1, 3),
    S=st.integers(1, 96),
    Hkv=st.sampled_from([1, 2]),
    group=st.sampled_from([1, 2, 4]),
    blk=st.sampled_from([16, 32, 64]),
    seed=st.integers(0, 2 ** 16),
    ragged=st.booleans(),
)
# one-row GQA groups: unpadded, their dot is a CPU matrix-vector product
# whose summation order depends on the surrounding program (see
# kernel.head_major)
@example(B=1, S=33, Hkv=1, group=1, blk=32, seed=0, ragged=False)
@example(B=2, S=33, Hkv=1, group=1, blk=32, seed=1, ragged=True)
def test_property_interpret_bitexact_vs_ref(B, S, Hkv, group, blk, seed,
                                            ragged):
    """The acceptance pin: interpret backend == blocked ref BIT-exactly over
    ragged per-slot lengths, GQA ratios, and non-multiple-of-blk S
    (including rows with length 0 — fully masked)."""
    rng = np.random.RandomState(seed)
    lengths = rng.randint(0, S + 1, size=B).tolist() if ragged else None
    q, k, v, k_q, k_s, v_q, v_s = _inputs(B, S, Hkv, 16, seed=seed % 997,
                                          Hq=Hkv * group, lengths=lengths)
    ref = kv_attention_ref(q, k_q, k_s, v_q, v_s, blk=blk)
    out = kv_attention(q, k_q, k_s, v_q, v_s, blk=blk, backend="interpret")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref),
        err_msg=f"B={B} S={S} Hkv={Hkv} G={group} blk={blk} lens={lengths}",
    )


# ------------------------------------------------- fused append-quantize

def test_fused_append_decode_matches_manual():
    """kv_attention_decode (quantize new token once → scatter → attend) ==
    quantizing/scattering by hand then attending; stale payload behind
    ``valid`` contributes nothing."""
    B, S, Hkv, Hq, hd = 2, 24, 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    k_fp = jax.random.normal(ks[0], (B, S, Hkv, hd))
    v_fp = jax.random.normal(ks[1], (B, S, Hkv, hd))
    ck, cks = quantize_kv(k_fp)
    cv, cvs = quantize_kv(v_fp)
    # garbage beyond position 10 — must be masked out by `valid`
    q = jax.random.normal(ks[2], (B, Hq, hd))
    k_new = jax.random.normal(ks[3], (B, 1, Hkv, hd))
    v_new = jax.random.normal(ks[0], (B, 1, Hkv, hd))
    idx = jnp.full((B, 1), 10, jnp.int32)
    valid = (jnp.arange(S) <= 10)[None, :].repeat(B, 0)

    out, leaves = kv_attention_decode(
        q, ck, cks, cv, cvs, k_new, v_new, idx, valid=valid,
        backend="interpret", blk=16)

    mk, mks, mv, mvs = append_quantize(ck, cks, cv, cvs, k_new, v_new, idx)
    ref = kv_attention(q, mk, jnp.where(valid[..., None], mks, 0.0),
                       mv, jnp.where(valid[..., None], mvs, 0.0),
                       backend="interpret", blk=16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    for a, b in zip(leaves, (mk, mks, mv, mvs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the new token landed at idx, quantized exactly once
    kq10, ks10 = quantize_kv(k_new)
    np.testing.assert_array_equal(np.asarray(leaves[0][:, 10]),
                                  np.asarray(kq10[:, 0]))


def test_v_bias_correction_reduces_mean_error():
    """The optional V dequant-error correction (paper §4.2 on the KV stream)
    must remove the per-token mean component of the V quantization error."""
    B, S, Hkv, hd = 2, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, Hkv, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    # biased V: round-to-nearest error keeps a nonzero mean per token
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)) + 0.8
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    v_err = jnp.mean(v_q.astype(jnp.float32) * v_s[..., None] - v, axis=-1)

    fp = _fp_oracle(q, k, v)
    plain = kv_attention_xla(q, k_q, k_s, v_q, v_s)
    corrected = kv_attention_xla(q, k_q, k_s, v_q, v_s, v_err=v_err)
    err_plain = float(jnp.mean(jnp.abs(plain - fp)))
    err_corr = float(jnp.mean(jnp.abs(corrected - fp)))
    assert err_corr <= err_plain
    assert not np.allclose(np.asarray(plain), np.asarray(corrected))
