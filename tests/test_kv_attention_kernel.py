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
from repro.kernels.kv_attention.kernel import bf16_terms
from repro.kernels.kv_attention.ref import (
    block_rows,
    flat_heads,
    kv_attention_ref,
    kv_attention_xla,
)


def _inputs(B, S, Hkv, hd, seed=0, Hq=None, lengths=None):
    """Random fp K/V quantized per-token/per-head into lane-dense payloads;
    positions at or past each row's ragged ``length`` get scale 0 (=
    masked, the op contract)."""
    Hq = Hq or Hkv
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    k_q, k_s = quantize_kv(k)
    v_q, v_s = quantize_kv(v)
    k_q, v_q = flat_heads(k_q), flat_heads(v_q)
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


@pytest.mark.parametrize("kind", ["normal", "probabilities"])
def test_bf16_terms_split_exactly(kind):
    """The attention dots' bf16 terms rebuild an fp32 operand bit for bit
    (so q·kᵀ and p·v over the bf16-exact int8 payload multiply exactly),
    for plain values and for softmax weights spread over ~100 binades (down
    to ~1e-31, whose last term stays a normal fp32); a bf16 operand is its
    own term."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 512)) * 3.0
    if kind == "probabilities":
        x = jnp.exp(-jnp.abs(x) * 5.0) * 0.03       # exp(s − m) · v-scale
    terms = bf16_terms(x)
    assert len(terms) == 3 and all(t.dtype == jnp.bfloat16 for t in terms)
    back = sum(t.astype(jnp.float32) for t in terms)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    xb = x.astype(jnp.bfloat16)
    assert bf16_terms(xb) == [xb]


@pytest.mark.parametrize("S,width,rows", [
    (2560, 128, 2560),      # a whole ring fits one 512 KiB block
    (4096, 128, 4096),
    (8192, 128, 4096),
    (5000, 128, 2560),      # two blocks, rounded up to the 128-lane tile
    (32768, 1024, 512),
])
def test_block_rows_by_bytes(S, width, rows):
    assert block_rows(S, width) == rows


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
    ck, cv = flat_heads(ck), flat_heads(cv)
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
    # the new token landed at idx as one lane-dense row, quantized once
    kq10, ks10 = quantize_kv(k_new)
    np.testing.assert_array_equal(np.asarray(leaves[0][:, 10]),
                                  np.asarray(flat_heads(kq10)[:, 0]))
    np.testing.assert_array_equal(np.asarray(leaves[1][:, 10]),
                                  np.asarray(ks10[:, 0]))


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
    k_q, v_q = flat_heads(k_q), flat_heads(v_q)

    fp = _fp_oracle(q, k, v)
    plain = kv_attention_xla(q, k_q, k_s, v_q, v_s)
    corrected = kv_attention_xla(q, k_q, k_s, v_q, v_s, v_err=v_err)
    err_plain = float(jnp.mean(jnp.abs(plain - fp)))
    err_corr = float(jnp.mean(jnp.abs(corrected - fp)))
    assert err_corr <= err_plain
    assert not np.allclose(np.asarray(plain), np.asarray(corrected))
