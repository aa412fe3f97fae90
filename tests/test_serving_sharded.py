"""Tensor-parallel sharded serving tests (the tier1-multidevice CI job).

Anchor: on an 8-virtual-device CPU mesh the sharded ``ServingEngine`` must
produce the SAME tokens as the single-device engine — for fp32, the
serve-w8a16-tp recipe, and the full-int8 serve-w8a8-kv8-tp recipe. Slot
sharding is exact by construction (every slot's computation is
row-independent); TP's row-parallel psum reorders float reductions, so raw
logits carry a pinned tolerance (test_tp_logits_within_pinned_tolerance)
while greedy argmax — and therefore every generated token — must not move.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
tier1-multidevice job); skips, rather than fails, on a single-device host so
plain tier1 stays runnable anywhere.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs import get_config
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.serving import Request, ServingEngine
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

ARCH = "qwen2-0.5b"
VARIANTS = ["fp32", "serve-w8a16-tp", "serve-w8a8-kv8-tp"]


@pytest.fixture(scope="module")
def mesh():
    return make_production_mesh(shape=(2, 4))


@pytest.fixture(scope="module")
def fp32_setup():
    cfg = get_config(ARCH, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params, cfg


@pytest.fixture(scope="module")
def tp_artifacts(fp32_setup):
    model, params, _ = fp32_setup
    return {
        name: repro.quantize(model, params=params, recipe=name)
        for name in VARIANTS[1:]
    }


def _setup(variant, fp32_setup, tp_artifacts):
    if variant == "fp32":
        return fp32_setup
    qm = tp_artifacts[variant]
    return qm.model, qm.params, qm.cfg


def _mixed_trace(vocab):
    rng = np.random.RandomState(7)
    lens = [(5, 6), (12, 3), (3, 1), (9, 8)]  # includes a gen-at-prefill edge
    return [
        Request(rid=i, prompt=rng.randint(0, vocab, size=p).astype(np.int32),
                max_new_tokens=g)
        for i, (p, g) in enumerate(lens)
    ]


def _engine(model, params, cfg, **kw):
    kw.setdefault("num_slots", 2)   # < len(trace): forces slot recycling
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, params, cfg, **kw)


def _tokens(engine, trace):
    out = engine.run([dataclasses.replace(r) for r in trace])
    return {rid: r.tokens for rid, r in out.items()}


# ----------------------------------------------------- sharded-vs-single

@pytest.mark.parametrize("variant", VARIANTS)
def test_sharded_engine_token_parity(variant, fp32_setup, tp_artifacts, mesh):
    """The acceptance anchor: sharded == single-device, token for token,
    through slot recycling and the gen-at-prefill edge."""
    model, params, cfg = _setup(variant, fp32_setup, tp_artifacts)
    trace = _mixed_trace(cfg.vocab_size)
    single = _tokens(_engine(model, params, cfg), trace)
    sharded = _tokens(_engine(model, params, cfg, mesh=mesh), trace)
    assert sharded == single, f"{variant}: sharded tokens diverged"
    for r in trace:
        assert len(sharded[r.rid]) == r.max_new_tokens


@pytest.mark.parametrize("variant", VARIANTS[1:])
def test_sharded_kernel_tier_token_parity(variant, fp32_setup, tp_artifacts,
                                          mesh, monkeypatch):
    """On a Pallas tier (here its interpret twin) GSPMD cannot partition the
    kernels, so every one runs per shard under shard_map — column-parallel
    GEMMs on their own columns, row-parallel ones psum'd, attention per
    slot shard — and the sharded engine still serves the single-device
    tokens."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    model, params, cfg = _setup(variant, fp32_setup, tp_artifacts)
    trace = _mixed_trace(cfg.vocab_size)
    single = _tokens(_engine(model, params, cfg), trace)
    sharded = _tokens(_engine(model, params, cfg, mesh=mesh), trace)
    assert sharded == single, f"{variant}: per-shard kernels diverged"


@pytest.mark.parametrize("variant", ["fp32", "serve-w8a8-kv8-tp"])
def test_sharded_fast_vs_stepwise_parity(variant, fp32_setup, tp_artifacts,
                                         mesh):
    """The PR-3 fast-path contract survives sharding: fused horizons +
    batched prefill under the mesh == the sharded stepwise reference."""
    model, params, cfg = _setup(variant, fp32_setup, tp_artifacts)
    trace = _mixed_trace(cfg.vocab_size)
    fast = _tokens(_engine(model, params, cfg, mesh=mesh, fast=True), trace)
    slow = _tokens(_engine(model, params, cfg, mesh=mesh, fast=False), trace)
    assert fast == slow


def test_sharded_non_divisible_slots_replicate_and_match(fp32_setup, mesh):
    """num_slots=3 doesn't divide data=2: the pool replicates (graceful
    degradation) and tokens still match the single-device engine."""
    model, params, cfg = fp32_setup
    trace = _mixed_trace(cfg.vocab_size)
    kw = dict(num_slots=3)
    single = _tokens(_engine(model, params, cfg, **kw), trace)
    eng = _engine(model, params, cfg, mesh=mesh, **kw)
    assert eng.pool.cache["k"].sharding.spec == P(None, None, None, None, None)
    assert _tokens(eng, trace) == single


def test_tp_logits_within_pinned_tolerance(fp32_setup, mesh):
    """Where TP legitimately differs: the row-parallel wo/wd psum reorders
    float reductions, so sharded prefill logits wobble at float precision.
    Pin the tolerance — and that the greedy argmax does not move."""
    model, params, cfg = fp32_setup
    heads = {"n_q": cfg.n_heads, "n_kv": cfg.n_kv_heads}
    from repro.sharding import named_shardings, params_pspecs

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    sharded_params = jax.device_put(
        params, named_shardings(params_pspecs(shapes, mesh, heads,
                                              mode="serve"), mesh))
    tokens = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab_size, size=(1, 8)),
        jnp.int32)

    def prefill(p):
        cache = model.init_cache(1, 16, dtype=jnp.float32, per_slot=True)
        logits, _ = model.prefill(p, tokens, cache)
        return logits

    ref = np.asarray(jax.jit(prefill)(params))
    got = np.asarray(jax.jit(prefill)(sharded_params))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=1e-5)
    assert np.array_equal(np.argmax(got, -1), np.argmax(ref, -1))


# ------------------------------------------------------ placement contracts

def test_sharded_pool_and_param_placement(tp_artifacts, mesh):
    """End-to-end placement over a REAL mesh: kv8 scale/v_err leaves follow
    their payload, slots shard over data, int8 weights TP over model with
    tied embeddings vocab-parallel."""
    qm = tp_artifacts["serve-w8a8-kv8-tp"]
    eng = _engine(qm.model, qm.params, qm.cfg, mesh=mesh, num_slots=4)
    cache = eng.pool.cache
    assert cache["k"].sharding.spec == P(None, "data", None, None, None)
    for leaf in ("k_scale", "v_scale"):
        assert cache[leaf].sharding.spec == P(None, "data", None, None)
    assert cache["kpos"].sharding.spec == P("data", None)
    assert cache["pos"].sharding.spec == P("data")
    wu = eng.params["blocks"]["mlp"]["wu"]
    assert wu.q.sharding.spec == P(None, None, "model")     # column-parallel
    wd = eng.params["blocks"]["mlp"]["wd"]
    assert wd.q.sharding.spec == P(None, "model", None)     # row-parallel
    assert eng.params["embed"].sharding.spec == P("model", None)


def test_sharded_cache_donation_preserved(fp32_setup, mesh):
    """Donation must survive the pinned out_shardings: after a run, the
    pre-run pooled cache buffer has been consumed in place, not copied."""
    model, params, cfg = fp32_setup
    eng = _engine(model, params, cfg, mesh=mesh)
    pre = eng.pool.cache["k"]
    eng.run(_mixed_trace(cfg.vocab_size))
    assert pre.is_deleted()


# ------------------------------------------------------- artifact round trip

def test_tp_artifact_save_load_serve_round_trip(tp_artifacts, mesh, tmp_path):
    """quantize → save(mesh) → load → serve: the artifact records the
    parallelism plan + concrete specs, and the restored engine reproduces
    the pre-save tokens on the recorded topology."""
    qm = tp_artifacts["serve-w8a16-tp"]
    trace = _mixed_trace(qm.cfg.vocab_size)
    before = _tokens(_engine(qm.model, qm.params, qm.cfg, mesh=mesh), trace)

    from repro.pipeline import QuantizedModel

    qm.save(str(tmp_path), mesh=mesh)
    loaded = QuantizedModel.load(str(tmp_path))
    assert loaded.shard_mode == "tp"
    assert loaded.sharding["mesh_shape"] == [2, 4]
    assert loaded.sharding["mesh_axes"] == ["data", "model"]
    specs = loaded.sharding["specs"]
    # int8 payload and scale recorded on the same TP axis
    assert "'model'" in specs["/blocks/mlp/wu/q"]
    assert specs["/blocks/attn/wo/scale"] == "PartitionSpec(None, None)"

    restored_mesh = make_production_mesh(
        shape=tuple(loaded.sharding["mesh_shape"]))
    eng = ServingEngine.from_quantized(
        loaded, mesh=restored_mesh, num_slots=2, max_len=32, prefill_chunk=8)
    assert _tokens(eng, trace) == before


# ------------------------------------------------- shard_map decode kernel

def test_shard_map_decode_engages_and_matches(fp32_setup, monkeypatch):
    """On a mesh whose model axis divides BOTH head counts (2x2: Hq=4,
    Hkv=2), the int8-KV decode hot path routes through the shard_map'd fused
    kernel (head-local attention, zero collectives in the body) — and the
    tokens still match the single-device engine bit for bit."""
    from repro.models import layers

    model, params, cfg = fp32_setup
    assert cfg.n_heads % 2 == 0 and cfg.n_kv_heads % 2 == 0
    trace = _mixed_trace(cfg.vocab_size)
    single = _tokens(_engine(model, params, cfg, kv_bits=8), trace)

    calls = []
    real = layers._fused_decode_tp

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(layers, "_fused_decode_tp", counting)
    small = make_production_mesh(shape=(2, 2))
    sharded = _tokens(_engine(model, params, cfg, mesh=small, kv_bits=8),
                      trace)
    assert calls, "shard_map decode path never engaged on the 2x2 mesh"
    assert sharded == single


def test_shard_map_decode_guard_disengages_on_indivisible_heads(fp32_setup,
                                                                monkeypatch,
                                                                mesh):
    """model=4 does not divide n_kv_heads=2: the guard must fall back to the
    replicated decode path rather than shard_map a ragged head split."""
    from repro.models import layers

    model, params, cfg = fp32_setup
    assert cfg.n_kv_heads % mesh.shape["model"] != 0
    calls = []
    real = layers._fused_decode_tp

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(layers, "_fused_decode_tp", counting)
    trace = _mixed_trace(cfg.vocab_size)
    _tokens(_engine(model, params, cfg, mesh=mesh, kv_bits=8), trace)
    assert not calls


# ---------------------------------------------------------------- mesh ctor

def test_make_production_mesh_shape_override():
    m = make_production_mesh(shape=(1, 8))
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 1, "model": 8}
    m3 = make_production_mesh(shape=(2, 2, 2))
    assert m3.axis_names == ("pod", "data", "model")
