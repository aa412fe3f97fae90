"""Partition-planner unit tests: serve-mode specs for quantized leaves.

Pins the sharding contract the TP serving path relies on:

  * a column-parallel int8 weight and its per-channel scale land on the SAME
    "model" axis (a TP shard dequantizes its own columns locally),
  * row-parallel weights shard their IN dim, so their scales replicate,
  * non-divisible dims replicate (graceful degradation),
  * kv8 cache scale / ``v_err`` leaves follow their payload tensor (same
    slot axis over "data", same head axis over "model").

Spec computation only reads ``mesh.shape``, so these run on a single device
(tier1) with a stub mesh; the multi-device CI job exercises the same specs
against a real mesh end-to-end in test_serving_sharded.py.
"""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_production_mesh
from repro.quantized.qtensor import QTensor
from repro.sharding import cache_pspecs, params_pspecs, serve_cache_pspecs
from repro.sharding.partition import spec_paths


class _StubMesh:
    """Just enough mesh for the planner: spec rules only read .shape."""

    def __init__(self, **axes):
        self.shape = dict(axes)


MESH = _StubMesh(data=2, model=4)
HEADS = {"n_q": 8, "n_kv": 2}


def _sds(*shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, jax.numpy.dtype(dtype))


def _qt(k, n, *, per_channel=True, L=2):
    """Stacked [L, K, N] int8 QTensor shapes with [L, N] or [L, 1] scales."""
    return QTensor(
        _sds(L, k, n, dtype="int8"),
        _sds(L, n if per_channel else 1),
        "w8a16",
    )


def _specs(params):
    return params_pspecs(params, MESH, HEADS, mode="serve")


# ------------------------------------------------------- quantized weights

def test_column_parallel_weight_and_scale_co_shard():
    """wu [L, D, F]: out dim on "model" — and the per-channel scale's channel
    dim must land on the SAME axis."""
    spec = _specs({"blocks": {"mlp": {"wu": _qt(256, 512)}}})
    wu = spec["blocks"]["mlp"]["wu"]
    assert wu.q == P(None, None, "model")
    assert wu.scale == P(None, "model")


def test_row_parallel_weight_shards_in_dim_scale_replicates():
    """wd [L, F, D]: IN dim on "model" (row-parallel partial sums); the scale
    mirrors the OUT dim, which is unsharded — it must replicate."""
    spec = _specs({"blocks": {"mlp": {"wd": _qt(512, 256)}}})
    wd = spec["blocks"]["mlp"]["wd"]
    assert wd.q == P(None, "model", None)
    assert wd.scale == P(None, None)


def test_per_tensor_scale_replicates():
    """[L, 1] per-tensor scales are never divisible — replicate."""
    spec = _specs({"blocks": {"mlp": {"wu": _qt(256, 512, per_channel=False)}}})
    assert spec["blocks"]["mlp"]["wu"].q == P(None, None, "model")
    assert spec["blocks"]["mlp"]["wu"].scale == P(None, None)


def test_non_divisible_out_dim_replicates_weight_and_scale():
    """d_ff=100 doesn't divide model=4 (and is < MIN_SHARD_DIM): both the
    int8 payload and its scale replicate — no orphaned-scale mismatch."""
    spec = _specs({"blocks": {"mlp": {"wu": _qt(256, 100)}}})
    assert spec["blocks"]["mlp"]["wu"].q == P(None, None, None)
    assert spec["blocks"]["mlp"]["wu"].scale == P(None, None)


def test_attention_scale_respects_head_divisibility():
    """wq shards only when n_q divides model; wk/wv key off n_kv (2 % 4 != 0
    here) — their scale must follow the payload into replication."""
    spec = _specs({"blocks": {"attn": {
        "wq": _qt(256, 256), "wk": _qt(256, 256), "wv": _qt(256, 256),
    }}})
    attn = spec["blocks"]["attn"]
    assert attn["wq"].q == P(None, None, "model")      # n_q=8 % 4 == 0
    assert attn["wq"].scale == P(None, "model")
    for name in ("wk", "wv"):                          # n_kv=2 % 4 != 0
        assert attn[name].q == P(None, None, None)
        assert attn[name].scale == P(None, None)


def test_serve_mode_drops_fsdp_factor():
    """Serving weights stay resident: no "data" factor anywhere (train mode
    would shard the in dim over "data")."""
    params = {"blocks": {"mlp": {"wu": _sds(2, 256, 512)}}}
    train = params_pspecs(params, MESH, HEADS, mode="train")
    serve = params_pspecs(params, MESH, HEADS, mode="serve")
    assert train["blocks"]["mlp"]["wu"] == P(None, "data", "model")
    assert serve["blocks"]["mlp"]["wu"] == P(None, None, "model")


def test_train_mode_scale_still_replicates():
    """The co-sharding rule is serve-only; train/decode keep scales tiny and
    replicated (the pre-existing contract)."""
    spec = params_pspecs(
        {"blocks": {"mlp": {"wu": _qt(256, 512)}}}, MESH, HEADS, mode="train"
    )
    assert spec["blocks"]["mlp"]["wu"].scale == P()


# ------------------------------------------------------------ serving cache

def _kv8_cache(B, *, L=2, S=32, H=2, hd=16, v_err=True):
    """The serving int8 pool: lane-dense payload [L, B, S, H·hd]."""
    c = {
        "k": _sds(L, B, S, H * hd, dtype="int8"),
        "v": _sds(L, B, S, H * hd, dtype="int8"),
        "k_scale": _sds(L, B, S, H),
        "v_scale": _sds(L, B, S, H),
        "kpos": _sds(B, S, dtype="int32"),
        "pos": _sds(B, dtype="int32"),
    }
    if v_err:
        c["v_err"] = _sds(L, B, S, H)
    return c


def test_serve_cache_slots_shard_over_data():
    spec = serve_cache_pspecs(_kv8_cache(4), MESH)
    assert spec["k"] == P(None, "data", None, None)
    assert spec["kpos"] == P("data", None)
    assert spec["pos"] == P("data")


def test_serve_cache_scales_follow_their_cache_tensor():
    """k_scale/v_scale/v_err [L, B, S, H] must mirror the payload's slot and
    head placement — here heads replicate (2 % 4 != 0), slots shard."""
    spec = serve_cache_pspecs(_kv8_cache(4), MESH)
    for leaf in ("k_scale", "v_scale", "v_err"):
        assert spec[leaf] == P(None, "data", None, None)
    # a model axis the heads DO divide: payload and scales move together
    spec = serve_cache_pspecs(_kv8_cache(4, H=4), _StubMesh(data=2, model=2))
    assert spec["k"] == P(None, "data", None, "model")
    for leaf in ("k_scale", "v_scale", "v_err"):
        assert spec[leaf] == P(None, "data", None, "model")


@pytest.mark.parametrize("H,model,sharded", [
    (2, 4, False),     # H·hd = 32 divides by 4, but the 2 heads do not
    (4, 4, True),      # one whole head per shard
    (8, 4, True),      # two whole heads per shard
])
def test_serve_cache_lane_dense_payload_splits_whole_heads(H, model, sharded):
    """The int8 payload's lane-dense H·hd axis shards over "model" only
    when the head count divides, so a shard owns contiguous whole heads —
    never when just H·hd divides — and always with its scales."""
    spec = serve_cache_pspecs(_kv8_cache(4, H=H),
                              _StubMesh(data=2, model=model))
    want = "model" if sharded else None
    for leaf in ("k", "v"):
        assert spec[leaf] == P(None, "data", None, want)
    for leaf in ("k_scale", "v_scale", "v_err"):
        assert spec[leaf] == P(None, "data", None, want)


@pytest.mark.parametrize("H,sharded", [
    (3, False),        # H·hd = 384 divides by 4, but the 3 heads do not
    (4, True),         # one whole head per shard
])
def test_cache_pspecs_lane_dense_payload_splits_whole_heads(H, sharded):
    """The generic decode cache specs follow the same whole-head rule: with
    a ring (30) the model axis cannot split, the int8 payload's lane-dense
    H·hd axis shards over "model" only when the head count divides."""
    spec = cache_pspecs(_kv8_cache(4, S=30, H=H, hd=128),
                        _StubMesh(data=2, model=4), batch=4)
    want = "model" if sharded else None
    for leaf in ("k", "v"):
        assert spec[leaf] == P(None, "data", None, want)


def test_serve_cache_non_divisible_slots_replicate():
    spec = serve_cache_pspecs(_kv8_cache(3), MESH)
    assert spec["k"] == P(None, None, None, None)
    assert spec["kpos"] == P(None, None)
    assert spec["pos"] == P(None)


def test_spec_paths_yields_qtensor_children_not_tuple_elements():
    """PartitionSpec subclasses tuple on some jax versions — the spec walker
    must yield whole specs at QTensor q/scale paths, not iterate into them."""
    spec = _specs({"blocks": {"mlp": {"wu": _qt(256, 512)}}})
    flat = dict(spec_paths(spec))
    assert flat["/blocks/mlp/wu/q"] == P(None, None, "model")
    assert flat["/blocks/mlp/wu/scale"] == P(None, "model")


# ---------------------------------------------------------------- mesh ctor

def test_make_production_mesh_rejects_bad_shapes():
    with pytest.raises(ValueError):
        make_production_mesh(shape=(8,))
    with pytest.raises(ValueError):
        make_production_mesh(shape=(2, 0))
    with pytest.raises(ValueError):
        make_production_mesh(shape=(1, 2, 3, 4))
