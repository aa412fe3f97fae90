"""Set-up layer by layer (``weights.by_layer``): each layer made from the
seed alone is the whole model's slice, quantizes exactly as the whole
model does, and the reference run layer by layer computes what it computes
over the whole model."""
import argparse
import json
import types

import numpy as np
import pytest

from chipbench_tiny import TINY_MISTRAL, make_tiny_checkout

import run
from chipbench import layout, model, reference


def _leaves(tree):
    import jax

    return {"/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_a_group_is_the_whole_models_slice():
    """Layers made one at a time, or a run of them in one call (a test
    input: set-up makes one), are the slices of the whole stack."""
    import jax

    whole = _leaves(model.make_params(TINY_MISTRAL, 2 ** 33 + 1))
    one = [_leaves(model.make_layers(TINY_MISTRAL, 2 ** 33 + 1, i, 1))
           for i in range(TINY_MISTRAL["num_hidden_layers"])]
    two = _leaves(model.make_layers(TINY_MISTRAL, 2 ** 33 + 1, 1, 2))
    outer = _leaves(model.make_outer(TINY_MISTRAL, 2 ** 33 + 1))
    assert set(whole) == set(outer) | {"blocks/" + k for k in two}
    for k, v in outer.items():
        assert np.array_equal(v, whole[k]), k
    for k, v in two.items():
        stacked = np.concatenate([layer[k] for layer in one])
        assert np.array_equal(stacked, whole["blocks/" + k]), k
        assert np.array_equal(v, whole["blocks/" + k][1:3]), k
    other = _leaves(model.make_params(TINY_MISTRAL, 2 ** 33 + 2))
    assert not np.array_equal(other["blocks/attn/wq"], whole["blocks/attn/wq"])
    assert jax.tree.structure(model.make_params(TINY_MISTRAL, 1)) == (
        jax.tree.structure(model.make_params(
            dict(TINY_MISTRAL, weights=dict(TINY_MISTRAL["weights"],
                                            by_layer=False)), 1)))


@pytest.mark.parametrize("recipe", ["serve-w8a8-kv8", "serve-w8a16"])
def test_groups_quantize_as_the_whole_model(recipe):
    """Quantized one layer at a time, as set-up does: int8 payloads, scales
    and every other leaf bit-equal to the whole model's; the serve recipes'
    stages act within a layer."""
    import repro
    from repro.models import build_model
    from repro.models.config import ModelConfig

    config = dict(TINY_MISTRAL, serving=dict(TINY_MISTRAL["serving"],
                                             recipe=recipe))
    arch = model.arch_of(config)
    ctx = types.SimpleNamespace(config=config, arch=arch,
                                serving=config["serving"])
    grouped, t_weights = run._quantize(ctx, 5, repro, build_model,
                                       ModelConfig, None)
    assert t_weights is None
    whole = repro.quantize(
        build_model(model.program_config(config, ModelConfig)),
        params=model.make_params(config, 5), recipe=recipe)
    assert grouped.cfg == whole.cfg
    g, w = _leaves(grouped.params), _leaves(whole.params)
    assert g.keys() == w.keys()
    assert any(v.dtype == np.int8 for v in w.values())
    for k in w:
        assert g[k].dtype == w[k].dtype and np.array_equal(g[k], w[k]), k


def test_the_reference_by_groups_is_the_whole_reference():
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, 512, 9), rng.integers(0, 512, 20)),
            (rng.integers(0, 512, 30), rng.integers(0, 512, 5))]
    by_layer = reference.seeded_logit_gaps(TINY_MISTRAL, 7, seqs, 64,
                                            with_control=True)
    whole = reference.logit_gaps(model.make_params(TINY_MISTRAL, 7),
                                 TINY_MISTRAL, seqs, 64, with_control=True)
    for a, b in zip(by_layer, whole):
        for k in ("served", "control"):
            # the same operations in other programs: float32 rounding only
            np.testing.assert_allclose(a[k], b[k], rtol=0, atol=1e-5)
        assert b["served"].max() > 0.1


# On the CPU at the tiny mistral size, sound runs read at most 0.341 (6
# seeds on each tiny mix), the int4 control at least 1.19.
TINY_MISTRAL_GAP_LIMIT = 0.6


def test_a_run_by_groups_is_correct_and_its_control_is_not(tmp_path,
                                                           monkeypatch):
    bench = make_tiny_checkout(tmp_path, gap_limit=TINY_MISTRAL_GAP_LIMIT)
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(dict(TINY_MISTRAL, name="tiny")))
    assert layout.load_cell("tiny.closed", tmp_path, bench).arch.__file__ \
        .endswith("archs/mistral.py")
    monkeypatch.setattr(run, "_configure_jax", lambda root: None)
    args = argparse.Namespace(workload="tiny.closed", seed=2 ** 31 + 3,
                              seconds=1.0, trace=0, keep_trace=None)
    r = run.run_cell(args, tmp_path, bench, require_tpu=False, control=True)
    c = r["checks"]
    assert r["correct"] and r["failed"] == 0
    assert r["control_correct"] is False
    assert (c["control_logit_gap"]["value"]
            >= 3 * c["max_logit_gap"]["value"])


@pytest.mark.parametrize("fault", ["token", "state"])
def test_a_planted_fault_in_a_run_by_groups_is_not_correct(
        tmp_path, monkeypatch, fault):
    from test_chipbench_run import _plant

    bench = make_tiny_checkout(tmp_path, gap_limit=TINY_MISTRAL_GAP_LIMIT)
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(dict(TINY_MISTRAL, name="tiny")))
    monkeypatch.setattr(run, "_configure_jax", lambda root: None)
    _plant(monkeypatch, fault)
    args = argparse.Namespace(workload="tiny.closed", seed=2 ** 31 + 3,
                              seconds=1.0, trace=0, keep_trace=None)
    r = run.run_cell(args, tmp_path, bench, require_tpu=False)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_MISTRAL_GAP_LIMIT
