"""The serving programs of a mistral-nemo-12b layer, compiled for a
described TPU v5e at its published widths: head_dim 128, 8 kv heads of a
GQA group of 4, int8 GEMMs over K 5120 and 14336. A compile is no chip
run: it shows that Mosaic and XLA accept the kernels at these shapes,
nothing of results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench_tiny import TINY_MISTRAL

from chipbench import model

# Mistral-Nemo-Base-2407's published config.json numbers
# (https://huggingface.co/mistralai/Mistral-Nemo-Base-2407)
MISTRAL_NEMO_12B = dict(
    TINY_MISTRAL, name="mistral-nemo-12b", hidden_size=5120, head_dim=128,
    intermediate_size=14336, num_attention_heads=32, num_hidden_layers=40,
    num_key_value_heads=8, vocab_size=131072,
    max_position_embeddings=131072, rms_norm_eps=1e-05,
    rope_theta=1000000.0)


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
def one_layer_engine():
    """One layer at its published widths, made and quantized as the
    benchmark does, with a 4096-row vocabulary (the head is no kernel) and
    8 slots over a 288-position ring."""
    import dataclasses

    import repro
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.serving import ServingEngine

    # the published 1e-5 where the program takes an epsilon; else the
    # program's own 1e-6 stands in for it (the epsilon is no shape)
    config = dict(MISTRAL_NEMO_12B, num_hidden_layers=1, vocab_size=4096)
    if "norm_eps" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        config["rms_norm_eps"] = 1e-6
    m = model.dims(config)
    assert (m["D"], m["Hq"], m["Hkv"], m["hd"], m["F"]) == (
        5120, 32, 8, 128, 14336)
    qm = repro.quantize(
        build_model(model.program_config(config, ModelConfig)),
        params=model.make_params(config, 0),
        recipe=config["serving"]["recipe"])
    return ServingEngine.from_quantized(
        qm, num_slots=8, max_len=288, prefill_chunk=16,
        kv_bits=config["serving"]["kv_bits"])


@pytest.mark.parametrize("jit", ["decode_horizon", "prefill_multi"])
def test_the_layer_compiles_for_v5e(jit, topo, one_layer_engine,
                                    monkeypatch):
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, _, args, kw = one_layer_engine.serve_jit_specs()[jit]
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    text = fn.lower(*jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args), **kw).compile().as_text()
    for kernel in ("qmatmul_w8a8_pallas", "quantize_act_pallas"):
        assert kernel in text, kernel
    if jit == "decode_horizon":
        assert "fused_decode_pallas" in text
