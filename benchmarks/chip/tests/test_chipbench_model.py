"""The weights the benchmark makes fit the program's layout, and the plain
reference computes what the program's float model computes."""
import json

import numpy as np

from chipbench_tiny import TINY_CONFIG, TINY_MISTRAL

from chipbench import layout, model, reference


def test_weights_have_the_program_layout():
    import jax
    from repro.models import build_model
    from repro.models.config import ModelConfig

    for cfg in [TINY_CONFIG, TINY_MISTRAL] + [
            json.loads(p.read_text())
            for p in (layout.BENCH_DIR / "configs").glob("*.json")]:
        prog = build_model(model.program_config(cfg, ModelConfig))
        want = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
        got = model.param_shapes(cfg)
        flat = {"/".join(k.key for k in path): leaf.shape for path, leaf
                in jax.tree_util.tree_flatten_with_path(want)[0]}
        assert flat == got


def test_weights_follow_the_seed():
    a = model.make_params(TINY_CONFIG, 2 ** 33 + 1)
    b = model.make_params(TINY_CONFIG, 2 ** 33 + 1)
    c = model.make_params(TINY_CONFIG, 2 ** 33 + 2)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])


def test_reference_matches_the_programs_float_model():
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.models.config import ModelConfig

    cfg = dataclasses.replace(model.program_config(TINY_CONFIG, ModelConfig),
                              dtype="float32", remat=False)
    params = model.make_params(TINY_CONFIG, 9)
    tokens = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = build_model(cfg).apply(params, jnp.asarray(tokens)[None])
    logits = np.asarray(logits[0])
    # teacher-forced over the same tokens, the reference's gap at every
    # position equals the program's: best logit less the next token's
    nxt = np.append(tokens[1:], 7)
    gaps = reference.logit_gaps(params, TINY_CONFIG, [(tokens[:1], nxt)],
                                pad_to=64)[0]["served"]
    want = logits.max(-1) - logits[np.arange(40), nxt]
    assert np.max(np.abs(gaps - want)) < 1e-4
    assert want.max() > 0.1


def test_mistral_prefill_then_decode_match_the_references_forward():
    """The program's float model on the mistral block (no biases, untied
    head, head_dim not hidden / heads): a prompt prefilled into the cache,
    then one token at a time decoded through it, gives at every position
    the logits of the reference's full forward pass. Both run float32 at
    the highest matmul precision, so they differ by rounding alone: 1e-4
    against logits of size ~1."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.models.config import ModelConfig

    cfg = dataclasses.replace(model.program_config(TINY_MISTRAL, ModelConfig),
                              dtype="float32", remat=False)
    prog = build_model(cfg)
    params = model.make_params(TINY_MISTRAL, 11)
    tokens = np.random.default_rng(1).integers(0, 512, 24).astype(np.int32)
    P = 16
    with jax.default_matmul_precision("highest"):
        cache = prog.init_cache(1, 32, dtype=jnp.float32, kv_bits=16)
        logits, cache = prog.prefill(params, jnp.asarray(tokens[None, :P]),
                                     cache)
        got = [np.asarray(logits[0])]
        for t in tokens[P:]:
            logits, cache = prog.decode_step(params, jnp.asarray([[t]]),
                                             cache)
            got.append(np.asarray(logits[0]))
        arch = model.arch_of(TINY_MISTRAL)
        h = reference._forward(arch, params, jnp.asarray(tokens),
                               m=model.dims(TINY_MISTRAL), prec={})
        want = np.asarray(jnp.dot(h, arch.head(params),
                                  precision=jax.lax.Precision.HIGHEST))
    got = np.stack(got)                         # positions P-1 .. 23
    assert np.max(np.abs(got - want[P - 1:])) < 1e-4
    assert np.ptp(want) > 0.5
