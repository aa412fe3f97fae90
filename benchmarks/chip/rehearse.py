#!/usr/bin/env python3
"""Compile each configuration's serving programs for a described TPU v5e,
without the chip, and print what they hold in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [config ...]

For each configuration (all of BENCHMARK.json's by default) it builds the
engine at the configuration's ``num_slots``, ``max_len``, chunk and horizon
over one quantized layer (the layer scan compiles the same body at any
depth), then lowers the full-width prefill and every decode horizon with
the arguments at the configuration's full depth, compiles them for one
chip of a described ``v5e:2x2``, and prints ``memory_analysis()`` with the
pool and weight bytes beside the chip's memory. Run by hand: whole-step
compiles are too slow for the test suite. A compile that passes is not a
chip run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import flops, layout, model  # noqa: E402

# cache leaves that carry a leading layer axis
_LAYERED = ("k", "v", "k_scale", "v_scale", "v_err")


def _lift(tree, depth, sharding, layered):
    """ShapeDtypeStructs of ``tree`` with its layer axis at ``depth``."""
    import jax

    def one(path, leaf):
        shape = tuple(leaf.shape)
        if layered(path) and shape and shape[0] == 1:
            shape = (depth,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, leaf.dtype, sharding=sharding)

    return jax.tree_util.tree_map_with_path(one, tree)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def rehearse(config: dict) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.serving import ServingEngine

    s, m = config["serving"], model.dims(config)
    one = dataclasses.replace(model.program_config(config, ModelConfig),
                              n_layers=1)
    cfg1 = dict(config, num_hidden_layers=1)
    qm = repro.quantize(build_model(one), params=model.make_params(cfg1, 0),
                        recipe=s["recipe"])
    eng = ServingEngine.from_quantized(
        qm, num_slots=s["num_slots"], max_len=s["max_len"],
        prefill_chunk=s["prefill_chunk"], decode_horizon=s["decode_horizon"],
        kv_bits=s["kv_bits"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    specs = eng.serve_jit_specs()
    os.environ["REPRO_KERNEL_BACKEND"] = "pallas"
    worst = 0
    for name, k in [("prefill_multi", None)] + [
            ("decode_horizon", k) for _, k in sorted(
                j for j in eng.warmup_shapes() if j[0] == "decode_horizon")]:
        _, impl, args, kw = specs[name]
        params = _lift(args[0], m["L"], chip,
                       lambda p: _name(p).startswith("blocks"))
        cache = _lift(args[2], m["L"], chip, lambda p: _name(p) in _LAYERED)
        rest = [_lift(a, 1, chip, lambda p: False) for a in args[1:]]
        rest[1] = cache
        kw = {"k": k} if k else {}
        jit = jax.jit(impl, static_argnames=tuple(kw), donate_argnums=(2,))
        compiled = jit.lower(params, *rest, **kw).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        worst = max(worst, total)
        print(f"  {name}{'' if k is None else f' k={k}'}: "
              f"{text.count('tpu_custom_call')} tpu_custom_call; arguments "
              f"{mem.argument_size_in_bytes}, outputs "
              f"{mem.output_size_in_bytes}, aliased "
              f"{mem.alias_size_in_bytes}, temporaries "
              f"{mem.temp_size_in_bytes}; total {total} bytes", flush=True)
    os.environ.pop("REPRO_KERNEL_BACKEND")
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="try another slot count than the file's")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(layout.ROOT / "src"))
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((layout.ROOT / "BENCHMARK.json").read_text())
    chip_bytes = flops.PEAKS["TPU v5 lite"]["hbm_bytes"]
    ok = True
    for c in bench["configs"]:
        if args.configs and c["name"] not in args.configs:
            continue
        config = json.loads((layout.ROOT / c["file"]).read_text())
        if args.num_slots:
            config["serving"]["num_slots"] = args.num_slots
        print(f"{c['name']}: {config['serving']}", flush=True)
        worst = rehearse(config)
        print(f"{c['name']}: largest program holds {worst} bytes of the "
              f"chip's {chip_bytes:.0f} ({100 * worst / chip_bytes:.1f}%)")
        ok &= worst < chip_bytes
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
