#!/usr/bin/env python3
"""Compile each configuration's serving programs for a described TPU v5e,
without the chip, and print what they hold in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [config ...]

A configuration is named as in BENCHMARK.json or by the path of its file
(any architecture that ``chipbench/archs`` describes). For each (all of
BENCHMARK.json's by default) it builds the engine at the configuration's
``num_slots``, ``max_len``, chunk and horizon over one quantized layer
(the layer scan compiles the same body at any depth), then lowers the
full-width prefill and every decode horizon with the arguments at the
configuration's full depth, compiles them for one chip of a described
``v5e:2x2`` (or, with ``--mesh data,model``, for a mesh over its chips,
placed as the program serves on one), and prints ``memory_analysis()``: a
device's bytes beside the chip's memory. Run by hand: whole-step compiles
are too slow for the test suite. A compile that passes is not a chip
run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from pathlib import Path

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import flops, layout, model  # noqa: E402

# cache leaves that carry a leading layer axis
_LAYERED = ("k", "v", "k_scale", "v_scale", "v_err")


def _lift(tree, depth, layered):
    """ShapeDtypeStructs of ``tree`` with its layer axis at ``depth``."""
    import jax

    def one(path, leaf):
        shape = tuple(leaf.shape)
        if layered(path) and shape and shape[0] == 1:
            shape = (depth,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def _placed(tree, sharding):
    """``tree``'s ShapeDtypeStructs placed by ``sharding``: one for every
    leaf, or a matching tree of them."""
    import jax

    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def rehearse(config: dict, mesh_shape=None) -> int:
    """Compile the configuration's serving programs at full depth for one
    chip of a described ``v5e:2x2`` or, with ``mesh_shape`` (data, model),
    for a mesh over its chips, the weights and the pool placed by the
    program's serve-mode specs; print each program's bytes (a device's,
    on a mesh) and return the largest."""
    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from jax.sharding import SingleDeviceSharding

    import repro
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.models.layers import set_serve_mesh
    from repro.serving import ServingEngine
    from repro.sharding import (named_shardings, params_pspecs,
                                serve_cache_pspecs)

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
    mesh = None
    if mesh_shape:
        mesh = Mesh(np.array(topo.devices[:mesh_shape[0] * mesh_shape[1]])
                    .reshape(mesh_shape), ("data", "model"))
    specs = eng.serve_jit_specs()
    os.environ["REPRO_KERNEL_BACKEND"] = "pallas"
    worst = 0
    for name, k in [("prefill_multi", None)] + [
            ("decode_horizon", k) for _, k in sorted(
                j for j in eng.warmup_shapes() if j[0] == "decode_horizon")]:
        _, impl, args, kw = specs[name]
        params = _lift(args[0], m["L"],
                       lambda p: _name(p).startswith("blocks"))
        cache = _lift(args[2], m["L"], lambda p: _name(p) in _LAYERED)
        rest = [_lift(a, 1, lambda p: False) for a in args[1:]]
        if mesh is None:
            rep = p_sh = c_sh = SingleDeviceSharding(topo.devices[0])
        else:
            heads = {"n_q": m["Hq"], "n_kv": m["Hkv"]}
            rep = NamedSharding(mesh, PartitionSpec())
            p_sh = named_shardings(
                params_pspecs(params, mesh, heads, mode="serve"), mesh)
            c_sh = named_shardings(serve_cache_pspecs(cache, mesh), mesh)
        rest = [_placed(a, rep) for a in rest]
        rest[1] = _placed(cache, c_sh)
        kw = {"k": k} if k else {}
        jit = jax.jit(impl, static_argnames=tuple(kw), donate_argnums=(2,))
        prev = set_serve_mesh(mesh)
        try:
            compiled = jit.lower(_placed(params, p_sh), *rest,
                                 **kw).compile()
        finally:
            set_serve_mesh(prev["mesh"], dp=prev["dp"], model=prev["model"])
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        worst = max(worst, total)
        reduces = len(re.findall(r"all-reduce(?:-start)?\(", text))
        print(f"  {name}{'' if k is None else f' k={k}'}: "
              f"{text.count('tpu_custom_call')} tpu_custom_call, "
              f"{reduces} all-reduce; arguments "
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
    ap.add_argument("--mesh", default=None,
                    help="data,model: compile for a mesh of that shape (a "
                    "device's bytes), not for one chip")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(layout.ROOT / "src"))
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((layout.ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: layout.ROOT / c["file"] for c in bench["configs"]}
    chip_bytes = flops.PEAKS["TPU v5 lite"]["hbm_bytes"]
    mesh_shape = (tuple(int(n) for n in args.mesh.split(","))
                  if args.mesh else None)
    ok = True
    for name in args.configs or list(files):
        path = files.get(name, Path(name))
        if not path.is_file():
            raise SystemExit(f"no configuration {name!r} in BENCHMARK.json "
                             "and no such file")
        config = json.loads(path.read_text())
        if args.num_slots:
            config["serving"]["num_slots"] = args.num_slots
        print(f"{name}: {config['serving']}", flush=True)
        worst = rehearse(config, mesh_shape)
        print(f"{name}: largest program holds {worst} bytes of the "
              f"chip's {chip_bytes:.0f} ({100 * worst / chip_bytes:.1f}%)")
        ok &= worst < chip_bytes
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
