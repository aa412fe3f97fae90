"""Graph extraction: trace/lower the serve paths WITHOUT running them.

For a recipe + mesh shape this builds a ``LintGraph``:

  * each of the four engine jits (prefill, decode, fused horizon, batched
    prefill) as a ``JitArtifact`` — its traced jaxpr (``jax.make_jaxpr`` on
    the unjitted impl) and its optimized per-device HLO (``.lower()`` +
    ``.compile()``, parsed by ``hlo_model`` — compilation never executes),
  * the standalone serving kernels (jaxpr-only artifacts: no cache pool,
    no donation contract — the dtype ledger still covers them),
  * the cache-pool leaf shapes (global and per-device) the donation and
    collective rules match against,
  * the sharding-spec pytrees (params + cache) and QTensor payload/scale
    pairs the scale-coupling rule checks,
  * the engine's warmup/dispatch shape sets for the recompilation guard.

Everything here is static: no engine step runs, no cache buffer is donated
(donation only invalidates on *execution*), and the whole extraction for a
smoke-config recipe takes a few seconds even on a TP mesh of virtual CPUs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np

from .hlo_model import HloModule, parse_hlo_module

# numpy dtype name → HLO shorthand (the reverse of hlo_model.DTYPE_BYTES keys)
_NP_TO_HLO = {
    "bool": "pred", "int8": "s8", "int16": "s16", "int32": "s32",
    "int64": "s64", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "float16": "f16", "bfloat16": "bf16",
    "float32": "f32", "float64": "f64",
}


def hlo_dtype(dtype) -> str:
    name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    try:
        return _NP_TO_HLO[name]
    except KeyError:
        raise ValueError(f"no HLO shorthand for dtype {name!r}") from None


def _spec_entries(spec, rank: int) -> list:
    """PartitionSpec → a JSON-able full-rank list of axis entries (None /
    "axis" / ["axis", ...] for multi-axis dims); trailing dims replicate."""
    entries: list = [None] * rank
    if spec is None:
        return entries
    for i, e in enumerate(tuple(spec)[:rank]):
        entries[i] = list(e) if isinstance(e, tuple) else e
    return entries


@dataclasses.dataclass
class JitArtifact:
    """One traced+lowered serve path (or a jaxpr-only standalone kernel)."""

    name: str
    kind: str                    # "prefill" | "decode" | "kernel"
    jaxpr: Any = None            # ClosedJaxpr (None when not traced)
    module: Optional[HloModule] = None
    hlo_text: Optional[str] = None
    # (hlo_dtype, dims) of every cache-pool leaf — global and per-device
    cache_leaves_global: list = dataclasses.field(default_factory=list)
    cache_leaves_local: list = dataclasses.field(default_factory=list)
    # "full dequant" element threshold: one slot's ring of one layer's KV
    slot_cache_elems: int = 1 << 62
    # trailing dims of a cache payload leaf ([S, Hkv·hd] for the lane-dense
    # int8 pool, [S, Hkv, hd] for fp) — a materialized
    # s8 convert matching these is a whole-ring dequant (dtype-ledger)
    cache_payload_dims: tuple = ()
    # (hlo_dtype, dims) of the paged pool's page-table leaf (global + local;
    # empty for contiguous pools) — excluded from pool-collective matching
    page_table_shapes: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class LintGraph:
    recipe: str
    mesh_shape: Optional[tuple]
    engine: dict                                  # fingerprint (arch, knobs)
    jits: dict = dataclasses.field(default_factory=dict)
    warmup_shapes: set = dataclasses.field(default_factory=set)
    dispatch_shapes: set = dataclasses.field(default_factory=set)
    # {path: {"dtype", "shape", "spec"}} for params and cache-pool leaves
    param_leaves: dict = dataclasses.field(default_factory=dict)
    cache_spec_leaves: dict = dataclasses.field(default_factory=dict)
    scale_pairs: list = dataclasses.field(default_factory=list)


def _leaf_table(tree, spec_tree, mesh) -> dict:
    """{path: {"dtype", "shape", "spec"}} over a (possibly QTensor-bearing)
    pytree, with normalized full-rank spec entries when a mesh is given."""
    from ...sharding.partition import _walk, spec_paths

    leaves = dict(_walk(tree))
    specs = dict(spec_paths(spec_tree)) if spec_tree is not None else {}
    out = {}
    for path, leaf in leaves.items():
        shape = tuple(int(d) for d in leaf.shape)
        spec = specs.get(path)
        out[path] = {
            "dtype": hlo_dtype(leaf.dtype),
            "shape": list(shape),
            "spec": (_spec_entries(spec, len(shape))
                     if mesh is not None and spec is not None else None),
        }
    return out


def _cache_leaf_shapes(pool) -> tuple[list, list]:
    """(global, per-device) (hlo_dtype, dims) pairs for the pool leaves."""
    glob, loc = [], []
    for name in sorted(pool.cache):
        leaf = pool.cache[name]
        dt = hlo_dtype(leaf.dtype)
        dims = tuple(int(d) for d in leaf.shape)
        glob.append((dt, dims))
        sh = (pool.shardings or {}).get(name) if pool.shardings else None
        loc.append((dt, tuple(sh.shard_shape(dims)) if sh is not None
                    else dims))
    return glob, loc


def graph_from_engine(engine, recipe: str = "",
                      mesh_shape: Optional[tuple] = None,
                      include_kernels: bool = True,
                      compile_hlo: bool = True) -> LintGraph:
    """Extract a ``LintGraph`` from a live ``ServingEngine`` (nothing runs:
    trace + lower + compile only). ``compile_hlo=False`` skips the XLA
    compile (jaxpr-only rules still work — used by the fast --lint path)."""
    cfg = engine.cfg
    pool = engine.pool
    glob, loc = _cache_leaf_shapes(pool)
    table_shapes = []
    if pool.paged:
        pt = pool.cache["page_table"]
        dims = tuple(int(d) for d in pt.shape)
        sh = (pool.shardings or {}).get("page_table") if pool.shardings \
            else None
        table_shapes = [(hlo_dtype(pt.dtype), dims),
                        (hlo_dtype(pt.dtype),
                         tuple(sh.shard_shape(dims)) if sh is not None
                         else dims)]
    k_shape = pool.cache["k"].shape
    if pool.paged:
        # paged leaves are [L, NP, pg, ...], but the jits attend through
        # the gathered DENSE view [L, B, S, ...] — the dtype ledger's
        # "whole-ring dequant" threshold and payload-dims matcher must see
        # the view dims or a paged prefill dequant would sail under them
        payload_dims = (engine.max_len,) + tuple(int(d) for d in k_shape[3:])
    else:
        # [S, Hkv, hd] (fp) or [S, Hkv·hd] (lane-dense int8)
        payload_dims = tuple(int(d) for d in k_shape[2:])
    slot_elems = int(np.prod(payload_dims))      # one slot, one layer
    if mesh_shape is None and engine.mesh is not None:
        mesh_shape = tuple(
            int(engine.mesh.shape[a]) for a in engine.mesh.axis_names)

    graph = LintGraph(
        recipe=recipe,
        mesh_shape=tuple(mesh_shape) if mesh_shape else None,
        engine={
            "arch": cfg.name,
            "num_slots": engine.num_slots,
            "max_len": engine.max_len,
            "prefill_chunk": engine.prefill_chunk,
            "decode_horizon": engine.decode_horizon,
            "kv_bits": engine.kv_bits,
            "fast": engine.fast,
            "page_size": engine.page_size,
        },
        warmup_shapes=set(engine.warmup_shapes()),
        dispatch_shapes=set(engine.dispatch_shapes()),
        scale_pairs=[],
    )

    for name, (jit_fn, impl_fn, args, static_kw) in \
            engine.serve_jit_specs().items():
        jaxpr = jax.make_jaxpr(
            lambda *a, _f=impl_fn, _kw=static_kw: _f(*a, **_kw))(*args)
        hlo_text = module = None
        if compile_hlo:
            hlo_text = jit_fn.lower(*args, **static_kw).compile().as_text()
            module = parse_hlo_module(hlo_text)
        graph.jits[name] = JitArtifact(
            name=name,
            kind="decode" if name.startswith("decode") else "prefill",
            jaxpr=jaxpr, module=module, hlo_text=hlo_text,
            cache_leaves_global=glob, cache_leaves_local=loc,
            slot_cache_elems=slot_elems,
            cache_payload_dims=payload_dims,
            page_table_shapes=table_shapes,
        )

    if include_kernels:
        from ...kernels import serving_kernel_specs

        kspecs = serving_kernel_specs(
            head_dim=cfg.head_dim, n_kv_heads=cfg.n_kv_heads,
            n_q_heads=cfg.n_heads, seq=engine.max_len,
            batch=engine.num_slots, d_in=cfg.d_model, d_out=cfg.d_ff,
        )
        for name, (fn, args, kw) in kspecs.items():
            jaxpr = jax.make_jaxpr(
                lambda *a, _f=fn, _kw=kw: _f(*a, **_kw))(*args)
            graph.jits[name] = JitArtifact(
                name=name, kind="kernel", jaxpr=jaxpr,
                slot_cache_elems=slot_elems,
                cache_payload_dims=payload_dims,
            )

    # sharding-spec tables for scale-coupling
    mesh = engine.mesh
    p_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), engine.params)
    p_specs = None
    if mesh is not None:
        from ...sharding import params_pspecs

        heads = {"n_q": cfg.n_heads, "n_kv": cfg.n_kv_heads}
        p_specs = params_pspecs(p_shapes, mesh, heads, mode="serve")
    graph.param_leaves = _leaf_table(p_shapes, p_specs, mesh)

    c_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pool.cache)
    c_specs = None
    if mesh is not None:
        from ...sharding import serve_cache_pspecs

        c_specs = serve_cache_pspecs(c_shapes, mesh)
    graph.cache_spec_leaves = _leaf_table(c_shapes, c_specs, mesh)

    from ...sharding import payload_scale_pairs

    graph.scale_pairs = payload_scale_pairs(engine.params)
    return graph


def build_graph(recipe: str, mesh_shape: Optional[tuple] = None,
                arch: str = "qwen2-0.5b", *, num_slots: int = 4,
                max_len: int = 32, prefill_chunk: int = 8,
                decode_horizon: int = 8, page_size: Optional[int] = None,
                include_kernels: bool = True) -> LintGraph:
    """Quantize a smoke model through ``recipe`` and extract its lint graph
    under ``mesh_shape`` (None = single device). ``page_size`` lints the
    paged-pool engine (the ``+paged`` recipe-flag geometry). The standard
    entry point for ``python -m repro.analysis.lint`` and the CI lint-graph
    job."""
    from ...configs import get_config
    from ...models import build_model
    from ...pipeline import quantize

    mesh = None
    if mesh_shape:
        need = int(np.prod(mesh_shape))
        if need > jax.device_count():
            raise RuntimeError(
                f"recipe {recipe!r} lints under mesh "
                f"{'x'.join(map(str, mesh_shape))} which needs {need} "
                f"devices but jax sees {jax.device_count()}; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
                f"BEFORE jax initializes"
            )
        from ...launch.mesh import make_production_mesh

        mesh = make_production_mesh(shape=tuple(mesh_shape))

    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    qm = quantize(model, recipe=recipe)
    from ...serving import ServingEngine

    engine = ServingEngine(
        qm.model, qm.params, qm.cfg, num_slots=num_slots, max_len=max_len,
        prefill_chunk=prefill_chunk, decode_horizon=decode_horizon,
        mesh=mesh, page_size=page_size,
    )
    return graph_from_engine(engine, recipe=recipe, mesh_shape=mesh_shape,
                             include_kernels=include_kernels)
