#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print one JSON line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell: it makes the weights from the seed, quantizes them
with the configuration's recipe (``repro.quantize``), builds the serving
engine (``ServingEngine.from_quantized``) and warms up the cell's shapes,
then drives the engine with the cell's traffic for ``--seconds``. Set-up
(``setup_s``) runs from the start of this script to the first request of
the window. After the window the engine is freed and the plain reference
(``chipbench/reference.py``) judges a sample of the served tokens.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
part of the window with the JAX profiler and reports the per-layer metrics,
with the device's busy time and a breakdown. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import layout  # noqa: E402
from chipbench.context import Context  # noqa: E402

# the part of the window a traced run records, at most
TRACE_S = 3.0


class BenchError(RuntimeError):
    """The run cannot go on; the message says why."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _configure_jax(root) -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout,
    keeping every program however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices "
                         "only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


class _Compiles:
    """Counts the programs compiled, or loaded from the persistent cache,
    from the moment it is made."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1


def _memory(devs) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    return {k: max(s.get(k, 0) for s in stats)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def dense_pool_bytes_per_position(m: dict, serving: dict) -> int:
    """The dense-GQA pool's bytes per slot position: K and V of every layer
    (int8 with a float32 scale per head, or the compute type) and the
    position's int32 bookkeeping."""
    if serving["kv_bits"] == 8:
        per = m["L"] * 2 * m["Hkv"] * (m["hd"] + 4)
    else:
        per = m["L"] * 2 * m["Hkv"] * m["hd"] * 2
    return per + 4


def pool_bytes(ctx) -> int:
    """Bytes of the engine's KV pool, by the architecture file's count per
    position where it gives one."""
    per = getattr(ctx.arch, "pool_bytes_per_position",
                  dense_pool_bytes_per_position)
    s = ctx.serving
    return s["num_slots"] * s["max_len"] * per(ctx.dims, s)


def pool_share(prog_model, serving: dict, mesh) -> float:
    """The fullest device's share of the KV pool's bytes: 1 on one chip;
    on a mesh, read off the program's own serve-mode cache shardings, under
    which a leaf whose slots or heads do not divide stays whole on every
    device."""
    if mesh is None:
        return 1.0
    import jax
    import jax.numpy as jnp
    from repro.sharding import named_shardings, serve_cache_pspecs

    cache = jax.eval_shape(lambda: prog_model.init_cache(
        serving["num_slots"], serving["max_len"],
        dtype=jnp.dtype(prog_model.cfg.dtype), per_slot=True,
        kv_bits=serving["kv_bits"]))
    placed = named_shardings(serve_cache_pspecs(cache, mesh), mesh)

    def nbytes(shape, leaf):
        return math.prod(shape) * leaf.dtype.itemsize

    whole = sum(nbytes(a.shape, a) for a in jax.tree.leaves(cache))
    part = sum(jax.tree.leaves(jax.tree.map(
        lambda a, sh: nbytes(sh.shard_shape(a.shape), a), cache, placed)))
    return part / whole


def _check_pool_fits(ctx, mem: dict, share: float) -> None:
    """The pool, at the fullest device's ``share`` of it, has to fit in
    half of that device's free memory: the warm-up holds a second copy."""
    need = math.ceil(pool_bytes(ctx) * share)
    free = mem["bytes_limit"] - mem["bytes_in_use"]
    log(f"memory after quantize: bytes_limit {mem['bytes_limit']}, "
        f"bytes_in_use {mem['bytes_in_use']}, peak {mem['peak_bytes_in_use']}"
        f"; pool of {ctx.serving['num_slots']} slots x "
        f"{ctx.serving['max_len']} positions needs {need} bytes a device, "
        f"half of the free {free} is {free // 2}")
    if mem["bytes_limit"] and need > free // 2:
        raise BenchError(
            f"the KV pool ({need} bytes a device) does not fit in half of the "
            f"free device memory ({free} bytes): the warm-up holds a second "
            "copy")


def _mesh(ctx, devs):
    """The cell's mesh over its devices, from the configuration's
    ``serving.mesh`` ({"data": d, "model": m}), or None on one chip."""
    shape = ctx.serving.get("mesh")
    if len(devs) == 1 and not shape:
        return None
    if not shape or shape["data"] * shape["model"] != len(devs):
        raise BenchError(f"the cell runs on {len(devs)} chips; its "
                         f"configuration's serving.mesh is {shape!r}")
    import numpy as np
    from jax.sharding import Mesh

    grid = np.array(devs).reshape(shape["data"], shape["model"])
    return Mesh(grid, ("data", "model"))


def _placement(ctx, mesh, ModelConfig) -> Optional[dict]:
    """{leaf path: sharding} of the float32 weights under the program's own
    serve-mode partition specs, so that they are made where they are
    served; None without a mesh."""
    if mesh is None:
        return None
    import jax
    from repro.sharding import named_shardings, params_pspecs

    from chipbench import model

    shapes = {p: jax.ShapeDtypeStruct(s, "float32")
              for p, s in model.param_shapes(ctx.config, ctx.arch).items()}
    heads = {"n_q": ctx.dims["Hq"], "n_kv": ctx.dims["Hkv"]}
    specs = named_shardings(
        params_pspecs(model.nest(shapes), mesh, heads, mode="serve"), mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    return {"/".join(k.key for k in path): s for path, s in flat}


def _quantize(ctx, seed, repro, build_model, ModelConfig, placement):
    """The quantized model, and the seconds spent making weights (None
    where they are made layer by layer, between the layers' quantizing).

    Without ``weights.by_layer``: every float32 weight in one call, then
    the recipe over the whole model. With it: the leaves outside the layers
    once, then for each layer its float32 weights from the seed, the recipe
    over that layer alone, and only the result kept, written into its place
    in the stacked layers. The serve recipes' stages (fold_norm, CLE,
    bias_absorb, pack, kv_cache) act within a layer, so layer by layer
    quantizes exactly as the whole model does
    (``tests/test_chipbench_groups.py``)."""
    import dataclasses

    import jax

    from chipbench import model

    cfg, arch, recipe = ctx.config, ctx.arch, ctx.serving["recipe"]
    whole = model.program_config(cfg, ModelConfig, arch)
    if not model.by_layer(cfg):
        t = time.perf_counter()
        params = model.make_params(cfg, seed, arch, placement)
        jax.block_until_ready(params)
        t_weights = time.perf_counter() - t
        qm = repro.quantize(build_model(whole), params=params, recipe=recipe)
        jax.block_until_ready(qm.params)
        del params
        return qm, t_weights
    on_outer, inner = model.split_shardings(placement)
    outer = model.make_outer(cfg, seed, arch, on_outer)
    one = build_model(dataclasses.replace(whole, n_layers=1))
    stacked = qm = None
    for i in range(whole.n_layers):
        qm = repro.quantize(one, params={
            **outer, "blocks": model.make_layers(cfg, seed, i, 1, arch, inner)},
            recipe=recipe)
        if stacked is None:
            stacked = _zeros_stacked(qm.params["blocks"], whole.n_layers)
        stacked = _layer_writer()(stacked, qm.params["blocks"], i)
        jax.block_until_ready(stacked)
        mem = _memory(jax.devices())
        log(f"layer {i} quantized; bytes_in_use {mem['bytes_in_use']}, "
            f"peak {mem['peak_bytes_in_use']}")
    cfg_q = dataclasses.replace(qm.cfg, n_layers=whole.n_layers)
    params = {**qm.params, "blocks": stacked}
    return dataclasses.replace(qm, cfg=cfg_q, model=build_model(cfg_q),
                               params=params), None


def _zeros_stacked(part, L: int):
    """Zeros of ``part``'s leaves with L layers, where each leaf lies."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda p: jax.tree.map(
            lambda a: jnp.zeros((L,) + a.shape[1:], a.dtype), p),
        out_shardings=jax.tree.map(lambda a: a.sharding, part))(part)


@functools.lru_cache(maxsize=None)
def _layer_writer():
    """jit of (stacked, part, lo) -> stacked with ``part`` written at layer
    ``lo``, in place (the stacked layers are donated)."""
    import jax

    def put(stacked, part, lo):
        return jax.tree.map(
            lambda s, p: jax.lax.dynamic_update_slice_in_dim(s, p, lo, 0),
            stacked, part)

    return jax.jit(put, donate_argnums=0)


class Setup:
    """What set-up leaves for the window: the cell, the run's context, the
    warmed engine, and the devices it runs on."""

    def __init__(self, cell, ctx, engine, devs, Request):
        self.cell, self.ctx, self.engine = cell, ctx, engine
        self.devs, self.Request = devs, Request


def prepare(args, root=layout.ROOT, bench_dir=None,
            require_tpu: bool = True) -> Setup:
    """Everything before the window: the program from this checkout, the
    compile cache, the device check, weights from the seed, quantization,
    the engine and its warm-up."""
    cell = layout.load_cell(args.workload, root, bench_dir)
    sys.path.insert(0, str(root / "src"))
    try:
        import repro
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}") from None
    _configure_jax(root)
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.serving import ServingEngine
    from repro.serving.scheduler import Request

    devs = _devices(cell.chips, require_tpu)[:cell.chips]
    ctx = Context(cell, args.seed, devs[0].device_kind,
                  require_peaks=require_tpu)
    s = ctx.serving
    mesh = _mesh(ctx, devs)
    log(f"cell {cell.name}: {ctx.config['name']} recipe {s['recipe']}, "
        f"{s['num_slots']} slots x {s['max_len']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}, device "
        f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
        f"{'' if mesh is None else f', mesh {dict(mesh.shape)}'}")

    t = time.perf_counter()
    qm, t_weights = _quantize(ctx, args.seed, repro, build_model, ModelConfig,
                              _placement(ctx, mesh, ModelConfig))
    gc.collect()
    t_quant = time.perf_counter() - t
    _check_pool_fits(ctx, _memory(devs), pool_share(qm.model, s, mesh))
    t = time.perf_counter()
    engine = ServingEngine.from_quantized(
        qm, num_slots=s["num_slots"], max_len=s["max_len"],
        prefill_chunk=s["prefill_chunk"], decode_horizon=s["decode_horizon"],
        kv_bits=s["kv_bits"], mesh=mesh)
    del qm
    engine.warmup()
    t_engine = time.perf_counter() - t
    made = (f"weights and quantize layer by layer {t_quant:.3f} s"
            if t_weights is None else f"weights {t_weights:.3f} s, quantize "
            f"{t_quant - t_weights:.3f} s")
    log(f"set-up split: {made}, engine build + warmup {t_engine:.3f} s")
    return Setup(cell, ctx, engine, devs, Request)


def run_cell(args, root=layout.ROOT, bench_dir=None,
             require_tpu: bool = True, control: bool = False) -> dict:
    """One run of a cell; returns the result object. ``control`` also reads
    the control's gap on the same sample (not in the benchmark's runs)."""
    su = prepare(args, root, bench_dir, require_tpu)
    import jax

    from chipbench import check, client as client_lib, traffic

    cell, ctx, engine, devs = su.cell, su.ctx, su.engine, su.devs
    s = ctx.serving
    compiles = _Compiles()
    tracer = _Tracer(args, ctx) if args.trace else None
    span = tracer.span if tracer else _no_span
    cl = client_lib.Client(engine, su.Request, span)
    if tracer:
        tracer.attach(cl)
    mix = cell.mix
    on_tick = tracer.tick if tracer else (lambda now: None)

    def on_open(t_open):
        ctx.setup_s = t_open - T_START
        compiles.n = 0
        if tracer:
            tracer.plan(t_open)

    if mix["loop"] == "closed":
        stream = traffic.closed_loop(mix, args.seed, ctx.dims["V"])
        ctx.window = client_lib.run_closed(
            cl, stream, s["num_slots"], mix["backlog_per_slot"],
            args.seconds, on_open, on_tick,
            finished=mix["sample"]["requests"])
    else:
        specs = traffic.open_loop(mix, cell.pinned["rate_per_s"],
                                  args.seconds, args.seed, ctx.dims["V"])
        ctx.window = client_lib.run_open(cl, specs, args.seconds, on_open,
                                         on_tick)
    ctx.records = cl.recs
    ctx.memory = _memory(devs)
    log(f"programs compiled or loaded in the window: {compiles.n}")
    log(f"window: {ctx.window[1] - ctx.window[0]:.3f} s, "
        f"{len(cl.recs)} requests sent, generator lateness p95 "
        f"{client_lib.lateness_p95_ms(cl):.3f} ms; memory after the window "
        f"{ctx.memory}")
    if tracer:
        tracer.finish()
        ctx.traced = tracer.reduced()

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in getattr(cell, kind):
        v = m.read(ctx)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    for line in ctx.notes:
        log(line)

    # the window's state is freed before the reference runs
    cl.engine = None
    su.engine = None
    del engine, cl
    gc.collect()
    shown = len(ctx.notes)
    verdict = check.judge(ctx, with_control=control)
    for line in ctx.notes[shown:]:
        log(line)
    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(ctx.memory["peak_bytes_in_use"])},
    }
    if control:
        result["control_correct"] = verdict["control_correct"]
    if tracer:
        result["device"]["busy_s"] = ctx.traced["busy_s"]
        result["device"]["window_s"] = ctx.traced["window_s"]
        result["breakdown"] = ctx.traced["breakdown"]
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _no_span(name):
    return contextlib.nullcontext()


class _Tracer:
    """Traces up to ``TRACE_S`` seconds in the middle of the window, and
    snapshots the counters at its two ends."""

    def __init__(self, args, ctx):
        import jax

        self.jax, self.ctx = jax, ctx
        self.seconds = min(TRACE_S, args.seconds)
        self.offset = max(0.0, (args.seconds - self.seconds) / 2)
        self.keep = args.keep_trace
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.state = "idle"
        self.snaps = []
        self.client = None

    def span(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def plan(self, t_open):
        self.t_start = t_open + self.offset
        self.t_stop = self.t_start + self.seconds

    def _snap(self):
        c = self.client
        e = c.engine
        return {"t": time.perf_counter(),
                "stats": dict(e.stats), "prefilled": c.prefilled_tokens(),
                "delivered": c.delivered()}

    def tick(self, now):
        if self.state == "idle" and now >= self.t_start:
            self.jax.profiler.start_trace(self.dir)
            self.win = self.jax.profiler.TraceAnnotation(
                "bench.trace_window")
            self.win.__enter__()
            self.snaps.append(self._snap())
            self.state = "on"
        elif self.state == "on" and now >= self.t_stop:
            self.snaps.append(self._snap())
            self.win.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.state = "done"

    def attach(self, client):
        self.client = client

    def finish(self):
        if self.state == "on":
            self.snaps.append(self._snap())
            self.win.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.state = "done"
        if self.state != "done":
            raise BenchError("the window closed before the trace started")

    def reduced(self) -> dict:
        import shutil

        from chipbench import trace as trace_lib

        t0 = time.perf_counter()
        tr = trace_lib.load(self.dir)
        if self.keep:
            os.makedirs(self.keep, exist_ok=True)
            trace_lib.save_json(tr, os.path.join(self.keep, "trace.json.gz"))
            for f in glob.glob(os.path.join(self.dir, "plugins", "profile",
                                            "*", "*.xplane.pb")):
                if os.path.getsize(f) < 40 << 20:
                    shutil.copy(f, self.keep)
        shutil.rmtree(self.dir, ignore_errors=True)
        out = self.ctx.reduce_trace(tr, self.snaps)
        log(f"trace read and reduced in {time.perf_counter() - t0:.3f} s")
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace events here")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args)
    except (BenchError, layout.LayoutError) as e:
        log(f"error: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
