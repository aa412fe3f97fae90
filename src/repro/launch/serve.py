"""Serving driver: quantize a model through the pipeline API and serve it
with the continuous-batching engine (INT8 weights via the QTensor kernel
dispatch, slot-based KV-cache pool, FIFO admission).

    python -m repro.launch.serve --arch qwen2-0.5b --smoke --quantize w8a16
    python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --recipe serve-w8a8 --verbose --save /tmp/qwen_int8
    python -m repro.launch.serve --load /tmp/qwen_int8
    python -m repro.launch.serve --arch qwen2-0.5b --smoke --trace 20

    # tensor-parallel sharded serving (8 virtual CPU devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.serve --arch qwen2-0.5b --smoke --mesh 2x4

The flag surface is the typed ``ServeConfig`` dataclass (serve_config.py) —
argparse is derived from it, and ``serve(config)`` is the public API peer
of ``repro.quantize``:

    import repro
    repro.serve(repro.ServeConfig(arch="qwen2-0.5b", smoke=True, trace=20))
"""
from __future__ import annotations

import dataclasses
import os
import signal
import time
from pathlib import Path

import jax
import numpy as np

from ..configs import get_config
from ..data import calibration_tokens
from ..models import build_model
from ..pipeline import QuantizedModel, quantize
from ..serving import (
    QueueFull,
    Request,
    ServingEngine,
    open_loop_trace,
    required_cache_len,
    synthetic_trace,
)
from .serve_config import (          # noqa: F401  (re-exported API surface)
    ServeConfig,
    ServeConfigError,
    build_parser,
)


def _check_servable(cfg, what):
    if cfg.family in ("ssm", "hybrid") or cfg.is_encdec:
        raise ServeConfigError(
            f"{what}: the continuous-batching engine serves "
            f"attention-family decoder-only models; quantize "
            f"{cfg.family!r} archs via repro.pipeline.cli and run them "
            f"through model.prefill/decode_step directly"
        )


class ServeResults(dict):
    """``serve``'s return value: the engine's ``{rid: RequestResult}`` map,
    plus the ``engine`` that served it and the run's wall-clock split —
    ``setup_seconds`` (quantize or load, engine build, warmup compiles) and
    ``serve_seconds`` (submission to drain)."""

    def __init__(self, results, engine, setup_seconds, serve_seconds):
        super().__init__(results)
        self.engine = engine
        self.setup_seconds = setup_seconds
        self.serve_seconds = serve_seconds


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself), else ``.jax_cache`` at the root of this checkout. Entry points
    call this; importing the package never touches the cache."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def serve(config: ServeConfig):
    """Quantize (or ``load``) a model and serve it — the whole driver behind
    ``python -m repro.launch.serve``, callable as ``repro.serve(config)``.
    Returns a ``ServeResults``: the engine's ``{rid: RequestResult}`` map
    (with the engine and the set-up/serve wall times attached). Invalid or
    conflicting configuration raises ``ServeConfigError``."""
    config = dataclasses.replace(config).validate()
    t_setup = time.time()
    cli_mesh = config.mesh        # pre-merge: distinguishes --mesh vs artifact

    qm = None
    if config.load:
        qm = QuantizedModel.load(config.load)
        _check_servable(qm.cfg, f"--load {config.load} (arch {qm.cfg.name})")
        # the artifact's kv_cache stage already quantized FOR its recorded
        # precision (and its weights ARE the recorded recipe) — one
        # precedence rule covers every CLI-vs-artifact field
        config, notes = config.with_artifact(ServeConfig.from_artifact(qm))
        for n in notes:
            print(f"note: {n}")
        cfg, model, params = qm.cfg, qm.model, qm.params
        print(f"loaded QuantizedModel from {config.load} "
              f"(arch {cfg.name}, recipe {qm.recipe.name!r})")
    else:
        cfg = get_config(config.arch, smoke=config.smoke)
        _check_servable(cfg, f"--arch {config.arch}")
        model = build_model(cfg)
        if config.recipe or config.quantize != "none":
            recipe = config.recipe
            if recipe is None:
                from ..pipeline.recipes import BUILTIN_RECIPES

                recipe = (f"serve-{config.quantize}-kv8"
                          if config.kv_bits == 8
                          else f"serve-{config.quantize}")
                # --mesh prefers the -tp recipe variant (adds the shard
                # stage, so the artifact records the parallelism plan); the
                # engine serves any recipe sharded either way
                if config.mesh and f"{recipe}-tp" in BUILTIN_RECIPES:
                    recipe = f"{recipe}-tp"
            t0 = time.time()
            qm = quantize(model, recipe=recipe)
            print(f"quantize: recipe {recipe!r} in {time.time() - t0:.1f} s")
            if (config.kv_bits is not None
                    and qm.cfg.kv_cache_bits != config.kv_bits):
                # an explicit --recipe may not carry a kv_cache stage: fold
                # the requested KV precision into the artifact so a --save /
                # --load round trip serves with the same cache as this run
                qm.cfg = dataclasses.replace(
                    qm.cfg, kv_cache_bits=config.kv_bits)
                qm.model = build_model(qm.cfg)
            cfg, model, params = qm.cfg, qm.model, qm.params
        else:
            params = model.init(jax.random.PRNGKey(0))

    # ------------------------------------------------------------------ mesh
    mesh = None
    shape = config.mesh
    mesh_src = ("--mesh" if shape is not None and shape == cli_mesh
                else "artifact-recorded mesh" if shape is not None else None)
    if mesh_src == "artifact-recorded mesh":
        need = int(np.prod(shape))
        if need > jax.device_count():
            # artifact-recorded topology on a smaller host: serve unsharded
            print(f"note: {mesh_src} {'x'.join(map(str, shape))} needs "
                  f"{need} devices but jax sees {jax.device_count()}; on CPU "
                  f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                  f"{need} — serving single-device")
            shape = None
    if shape is not None:
        from .mesh import make_production_mesh

        mesh = make_production_mesh(shape=shape)
        print(f"mesh ({mesh_src}): "
              f"{dict(zip(mesh.axis_names, (mesh.shape[a] for a in mesh.axis_names)))}")
    elif qm is not None and qm.shard_mode and mesh_src is None:
        print(f"note: artifact records {qm.shard_mode!r} sharding; pass "
              f"--mesh DxM to serve it across a device mesh")

    if qm is not None:
        s = qm.serving_summary()
        print(f"quantized (recipe {qm.recipe.name!r}): "
              f"{s['int8_bytes'] / 1e6:.1f} MB "
              f"vs fp32 {s['fp32_bytes'] / 1e6:.1f} MB "
              f"({s['compression']:.2f}x)")
        if config.verbose:
            from ..pipeline.cli import print_site_sqnr

            print_site_sqnr(qm)
        if config.save:
            qm.save(config.save, mesh=mesh)
            print(f"saved QuantizedModel to {config.save}"
                  + (" (serve-mode specs recorded)"
                     if mesh is not None and qm.shard_mode else ""))

    # ---------------------------------------------------------------- engine
    C = config.prefill_chunk
    if config.trace:
        p_lo, g_lo = min(4, config.prompt_len), min(4, config.gen_len)
        if config.serve_async:
            # two priority classes so the shedder's lowest-class rung has a
            # victim population (class 1 survives rung 1)
            requests = open_loop_trace(
                config.trace_seed, config.trace, config.qps,
                vocab_size=cfg.vocab_size,
                prompt_lens=(p_lo, config.prompt_len),
                gen_lens=(g_lo, config.gen_len), priority_levels=2,
            )
        else:
            requests = synthetic_trace(
                config.trace_seed, config.trace, vocab_size=cfg.vocab_size,
                prompt_lens=(p_lo, config.prompt_len),
                gen_lens=(g_lo, config.gen_len), mean_interarrival=1.0,
            )
        if config.deadline is not None:
            requests = [dataclasses.replace(
                r, deadline=r.arrival + config.deadline) for r in requests]
        rate = f" at {config.qps:g} req/tick" if config.serve_async else ""
        print(f"trace: {len(requests)} requests, "
              f"prompt {p_lo}..{config.prompt_len}, "
              f"gen {g_lo}..{config.gen_len}, Poisson arrivals{rate}")
    else:
        prompts = np.asarray(
            calibration_tokens(0, config.batch, config.prompt_len,
                               cfg.vocab_size)
        )
        requests = [
            Request(rid=i, prompt=prompts[i],
                    max_new_tokens=config.gen_len,
                    deadline=(config.deadline
                              if config.deadline is not None else None))
            for i in range(config.batch)
        ]

    need = max(
        required_cache_len(len(r.prompt), r.max_new_tokens, C)
        for r in requests
    )
    max_len = config.max_len or need
    straggler = None
    if config.straggler_threshold is not None:
        from ..runtime.fault_tolerance import StragglerMonitor

        straggler = StragglerMonitor(threshold=config.straggler_threshold)
    engine = ServingEngine(
        model, params, cfg, num_slots=config.slots, max_len=max_len,
        prefill_chunk=C, decode_horizon=config.decode_horizon,
        fast=not config.reference, kv_bits=config.kv_bits, mesh=mesh,
        page_size=config.page_size, num_pages=config.num_pages,
        prefix_reuse=config.prefix_reuse, max_queue=config.max_queue,
        straggler=straggler,
    )
    layout = (f"paged ({engine.pool.num_pages} pages x {engine.page_size} "
              f"positions, prefix reuse "
              f"{'on' if engine.prefix_index is not None else 'off'})"
              if engine.paged
              else f"{config.slots} slots x {max_len} positions")
    print(f"kv cache: {'int8' if engine.kv_bits == 8 else 'fp'} "
          f"({engine.pool.bytes_per_slot() / 1e3:.1f} kB/slot, {layout})")
    if config.lint:
        from ..analysis.lint import lint_engine

        recipe_name = qm.recipe.name if qm is not None else "fp32"
        t0 = time.time()
        findings = lint_engine(engine, recipe_name)
        n_err = sum(f.severity == "error" for f in findings)
        print(f"--lint: {'FAIL' if n_err else 'pass'} "
              f"({time.time() - t0:.1f} s; warn-only at runtime — serving "
              f"continues)")
    if config.warmup:
        t0 = time.time()
        engine.warmup()
        print(f"warmup: compiled serving shapes in {time.time() - t0:.1f} s")
    setup_s = time.time() - t_setup

    # SIGTERM → graceful drain: stop admitting, finish in-flight + parked,
    # report, exit 0 (modeled on runtime.fault_tolerance.FaultTolerantLoop).
    t0 = time.time()
    sigterm: list = []   # the async path drains on normal close too, so the
    #                      report needs to know whether SIGTERM actually fired
    if config.serve_async:
        import asyncio

        from ..serving import (
            SLO,
            AsyncClient,
            AsyncServer,
            CircuitBreaker,
            RetryPolicy,
            ShedPolicy,
            run_open_loop,
            summarize,
        )

        sp = config.shed_pressure
        server = AsyncServer(
            engine,
            breaker=CircuitBreaker(cooldown=config.breaker_cooldown),
            shed=ShedPolicy(shed_pressure=sp,
                            tighten_pressure=min(1.0, 1.5 * sp),
                            refuse_pressure=min(1.0, 2.0 * sp)),
        )
        client = AsyncClient(
            server, RetryPolicy(max_attempts=config.retry_attempts),
            seed=config.trace_seed)
        prev_handler = signal.signal(
            signal.SIGTERM,
            lambda *_: (sigterm.append(1), server.drain()))
        try:
            outcomes = asyncio.run(run_open_loop(
                server, client, requests, timeout=config.timeout))
        finally:
            signal.signal(signal.SIGTERM, prev_handler)
        dt = time.time() - t0
        slo = SLO()
        summary = summarize(outcomes, slo=slo)
        print(f"async front-end: offered {summary['offered_qps']:.3f} "
              f"req/tick, goodput {summary['goodput_qps']:.3f} req/tick "
              f"({summary['goodput_fraction']:.0%} of offered; SLO: ttft <= "
              f"{slo.ttft:g}, per-token <= {slo.per_token:g} ticks)")
        print(f"  ttft p50/p99 {summary['ttft_p50']:.1f}/"
              f"{summary['ttft_p99']:.1f} ticks, per-token p50/p99 "
              f"{summary['per_token_p50']:.2f}/"
              f"{summary['per_token_p99']:.2f} ticks, "
              f"mean attempts {summary['mean_attempts']:.2f}")
        srv = server.stats
        print("  admission: " + ", ".join(
            f"{k}={srv[k]}" for k in
            ("submitted", "accepted", "shed_breaker", "shed_priority",
             "shed_refused", "shed_queue", "deadlines_tightened"))
            + f"; breaker opens={server.breaker.opens}")
        results = engine.results
    else:
        prev_handler = signal.signal(
            signal.SIGTERM,
            lambda *_: (sigterm.append(1), engine.request_drain()))
        try:
            shed = []
            for r in requests:
                try:
                    engine.submit(r)
                except QueueFull:
                    shed.append(r.rid)
            results = engine.run()
        finally:
            signal.signal(signal.SIGTERM, prev_handler)
        dt = time.time() - t0
    if sigterm:
        print(f"drain: SIGTERM received — admission stopped, "
              f"{engine.scheduler.pending()} queued requests unserved")
    gen = engine.stats["generated_tokens"]
    path = "reference (stepwise)" if config.reference else \
        f"fast (decode horizon {config.decode_horizon})"
    if mesh is not None:
        path += f", sharded {'x'.join(str(mesh.shape[a]) for a in mesh.axis_names)}"
    print(f"served {len(results)} requests / {gen} generated tokens "
          f"in {dt*1e3:.1f} ms ({gen / max(dt, 1e-9):.1f} tok/s, "
          f"{path} path)")
    print(f"engine: {engine.stats['decode_steps']} decode steps in "
          f"{engine.stats['decode_dispatches']} dispatches, "
          f"{engine.stats['prefill_chunks']} prefill chunks in "
          f"{engine.stats['prefill_dispatches']} dispatches, "
          f"{engine.syncs_per_token():.2f} host syncs/token, "
          f"mean slot occupancy {engine.mean_occupancy():.2f}")
    faults = {k: engine.stats[k] for k in
              ("shed", "preempted", "resumed", "cancelled", "expired",
               "quarantined", "straggler_steps")}
    by_status: dict[str, int] = {}
    for res in results.values():
        by_status[res.status] = by_status.get(res.status, 0) + 1
    if any(faults.values()) or set(by_status) - {"ok"}:
        print("faults: " + ", ".join(f"{k}={v}" for k, v in faults.items())
              + f" (straggler threshold "
                f"{engine.stats['straggler_threshold']:g}x step EMA)")
        print("results by status: " +
              ", ".join(f"{k}={v}" for k, v in sorted(by_status.items())))
    results = ServeResults(results, engine, setup_s, dt)
    if not results:
        return results
    first = results[min(results)]
    print(f"sample token ids (rid {first.rid}):", first.tokens[:12])
    return results


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    try:
        return serve(ServeConfig.from_args(args))
    except ServeConfigError as e:
        ap.error(str(e))


if __name__ == "__main__":
    use_compile_cache()
    main()
