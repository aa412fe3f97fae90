"""Production training driver: mesh → sharded train_step → fault-tolerant
loop (checkpoint/restore, preemption, stragglers) → metrics.

On this CPU container it runs reduced configs end-to-end (the same code path
the dry-run proves out at 512 devices):

    python -m repro.launch.train --arch qwen2-0.5b --smoke --steps 100
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer
from ..configs import get_config
from ..data import TokenStream
from ..optim import adamw_init
from ..runtime import FaultTolerantLoop, StragglerMonitor
from ..sharding import named_shardings, params_pspecs
from .steps import configure_sharding_hints, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="results/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    model, train_step = make_train_step(cfg, lr_cfg={
        "peak_lr": 1e-3, "warmup": 20, "total": args.steps})
    configure_sharding_hints(cfg, mesh)

    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    heads = {"n_q": cfg.n_heads, "n_kv": cfg.n_kv_heads}
    p_sh = named_shardings(params_pspecs(params, mesh, heads), mesh)
    params = jax.device_put(params, p_sh)

    stream = TokenStream(seed=0, shard=0, n_shards=1,
                         batch_per_shard=args.batch, seq=args.seq,
                         vocab=cfg.vocab_size)

    with mesh:
        jitted = jax.jit(train_step, donate_argnums=(0, 1))

        def step_fn(state, batch):
            params, opt = state
            params, opt, metrics = jitted(params, opt, batch)
            return (params, opt), {"loss": float(metrics["loss"])}

        ckpt = Checkpointer(args.ckpt_dir, keep=2)
        mon = StragglerMonitor(threshold=3.0)
        loop = FaultTolerantLoop(step_fn, lambda s: stream.batch(s), ckpt,
                                 ckpt_every=args.ckpt_every, straggler=mon)
        state = (params, opt)
        start = 0
        if args.resume and ckpt.latest_step() is not None:
            state, start = ckpt.restore(state)
            print(f"resumed from step {start}")

        t0 = time.time()
        losses = []
        orig_step = loop.step_fn

        def logging_step(state, batch):
            state, m = orig_step(state, batch)
            losses.append(m["loss"])
            n = len(losses) + start
            if n % args.log_every == 0:
                print(f"step {n}: loss {np.mean(losses[-args.log_every:]):.4f} "
                      f"({(time.time() - t0) / len(losses):.2f}s/step)",
                      flush=True)
            return state, m

        loop.step_fn = logging_step
        state, end = loop.run(state, start, args.steps - start)

    print(f"done at step {end}; loss {np.mean(losses[-10:]):.4f} "
          f"(start {np.mean(losses[:10]):.4f}); "
          f"straggler events: {loop.metrics.straggler_events}; "
          f"retries: {loop.metrics.retries}")
    return state


if __name__ == "__main__":
    main()
