#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which 90%
of requests meet both latency limits with no growing backlog.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --rates 0.8,1.0,1.2 [--seconds <s>] [--probe 0] [--out result.json]

One process and one set-up (``run.prepare``). First it serves, one at a
time and alone, ``--probe`` requests with a 512-token prompt and the mix's
median output, and sets the limits at 5x their median time to first token
and 5x their median time per output token (DistServe's SLO scale); with
``--probe 0`` it takes the limits pinned in the cell's file. Then it runs
one window of the cell's traffic at each rate, in the order given, for the
benchmark's ``run_seconds`` unless ``--seconds`` says otherwise, draining
the engine between them, and stops after the first rate that fails. The
backlog grows where, at the window's close, a request still waits for a
slot. Run by hand, once, when a cell is defined; the benchmark's runs use
the rate and limits pinned in the cell's file.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
from chipbench import client as client_lib, layout, traffic  # noqa: E402

SLO_SCALE = 5.0
ATTAIN = 0.9
# past the window, how long a rate's requests may still finish; those that
# do not count as misses
DRAIN_S = 30.0


def _drain(cl, limit=float("inf")):
    """Serve until nothing is outstanding, or for ``limit`` seconds."""
    end = time.perf_counter() + limit
    while cl.outstanding and time.perf_counter() < end:
        cl.step()


def _ttft_tpot(r):
    """A request's TTFT and TPOT over all its tokens (s); inf where it
    never got its first token or did not finish."""
    ttft = (r.first - r.due) if r.first is not None else float("inf")
    if r.status != "ok":
        return ttft, float("inf")
    tpot = client_lib.tpot_s(r, float("inf"))
    return ttft, 0.0 if tpot is None else tpot


def unloaded(su, n: int, prompt: int, output: int, seed: int) -> tuple:
    """Median TTFT and TPOT (s) of n requests served alone."""
    import numpy as np

    cl = client_lib.Client(su.engine, su.Request, run._no_span)
    rng = np.random.default_rng([seed, 11])
    out = []
    for i in range(n):
        spec = traffic.Spec(i, rng.integers(0, su.ctx.dims["V"], prompt,
                                            dtype=np.int32), output, 0.0)
        cl.submit(spec, i, time.perf_counter())
        _drain(cl)
        out.append(_ttft_tpot(cl.recs[i]))
    return (statistics.median(t for t, _ in out),
            statistics.median(p for _, p in out))


def at_rate(su, rate: float, seconds: float, seed: int, limits) -> dict:
    cl = client_lib.Client(su.engine, su.Request, run._no_span)
    specs = traffic.open_loop(su.cell.mix, rate, seconds, seed,
                              su.ctx.dims["V"])
    at_close = {}

    def on_open(t_open):
        at_close["t"] = t_open + seconds

    def on_tick(now):
        if "queued" not in at_close and now >= at_close["t"]:
            at_close["queued"] = su.engine.scheduler.pending()
            at_close["unserved"] = sum(r.first is None
                                       for r in cl.recs.values())

    window = client_lib.run_open(cl, specs, seconds, on_open, on_tick)
    _drain(cl, DRAIN_S)
    recs = [cl.recs[i] for i in sorted(cl.recs)]
    v = [_ttft_tpot(r) for r in recs]
    met = sum(t <= limits[0] and p <= limits[1] for t, p in v)
    ttft = sorted(t for t, _ in v)
    tpot = sorted(p for _, p in v)
    return {"rate_per_s": rate, "requests": len(v),
            "window_s": window[1] - window[0],
            "attainment": met / len(v),
            "ttft_p50_ms": 1e3 * ttft[len(v) // 2],
            "ttft_p95_ms": 1e3 * ttft[int(0.95 * len(v))],
            "tpot_p50_ms": 1e3 * tpot[len(v) // 2],
            "tpot_p95_ms": 1e3 * tpot[int(0.95 * len(v))],
            "queued_at_close": at_close.get("queued"),
            "unserved_at_close": at_close.get("unserved"),
            "growing": at_close.get("queued", 1) > 0,
            "lateness_p95_ms": client_lib.lateness_p95_ms(cl)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--probe", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.seconds is None:
        bench = json.loads((layout.ROOT / "BENCHMARK.json").read_text())
        args.seconds = float(bench["run_seconds"])
    args.trace, args.keep_trace = 0, None
    su = run.prepare(args)
    res = {"workload": args.workload, "seed": args.seed,
           "device": su.devs[0].device_kind, "seconds": args.seconds}
    if args.probe:
        out_med = int(su.cell.mix["output"]["median"])
        ttft, tpot = unloaded(su, args.probe, 512, out_med, args.seed)
        limits = (SLO_SCALE * ttft, SLO_SCALE * tpot)
        res["unloaded"] = {"prompt": 512, "output": out_med,
                           "ttft_p50_ms": 1e3 * ttft,
                           "tpot_p50_ms": 1e3 * tpot}
    else:
        limits = (su.cell.pinned["ttft_limit_ms"] / 1e3,
                  su.cell.pinned["tpot_limit_ms"] / 1e3)
    res.update(ttft_limit_ms=1e3 * limits[0], tpot_limit_ms=1e3 * limits[1],
               rates=[])
    print(json.dumps(res), flush=True)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = at_rate(su, rate, args.seconds, args.seed + 1 + i, limits)
        res["rates"].append(row)
        print(json.dumps(row), flush=True)
        if row["attainment"] < ATTAIN or row["growing"]:
            break
        knee = rate
    res["knee_per_s"] = knee
    print(json.dumps({"knee_per_s": res["knee_per_s"]}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
