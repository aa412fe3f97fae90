#!/usr/bin/env python3
"""One traced run of a cell, read by the serving engine's own spans: where
the device's idle time in the traced window goes.

    python3 benchmarks/chip/idle_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out FILE]

It makes ``run.py``'s run of the cell with ``--trace 1``, with every garbage
collection of the run in a ``bench.gc`` span, and prints the run's result
line, then one JSON line (also written to ``--out``): the window's idle
time split among the innermost host spans (``chipbench.spans``), its ten
longest gaps named the same way, where in the window each gap over 10 ms
and the first device op fall, the collections that ran in it, the host's
own milliseconds per engine step from the spans and from the engine's
counters, and the traced run's end-to-end metrics, to hold against an
untraced run of the same seed. A diagnostic of the breakdown the ledger
records, not a run of the benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402  (its import starts the set-up clock)
from chipbench import spans, trace as trace_lib  # noqa: E402

GC_SPAN = "bench.gc"


class GcSpans:
    """A ``gc.callbacks`` hook that puts each collection in a ``bench.gc``
    span carrying its generation; the profiler records it while a session
    runs."""

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self.Annotation = TraceAnnotation
        self.open = None

    def __call__(self, phase, info):
        if phase == "start":
            self.open = self.Annotation(GC_SPAN,
                                        generation=info["generation"])
            self.open.__enter__()
        elif self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


def collections(path, lo, hi) -> list:
    """[generation, start ns, seconds] of the ``bench.gc`` spans inside
    [lo, hi] of the trace at ``path``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(spans.xplane(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([dict(e.stats).get("generation"), e.start_ns,
                            (e.end_ns - e.start_ns) * 1e-9]
                           for e in line.events if e.name == GC_SPAN
                           and lo <= e.start_ns and e.end_ns <= hi)
    return sorted(out, key=lambda c: c[1])


def split(tracer) -> dict:
    """The engine-span reading of the trace ``tracer`` recorded."""
    tr = spans.load(tracer.dir)
    lo, hi = trace_lib.window(tr)
    lines = trace_lib.device_lines(tr, trace_lib.OPS)
    ops = trace_lib.clip(lines[min(lines)], lo, hi)
    by = spans.idle_by_span(ops, tr.host, lo, hi)
    gcs = collections(tracer.dir, lo, hi)
    segs = spans.leaf_segments(tr.host)
    long_gaps = []
    for s, e in spans.gaps(ops, lo, hi):
        if e - s > 1e7:
            cover = spans.leaf_time(segs, s, e)
            long_gaps.append([max(cover, key=cover.get) if cover
                              else spans.UNSPANNED, (s - lo) * 1e-9,
                              (e - s) * 1e-9])
    long_gc = [c for c in gcs if c[2] > 0.01]
    return {
        "window_s": (hi - lo) * 1e-9,
        "first_op_s": (min(e.start for e in ops) - lo) * 1e-9,
        "idle_s": sum(v for _, v in by),
        "idle_by_span": by,
        "idle_gaps": spans.idle_gaps(ops, tr.host, lo, hi),
        "gaps_over_10ms": long_gaps,
        "gc": {"count": {str(g): sum(c[0] == g for c in gcs)
                         for g in sorted({c[0] for c in gcs})},
               "seconds": sum(c[2] for c in gcs),
               "over_10ms": [[g, (s - lo) * 1e-9, d] for g, s, d in long_gc]},
        "host_ms_per_step_spans": spans.host_ms_per_step(tr.host, lo, hi),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.trace, args.keep_trace = 1, None

    held = {}
    reduced = run._Tracer.reduced

    def read_then_reduce(tracer):
        # the engine's spans are read before run.py removes the trace
        held["split"] = split(tracer)
        held["ctx"] = tracer.ctx
        return reduced(tracer)

    run._Tracer.reduced = read_then_reduce
    hook = GcSpans()
    gc.callbacks.append(hook)
    try:
        result = run.run_cell(args)
    except (run.BenchError, run.layout.LayoutError) as e:
        run.log(f"error: {e}")
        return 1
    finally:
        gc.callbacks.remove(hook)
        run._Tracer.reduced = reduced
    print(json.dumps(result), flush=True)
    ctx = held["ctx"]
    out = dict(held["split"], workload=args.workload, seed=args.seed,
               host_ms_per_step_counted=spans.counted_host_ms(ctx),
               end_to_end={m.name: m.read(ctx) for m in ctx.cell.end_to_end})
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
