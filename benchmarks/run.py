"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; a benchmark that raises
prints an ``ERROR`` row and its traceback, the rest still run, and the
harness exits non-zero. Accuracy tables emit their
metric in the ``derived`` column with us_per_call as the wall time of the
full table evaluation. The serving benchmark additionally persists a
machine-readable ``BENCH_serve.json`` (tok/s, speedups, occupancy, host-sync
and dispatch counts per token) so the serving-perf trajectory is tracked
across PRs — CI uploads it as an artifact.
"""
from __future__ import annotations

import argparse
import functools
import sys
import time
import traceback


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names to run")
    ap.add_argument("--skip-lm", action="store_true",
                    help="skip the (slower) LM-family DFQ and serving "
                         "benchmarks")
    ap.add_argument("--serve-json", default=None, metavar="PATH",
                    help="where serve_engine persists BENCH_serve.json "
                         "(default: benchmarks/BENCH_serve.json)")
    ap.add_argument("--kernels-json", default=None, metavar="PATH",
                    help="where kernels_bench persists BENCH_kernels.json "
                         "(default: benchmarks/BENCH_kernels.json)")
    args, _ = ap.parse_known_args()

    from .kernels_bench import kernel_rows_persisted
    from .roofline_table import roofline_rows
    from .tables import ALL_TABLES

    benches = dict(ALL_TABLES)
    benches["kernels"] = functools.partial(
        kernel_rows_persisted, json_path=args.kernels_json)
    benches["roofline"] = roofline_rows
    if not args.skip_lm:
        from .lm_dfq import lm_dfq_all
        from .serve_engine import serve_rows

        benches["lm_dfq"] = lm_dfq_all
        benches["serve_engine"] = functools.partial(
            serve_rows, json_path=args.serve_json)

    selected = benches
    if args.only:
        keys = args.only.split(",")
        unknown = [k for k in keys if k not in benches]
        if unknown:
            ap.error(f"unknown benchmark(s) {unknown}; available with the "
                     f"current flags: {sorted(benches)}")
        selected = {k: benches[k] for k in keys}

    print("name,us_per_call,derived")
    failed = []
    for bench_name, fn in selected.items():
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            _emit(f"{bench_name}.ERROR", 0.0, repr(e)[:80])
            traceback.print_exc()
            failed.append(bench_name)
            continue
        dt_us = (time.perf_counter() - t0) * 1e6
        for row_name, value in rows:
            _emit(f"{bench_name}.{row_name}", dt_us / max(len(rows), 1), value)
    if failed:
        sys.exit(f"benchmark(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
