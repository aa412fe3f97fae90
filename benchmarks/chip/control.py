#!/usr/bin/env python3
"""Read the numbers that the limits of ``correct`` are set from, in one
process: the program's widest logit gap on a dozen seeds or more, and on
some of them the control's (the reference one precision step down, on the
same prompts and served tokens).

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 10 [--out f.json]

Each seed is a whole run of the cell (``run.run_cell``) at its own load,
with a short window. On the control's seeds the control is judged in the
program's place by the same test; the script exits non-zero if any control
comes out correct. Run by hand when a limit is set; the benchmark's own
runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        a = argparse.Namespace(workload=args.workload, seed=seed,
                               seconds=args.seconds, trace=0,
                               keep_trace=None)
        r = run.run_cell(a, control=seed in ctl)
        c = r["checks"]
        row = {"seed": seed, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "max_logit_gap": c["max_logit_gap"]["value"],
               "control_logit_gap": c.get("control_logit_gap",
                                          {}).get("value"),
               "control_correct": r.get("control_correct")}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["max_logit_gap"] for r in rows]
    ctrl = [r["control_logit_gap"] for r in rows
            if r["control_logit_gap"] is not None]
    passed = [r["seed"] for r in rows if r["control_correct"]]
    summary = {"workload": args.workload, "program_max": max(prog),
               "control_min": min(ctrl) if ctrl else None,
               "controls_correct": passed, "rows": rows}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}),
          flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    if passed:
        print(f"error: the control came out correct on seeds {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
