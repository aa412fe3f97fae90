"""What a metric reader is handed: the cell, the run's records, and (in a
traced run) the reduced trace with the counters snapshotted at its ends.

Readers live in ``metrics/<name>.py``, one per metric, each a
``read(ctx) -> float | None``. A reader that finds nothing to read returns
None and the metric is left out of the line.
"""
from __future__ import annotations

import math

from . import flops, model, trace as trace_lib


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (inf counts as a miss)."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


class Context:
    def __init__(self, cell, seed: int, device_kind: str,
                 require_peaks: bool = True):
        self.cell = cell
        self.seed = seed
        self.config = cell.config
        self.serving = cell.config["serving"]
        self.arch = cell.arch
        self.dims = model.dims(cell.config, cell.arch)
        self.device_kind = device_kind
        try:
            self.peaks = flops.peaks(device_kind)
        except flops.UnknownDevice:
            if require_peaks:
                raise
            self.peaks = None
        self.setup_s = None
        self.window = None           # (open, close) host seconds
        self.records = {}            # rid -> client.Rec
        self.memory = {}
        self.traced = None           # reduce_trace(...) of a traced run
        self.notes = []              # lines printed before the result

    def note(self, line: str) -> None:
        self.notes.append(line)

    def decode_step_calls(self) -> dict:
        """The cell's decode step as its kernels' calls, by the
        architecture file's count where it gives one."""
        f = getattr(self.arch, "decode_step_calls", flops.decode_step_calls)
        return f(self.dims, self.serving["num_slots"],
                 self.serving["max_len"], self.serving["recipe"])

    def model_flops(self, tokens: float, attended: float,
                    heads: float) -> float:
        f = getattr(self.arch, "model_flops", flops.model_flops)
        return f(self.dims, tokens, attended, heads)

    # ---------------------------------------------------- traced window
    def reduce_trace(self, tr, snaps) -> dict:
        lo, hi = trace_lib.window(tr)
        ops = {p: trace_lib.clip(evs, lo, hi)
               for p, evs in trace_lib.device_lines(tr, trace_lib.OPS).items()}
        mods = {p: trace_lib.clip(evs, lo, hi) for p, evs in
                trace_lib.device_lines(tr, trace_lib.MODULES).items()}
        planes = sorted(ops)[:self.cell.chips]
        if not planes:
            raise ValueError("the trace holds no device operations")
        busy = [trace_lib.busy_ns(ops[p]) * 1e-9 for p in planes]
        first = planes[0]
        return {
            "lo": lo, "hi": hi, "ops": ops, "modules": mods,
            "planes": planes,
            "busy_s": sum(busy) / len(busy),
            "window_s": (hi - lo) * 1e-9,
            "breakdown": {
                "device_ops": trace_lib.top_ops(ops[first]),
                "idle_gaps": trace_lib.idle_gaps(ops[first], tr.host, lo, hi),
            },
            "snaps": snaps,
        }

    def delta(self, stat: str) -> float:
        a, b = self.traced["snaps"][0], self.traced["snaps"][-1]
        return b["stats"][stat] - a["stats"][stat]

    def traced_seconds(self) -> float:
        a, b = self.traced["snaps"][0], self.traced["snaps"][-1]
        return b["t"] - a["t"]

    def module_ns(self, match) -> float:
        """Device time of the programs whose name satisfies ``match``, on
        the first traced chip."""
        p = self.traced["planes"][0]
        return sum(e.end - e.start for e in self.traced["modules"].get(p, [])
                   if match(e.name))

    def work_in_trace(self) -> dict:
        """Tokens the engine processed between the traced window's ends:
        prompt positions prefilled and tokens decoded, with the key
        positions each attended, and rows through the head."""
        a, b = self.traced["snaps"][0], self.traced["snaps"][-1]
        pre = dec = att = heads = 0.0
        for rid, rec in self.records.items():
            P = len(rec.spec.prompt)
            p0, p1 = a["prefilled"].get(rid, 0), b["prefilled"].get(rid, 0)
            if p1 > p0:                  # positions p0..p1-1 attend p+1 keys
                pre += p1 - p0
                att += (p1 * (p1 + 1) - p0 * (p0 + 1)) / 2
                if p1 == P:
                    heads += 1
            # token i >= 1 comes from a decode step at position P + i - 1
            d0 = max(a["delivered"].get(rid, 0), 1)
            d1 = max(b["delivered"].get(rid, 0), 1)
            if d1 > d0:
                dec += d1 - d0
                att += (d1 - d0) * P + (d1 * (d1 - 1) - d0 * (d0 - 1)) / 2
                heads += d1 - d0
        return {"prefill": pre, "decode": dec, "attended": att,
                "heads": heads}
