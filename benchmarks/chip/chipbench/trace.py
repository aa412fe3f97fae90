"""Reduction of a JAX profiler trace to device times.

``load`` reads the ``.xplane.pb`` the profiler writes and keeps, in one
plain structure (``Trace``), the events of the device planes and the host
spans the benchmark itself recorded (``bench.*``), all in nanoseconds on
the trace's one clock. Everything after ``load`` works on that structure,
so the tests can feed it a small recorded trace.

Device planes are named ``/device:TPU:<n>``. On each, the line ``XLA Ops``
holds one event per operation run, and ``XLA Modules`` one per program
(jitted function) run. The traced window is the host span
``bench.trace_window``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.trace_window"


@dataclasses.dataclass
class Event:
    name: str           # for a device op, its whole HLO text
    start: float        # ns
    end: float          # ns


@dataclasses.dataclass
class Trace:
    devices: dict       # {plane name: {line name: [Event]}}
    host: list          # [Event] of the benchmark's own spans

    def to_json(self) -> dict:
        ev = lambda e: [e.name, e.start, e.end]  # noqa: E731
        return {"devices": {p: {ln: [ev(e) for e in evs]
                                for ln, evs in lines.items()}
                            for p, lines in self.devices.items()},
                "host": [ev(e) for e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda x: Event(*x)  # noqa: E731
        return cls({p: {ln: [ev(e) for e in evs] for ln, evs in lines.items()}
                    for p, lines in d["devices"].items()},
                   [ev(e) for e in d["host"]])


def load(logdir: str) -> Trace:
    """The trace the profiler wrote under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(logdir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    lines[line.name] = [Event(e.name, e.start_ns, e.end_ns)
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(devices, host)


def save_json(tr: Trace, path: str) -> None:
    import gzip

    with gzip.open(path, "wt") as f:
        json.dump(tr.to_json(), f)


def load_json(path: str) -> Trace:
    import gzip

    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# ------------------------------------------------------------- reduction

def window(tr: Trace) -> tuple:
    """(start, end) ns of the traced window."""
    spans = [e for e in tr.host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return spans[0].start, spans[0].end


def clip(events, lo, hi) -> list:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events) -> list:
    """Merged [start, end] intervals covered by the events."""
    out = []
    for s, e in sorted((e.start, e.end) for e in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    return sum(e - s for s, e in union(events))


def device_lines(tr: Trace, line: str) -> dict:
    """{plane: [Event]} of one line, on every device plane that has it."""
    return {p: lines[line] for p, lines in tr.devices.items()
            if lines.get(line)}


def ops_in(ops, modules, match) -> list:
    """The ops that run inside modules whose name satisfies ``match``."""
    spans = union([m for m in modules if match(m.name)])
    out, j = [], 0
    for e in sorted(ops, key=lambda e: e.start):
        while j < len(spans) and spans[j][1] < e.start:
            j += 1
        if j < len(spans) and spans[j][0] <= e.start < spans[j][1]:
            out.append(e)
    return out


def op_name(event) -> str:
    """An op's own name: the HLO instruction's name without its ``%`` (the
    trace names an op by its whole HLO text, operands included)."""
    return event.name.split(" = ", 1)[0].lstrip("%")


# an array shape in HLO text: its element type and its dimensions, as in
# ``bf16[32,151936]{1,0:T(8,128)(2,1)}`` (a dynamic one as ``<=32``)
_SHAPE = re.compile(r"\b(?:pred|token|[a-z]+\d+[a-z0-9]*)\[([<=\d,]*)\]")


def shapes(text: str) -> list:
    """The shapes in an op's HLO text, each a tuple of its dimensions:
    every array of a tuple, the result's and the operands'; layouts and
    instance numbers are no shapes."""
    return [tuple(int(d.lstrip("<=")) for d in dims.split(",") if d)
            for dims in _SHAPE.findall(text)]


def has_dim(event, n: int) -> bool:
    """Whether one of a device op's shapes has ``n`` as a whole dimension."""
    return any(n in s for s in shapes(event.name))


# ops that hold other ops: their time is their bodies'
CONTAINERS = ("while", "conditional", "call")


def kind(event) -> str:
    """An op's name with its ``.N`` instance number dropped."""
    return re.sub(r"\.\d+$", "", op_name(event))


def is_container(event) -> bool:
    return kind(event) in CONTAINERS


def top_ops(ops, n=10) -> list:
    """[name, seconds] of the ops that took most time, by name with its
    ``.N`` instance number dropped, leaving out the containers."""
    tot = {}
    for e in ops:
        name = kind(e)
        if name in CONTAINERS:
            continue
        tot[name] = tot.get(name, 0.0) + (e.end - e.start)
    return sorted(([k, v * 1e-9] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(ops, host, lo, hi, n=10) -> list:
    """The n longest stretches of [lo, hi] in which no op ran, each named
    by the benchmark's host span that covers most of it."""
    gaps, t = [], lo
    for s, e in union(ops) + [[hi, hi]]:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        cover = {}
        for h in host:
            if h.name == WINDOW_SPAN:
                continue
            ov = min(e, h.end) - max(s, h.start)
            if ov > 0:
                cover[h.name] = cover.get(h.name, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "host:unspanned"
        out.append([name, (e - s) * 1e-9])
    return out
