"""The serving engine's host spans (``engine.*``) in a profiler trace, read
beside the benchmark's own (``bench.*``).

``trace.load`` keeps only the ``bench.*`` host spans, and
``trace.idle_gaps`` names a gap by the span over most of it, which in a
served run is the outermost ``bench.engine_step``. ``load`` here keeps both
families, and idle time is split among the innermost spans open over it, so
that a gap is put down to the engine phase, or the benchmark's own span,
the host was in. ``benchmarks/chip/idle_split.py`` applies this to one
traced run of a cell.

The engine's counters give the host's own time per step without a trace
(``counted_host_ms``); the ``engine.host_ms.*`` metrics read them.
"""
from __future__ import annotations

import bisect
from pathlib import Path

from . import trace as trace_lib
from .trace import Event

# the host spans kept: the benchmark's own and the serving engine's
HOST_PREFIXES = ("bench.", "engine.")
# the engine's span over one ``step()``, and the suffix of its spans that
# wait on the device for results
STEP_SPAN, SYNC_SUFFIX = "engine.step", ".sync"
UNSPANNED = "host:unspanned"


def xplane(path) -> str:
    """The ``.xplane.pb`` at ``path``, or the newest one under it."""
    p = Path(path)
    if p.is_file():
        return str(p)
    files = sorted(p.glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return str(files[-1])


def load(path) -> trace_lib.Trace:
    """``trace.load``'s structure, with the engine's host spans as well as
    the benchmark's; ``path`` is a profiler log directory or one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in (trace_lib.OPS, trace_lib.MODULES)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return trace_lib.Trace(devices, host)


def gaps(ops, lo, hi) -> list:
    """The stretches of [lo, hi] in which no op ran, as (start, end)."""
    out, t = [], lo
    for s, e in trace_lib.union(ops) + [[hi, hi]]:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    return out


def leaf_segments(host) -> list:
    """[start, end, name] pieces of time, in order, each under one innermost
    host span: of the spans open over a piece, the one that began last (the
    shorter on a tie), leaving out the traced window's own span. Time under
    no span has no piece."""
    spans = sorted((h for h in host
                    if h.name != trace_lib.WINDOW_SPAN and h.end > h.start),
                   key=lambda h: h.start)
    cuts = sorted({t for h in spans for t in (h.start, h.end)})
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i].start <= a:
            open_.append(spans[i])
            i += 1
        open_ = [h for h in open_ if h.end > a]
        if not open_:
            continue
        name = max(open_, key=lambda h: (h.start, -h.end)).name
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def leaf_time(segs, s, e) -> dict:
    """{span name: ns} of [s, e] under each innermost span, from the
    pieces ``leaf_segments`` gives."""
    out = {}
    k = bisect.bisect_right(segs, s, key=lambda g: g[1])
    while k < len(segs) and segs[k][0] < e:
        a, b, name = segs[k]
        out[name] = out.get(name, 0.0) + min(b, e) - max(a, s)
        k += 1
    return out


def idle_gaps(ops, host, lo, hi, n=10) -> list:
    """The n longest stretches of [lo, hi] in which no op ran, each named
    by the innermost host span open over most of it (``host:unspanned``
    where none is)."""
    segs = leaf_segments(host)
    out = []
    for s, e in sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]:
        cover = leaf_time(segs, s, e)
        out.append([max(cover, key=cover.get) if cover else UNSPANNED,
                    (e - s) * 1e-9])
    return out


def idle_by_span(ops, host, lo, hi) -> list:
    """[name, seconds] of all the idle time in [lo, hi], split among the
    innermost host spans open in it, with the time under none as
    ``host:unspanned``; the most first."""
    segs = leaf_segments(host)
    tot = {}
    for s, e in gaps(ops, lo, hi):
        cover = leaf_time(segs, s, e)
        cover[UNSPANNED] = (e - s) - sum(cover.values())
        for k, v in cover.items():
            tot[k] = tot.get(k, 0.0) + v
    return sorted(([k, v * 1e-9] for k, v in tot.items() if v > 0),
                  key=lambda kv: -kv[1])


def host_ms_per_step(host, lo, hi):
    """Mean over the engine's step spans inside [lo, hi] of the step's time
    less the time in the sync spans inside it: the host's own work in a
    step. None without step spans."""
    steps = sorted((h for h in host if h.name == STEP_SPAN
                    and lo <= h.start and h.end <= hi),
                   key=lambda h: h.start)
    if not steps:
        return None
    syncs = trace_lib.union([h for h in host if h.name.startswith("engine.")
                             and h.name.endswith(SYNC_SUFFIX)])
    own, j = 0.0, 0
    for st in steps:
        own += st.end - st.start
        while j < len(syncs) and syncs[j][1] <= st.start:
            j += 1
        k = j
        while k < len(syncs) and syncs[k][0] < st.end:
            own -= min(syncs[k][1], st.end) - max(syncs[k][0], st.start)
            k += 1
    return own / len(steps) * 1e-6


def counted_host_ms(ctx):
    """The engine's counted host milliseconds per ``step()`` call between
    the traced window's ends (``stats["step_host_s"]`` over
    ``stats["step_calls"]``); None for an engine without those counters, or
    with no step in the window."""
    a, b = ctx.traced["snaps"][0]["stats"], ctx.traced["snaps"][-1]["stats"]
    if "step_calls" not in a:
        return None
    n = b["step_calls"] - a["step_calls"]
    return (b["step_host_s"] - a["step_host_s"]) / n * 1e3 if n else None
