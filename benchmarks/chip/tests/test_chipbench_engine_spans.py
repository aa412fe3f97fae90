"""The serving engine's own spans, counters and request stamps, and what
reads them: a tiny engine traced by the JAX profiler on the CPU, a
synthetic trace whose answers are known, and the two traces recorded on
the chip, where every reader the benchmark had before still reads what it
read then."""
import contextlib
import time
import types
from pathlib import Path

import numpy as np
import pytest

from chipbench import client as client_lib, layout, spans as S, trace as T
from chipbench.client import Rec
from chipbench.context import Context

DATA = Path(__file__).parent / "data"

# the fast path's spans inside ``engine.step``
PHASES = {"engine.reap", "engine.admit"} | {
    f"engine.{p}.{s}" for p in ("prefill", "decode")
    for s in ("prepare", "dispatch", "sync", "emit")}


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, cfg, num_slots=2, max_len=48,
                        prefill_chunk=8, decode_horizon=4)
    eng.warmup()
    return eng


def _serve(engine, span, n=4):
    """Four requests through the benchmark's client, two more than the
    slots, so that two wait in the queue; returns the client, the requests
    the engine was handed, and the prompt positions prefilled after the
    first step."""
    from repro.serving import Request

    made = []

    def request(**kw):
        made.append(Request(**kw))
        return made[-1]

    cl = client_lib.Client(engine, request, span)
    for i in range(n):
        spec = types.SimpleNamespace(
            prompt=np.arange(1, 20 + i, dtype=np.int32), max_new_tokens=5)
        cl.submit(spec, i, 0.0)
    cl.step()
    mid = cl.prefilled_tokens()
    while cl.outstanding:
        cl.step()
    return cl, made, mid


def test_engine_spans_nest_in_its_steps(tiny_engine, tmp_path):
    import jax

    before = dict(tiny_engine.stats)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            _serve(tiny_engine, jax.profiler.TraceAnnotation)
    tr = S.load(str(tmp_path))
    names = {e.name for e in tr.host}
    assert PHASES | {"engine.step", "bench.engine_step"} <= names
    steps = [e for e in tr.host if e.name == "engine.step"]
    outer = [e for e in tr.host if e.name == "bench.engine_step"]
    assert len(steps) == len(outer) >= 6
    for s in steps:
        assert any(o.start <= s.start and s.end <= o.end for o in outer)
        inside = [e for e in tr.host if e.name in PHASES
                  and s.start <= e.start < s.end]
        assert all(e.end <= s.end for e in inside)
        assert len(inside) + 1 <= 12
    for e in tr.host:
        if e.name in PHASES:
            assert any(s.start <= e.start and e.end <= s.end for s in steps)
    lo, hi = T.window(tr)
    host = S.host_ms_per_step(tr.host, lo, hi)
    mean = sum(s.end - s.start for s in steps) / len(steps) * 1e-6
    assert 0 < host < mean
    # the engine's counters tell the same host time without the trace
    after = tiny_engine.stats
    assert after["step_calls"] - before["step_calls"] == len(steps)
    counted = ((after["step_host_s"] - before["step_host_s"]) * 1e3
               / len(steps))
    assert counted == pytest.approx(host, rel=0.1)


def test_step_host_time_leaves_out_the_wait_on_the_device(tiny_engine,
                                                         monkeypatch):
    import repro.serving.engine as engine_mod

    wait = 0.02

    class SlowSync:
        """A span that holds every sync span open ``wait`` seconds more."""

        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            if self.name.endswith(".sync"):
                time.sleep(wait)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(engine_mod, "TraceAnnotation", SlowSync)
    eng, before = tiny_engine, dict(tiny_engine.stats)
    t0 = time.perf_counter()
    _serve(eng, lambda name: contextlib.nullcontext())
    took = time.perf_counter() - t0
    d = {k: eng.stats[k] - before[k] for k in ("step_host_s", "step_calls",
                                                "host_syncs")}
    assert d["step_calls"] >= 6 and d["host_syncs"] >= 4
    assert 0 < d["step_host_s"] < took - wait * d["host_syncs"]


def test_request_stamps_and_prefill_progress(tiny_engine):
    cl, made, mid = _serve(tiny_engine, lambda name: contextlib.nullcontext())
    # the benchmark reads prefill progress from the engine's in-flight
    # table: after one step the two admitted prompts are one chunk in
    assert mid == {0: 8, 1: 8, 2: 0, 3: 0}
    waits = []
    for req in made:
        rec = cl.recs[req.rid]
        assert req.t_submit <= req.t_admit <= rec.first <= rec.last
        assert rec.due <= req.t_submit <= rec.submitted
        waits.append(req.t_admit - req.t_submit)
    # the last two waited for a slot
    assert max(waits[:2]) < min(waits[2:])


def test_a_resumed_request_keeps_its_first_admission_stamp(tiny_engine):
    import dataclasses

    from repro.serving import Request

    eng = tiny_engine
    eng.set_stream_callbacks()
    req = Request(rid=7, prompt=list(range(1, 12)), max_new_tokens=6)
    eng.submit(req)
    eng.step()
    eng.step()
    first = req.t_admit
    resumed = eng.stats["resumed"]
    eng.preempt(7)
    eng.run()
    assert eng.stats["resumed"] == resumed + 1
    assert req.t_submit <= req.t_admit == first
    # the stamps are no arguments, and a copy starts without them
    copy = dataclasses.replace(req)
    assert (copy.t_submit, copy.t_admit) == (None, None) and copy == req


def _reader(name):
    return layout._reader(layout.BENCH_DIR, name)


def _synthetic():
    """A 1000 ns window: a bench step over an engine step; the device runs
    0-10, 300-590 (the sync waits on it) and 950-1000; a collection runs
    inside the emit span."""
    ops = [T.Event("%fusion.1 = f32[8] fusion(%p)", 0, 10),
           T.Event("%fusion.2 = f32[8] fusion(%p)", 300, 590),
           T.Event("%fusion.3 = f32[8] fusion(%p)", 950, 1000)]
    host = [T.Event(T.WINDOW_SPAN, 0, 1000),
            T.Event("bench.engine_step", 0, 1000),
            T.Event("engine.step", 10, 990),
            T.Event("engine.decode.prepare", 20, 290),
            T.Event("engine.decode.sync", 300, 590),
            T.Event("engine.decode.emit", 600, 900),
            T.Event("bench.gc", 850, 880)]
    return T.Trace({"/device:TPU:0": {T.OPS: ops, T.MODULES: []}}, host)


def test_idle_gaps_name_the_innermost_span():
    tr = _synthetic()
    ops = tr.devices["/device:TPU:0"][T.OPS]
    gaps = S.idle_gaps(ops, tr.host, 0, 1000)
    # 590-950: emit 270, engine.step 60, bench.gc 30
    assert gaps[0] == ["engine.decode.emit", pytest.approx(360e-9)]
    # 10-300: prepare 270, engine.step 20
    assert gaps[1] == ["engine.decode.prepare", pytest.approx(290e-9)]
    # the benchmark's own reduction names both by the outermost span
    assert [g[0] for g in T.idle_gaps(ops, tr.host, 0, 1000)] == [
        "bench.engine_step"] * 2
    by = dict(S.idle_by_span(ops, tr.host, 0, 1000))
    assert by == pytest.approx({"engine.decode.emit": 270e-9,
                                "engine.decode.prepare": 270e-9,
                                "engine.step": 80e-9, "bench.gc": 30e-9})
    assert sum(by.values()) == pytest.approx(650e-9)


def test_host_ms_leaves_out_the_wait_on_the_device():
    tr = _synthetic()
    # the step's 980 ns less its 290 ns sync
    assert S.host_ms_per_step(tr.host, 0, 1000) == pytest.approx(690e-6)
    # a step that sticks out of the window is not counted
    assert S.host_ms_per_step(tr.host, 20, 1000) is None
    assert S.host_ms_per_step(
        [e for e in tr.host if not e.name.startswith("engine.")],
        0, 1000) is None
    # the metrics read the engine's counters between the window's ends
    snap = lambda host_s, calls: {"stats": {  # noqa: E731
        "step_host_s": host_s, "step_calls": calls}}
    ctx = types.SimpleNamespace(traced={"snaps": [snap(2.0, 10),
                                                  snap(2.069, 110)]})
    for name in ("engine.host_ms.batch", "engine.host_ms.chat"):
        assert _reader(name)(ctx) == pytest.approx(0.69)
    ctx.traced["snaps"][-1] = snap(2.0, 10)
    assert _reader("engine.host_ms.batch")(ctx) is None


def test_unspanned_idle_time_names_a_gap_only_where_no_span_is_open():
    ops = [T.Event("%a = f32[8] fusion(%p)", 0, 10),
           T.Event("%b = f32[8] fusion(%p)", 500, 510)]
    host = [T.Event("bench.submit", 10, 20)]
    gaps = S.idle_gaps(ops, host, 0, 600)
    assert gaps[0][0] == "bench.submit"
    assert S.idle_gaps(ops, [], 0, 600)[0][0] == S.UNSPANNED
    by = dict(S.idle_by_span(ops, host, 0, 600))
    assert by == pytest.approx({"bench.submit": 10e-9,
                                S.UNSPANNED: 570e-9})


# ------------------------------------------- the traces recorded on the chip

# Every per-layer reader the benchmark had before the engine recorded
# spans, on the two recorded decode programs with the counters below: the
# values those readers gave before the engine's spans existed.
RECORDED = {
    "qwen2-0.5b.w8a8-kv8.decode-heavy": {
        "sched.occupancy.batch": 100.0,
        "step.decode_ms.batch": 34.678733,
        "step.mfu.batch": 1.6464128069012272,
        "step.vocab_ms.batch": 1.624866,
        "qmatmul_w8a8_roofline": 35.096712286345934,
        "fused_decode_roofline": 7.764371140870386,
        "device.idle_share.batch": 1.0836252267149904,
        "device.hbm_gb.batch": 1.4737,
    },
    "qwen2-0.5b.w8a8-kv8.chat": {
        "sched.prefill_row_use.chat": 50.0,
        "step.prefill_ms.chat": None,
        "device.idle_share.chat": 1.0836252267149904,
    },
    "qwen2-0.5b.w8a16.decode-heavy": {
        "sched.occupancy.batch": 100.0,
        "step.decode_ms.batch": 38.842853375,
        "step.mfu.batch": 0.7419049951074309,
        "step.vocab_ms.batch": 0.536937625,
        "qmatmul_w8a16_roofline": 36.38206746771734,
        "device.idle_share.batch": 0.1287186993386813,
        "device.hbm_gb.batch": 1.4737,
    },
}
GAPS = {"w8a8-kv8": [200154e-9, 163462e-9, 16389e-9],
        "w8a16": [200437e-9, 200001e-9]}


def _recorded_ctx(cell):
    recipe = cell.split(".")[-2]
    tr = T.load_json(DATA / f"decode_step_{recipe}.json.gz")
    ctx = Context(layout.load_cell(cell), 1, "TPU v5 lite")
    steps = 1 if recipe == "w8a8-kv8" else 8
    P = {i: 64 + 16 * i for i in range(32)}
    ctx.records = {i: Rec(spec=types.SimpleNamespace(
        prompt=np.zeros(P[i], np.int32)), due=0.0) for i in range(32)}
    lo, hi = T.window(tr)
    a = {"t": 0.0, "stats": {"engine_steps": 100, "occupancy_sum": 90.0,
                             "decode_steps": 100, "prefill_dispatches": 7},
         "prefilled": {i: P[i] - 16 * (i % 2) for i in P},
         "delivered": {i: 5 + i for i in P}}
    b = {"t": (hi - lo) * 1e-9,
         "stats": {"engine_steps": 100 + steps,
                   "occupancy_sum": 90.0 + steps,
                   "decode_steps": 100 + steps, "prefill_dispatches": 8},
         "prefilled": P, "delivered": {i: 5 + i + steps for i in P}}
    ctx.traced = ctx.reduce_trace(tr, [a, b])
    ctx.memory = {"bytes_in_use": 1473700000}
    ctx.window = (0.0, 1.0)
    return ctx, tr


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_traces_read_as_before(cell):
    ctx, tr = _recorded_ctx(cell)
    got = {m.name: m.read(ctx) for m in ctx.cell.per_layer}
    for name, want in RECORDED[cell].items():
        assert got.pop(name) == (want if want is None
                                 else pytest.approx(want, rel=1e-12)), name
    # the readers of the engine's counters find nothing in an engine's
    # stats without them
    assert got and all(v is None for v in got.values()), got
    gaps = ctx.traced["breakdown"]["idle_gaps"]
    want = GAPS[cell.split(".")[-2]]
    assert [g[0] for g in gaps] == ["bench.engine_step"] * 10
    assert [g[1] for g in gaps[:len(want)]] == pytest.approx(want)
    assert all(g[1] == pytest.approx(2e-9) for g in gaps[len(want):])
    # with the benchmark's spans alone, the innermost span is the one
    # the benchmark's reduction names
    p = ctx.traced["planes"][0]
    lo, hi = ctx.traced["lo"], ctx.traced["hi"]
    assert S.idle_gaps(ctx.traced["ops"][p], tr.host, lo, hi) == gaps


def test_collections_get_spans_with_their_generation(tmp_path):
    import gc

    import jax

    import idle_split

    hook = idle_split.GcSpans()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            gc.callbacks.append(hook)
            try:
                gc.collect()
                gc.collect(0)
            finally:
                gc.callbacks.remove(hook)
            gc.collect()
    assert hook.open is None
    tr = S.load(str(tmp_path))
    lo, hi = T.window(tr)
    got = idle_split.collections(str(tmp_path), lo, hi)
    assert [c[0] for c in got] == [2, 0]
    assert all(lo <= c[1] < hi and c[2] > 0 for c in got)
    assert [e.name for e in tr.host].count("bench.gc") == 2


def test_idle_split_reads_the_engine_spans_of_a_trace(monkeypatch):
    import idle_split

    def scale(e):
        return T.Event(e.name, e.start * 100000, e.end * 100000)

    # the synthetic trace with every time x 1e5: a 1e8 ns (0.1 s) window
    tr = _synthetic()
    tr.host = [scale(e) for e in tr.host]
    d = tr.devices["/device:TPU:0"]
    d[T.OPS] = [scale(e) for e in d[T.OPS]]
    monkeypatch.setattr(idle_split.spans, "load", lambda path: tr)
    monkeypatch.setattr(idle_split, "collections",
                        lambda path, lo, hi: [[0, 8.5e7, 0.003]])
    got = idle_split.split(types.SimpleNamespace(dir="unused"))
    assert got["window_s"] == pytest.approx(0.1)
    assert got["first_op_s"] == 0
    assert got["idle_s"] == pytest.approx(0.065)
    assert got["idle_gaps"][0] == ["engine.decode.emit", pytest.approx(0.036)]
    assert got["gaps_over_10ms"] == [
        ["engine.decode.prepare", pytest.approx(0.001), pytest.approx(0.029)],
        ["engine.decode.emit", pytest.approx(0.059), pytest.approx(0.036)]]
    assert got["gc"] == {"count": {"0": 1}, "seconds": 0.003,
                         "over_10ms": []}
    assert got["host_ms_per_step_spans"] == pytest.approx(69.0)
