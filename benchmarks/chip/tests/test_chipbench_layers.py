"""The per-layer readers on a synthetic traced window whose answers are
known: counters over the window, device time per program, idle share,
a kernel's roofline share and the step's share of the peak."""
import json
import types

import numpy as np
import pytest

from chipbench import flops, layout, trace as T
from chipbench.client import Rec
from chipbench.context import Context

CELL = "qwen2-0.5b.w8a8-kv8.decode-heavy"
MS = 1_000_000                       # ns


def _ctx():
    cell = layout.load_cell(CELL)
    ctx = Context(cell, 1, "TPU v5 lite")
    # 10 ms window: decode programs 0-4 and 5-9 ms, each with 24
    # fused_decode calls of 0.1 ms; a prefill program 9.0-9.5 ms
    ops, mods = [], []
    for start in (0, 5):
        mods.append(T.Event("jit(_decode_horizon_impl)", start * MS,
                            (start + 4) * MS))
        for i in range(24):
            s = start * MS + i * 150_000
            ops.append(T.Event(f"%fused_decode_pallas.{i} = s8[32] "
                               "custom-call(%q)", s, s + 100_000))
    mods.append(T.Event("jit(_prefill_multi_impl)", 9 * MS, 9.5 * MS))
    ops.append(T.Event("%fusion.2 = f32[8] fusion(%fused_decode_pallas.0)",
                       9 * MS, 9.5 * MS))
    tr = T.Trace({"/device:TPU:0": {T.OPS: ops, T.MODULES: mods}},
                 [T.Event(T.WINDOW_SPAN, 0, 10 * MS)])
    spec = types.SimpleNamespace(prompt=np.zeros(100, np.int32))
    ctx.records = {0: Rec(spec=spec, due=0.0)}
    snaps = [{"t": 0.0, "stats": {"engine_steps": 10, "occupancy_sum": 5.0,
                                  "decode_steps": 10,
                                  "prefill_dispatches": 3},
              "prefilled": {0: 100}, "delivered": {0: 1}},
             {"t": 0.01, "stats": {"engine_steps": 12, "occupancy_sum": 6.5,
                                   "decode_steps": 12,
                                   "prefill_dispatches": 4},
              "prefilled": {0: 100}, "delivered": {0: 3}}]
    ctx.traced = ctx.reduce_trace(tr, snaps)
    ctx.memory = {"bytes_in_use": 3_000_000_000}
    return ctx


def _read(name, ctx):
    return layout._reader(layout.BENCH_DIR, name)(ctx)


def test_window_busy_and_idle():
    ctx = _ctx()
    assert ctx.traced["window_s"] == pytest.approx(0.01)
    assert ctx.traced["busy_s"] == pytest.approx(48 * 0.1e-3 + 0.5e-3)
    assert _read("device.idle_share.batch", ctx) == pytest.approx(
        100 * (1 - 0.0053 / 0.01))
    assert _read("device.hbm_gb.batch", ctx) == 3.0


def test_counters_over_the_window():
    ctx = _ctx()
    assert _read("sched.occupancy.batch", ctx) == pytest.approx(75.0)
    assert _read("step.decode_ms.batch", ctx) == pytest.approx(4.0)


def test_kernel_roofline_and_step_mfu():
    ctx = _ctx()
    # 48 calls = 2 steps of 24 layers; least time per call from shapes
    ops, nbytes = flops.fused_decode(32, 2560, 14, 2, 64)
    least = flops.least_time(ops, nbytes, flops.peaks("TPU v5 lite"),
                             "bf16")[0]
    assert _read("fused_decode_roofline", ctx) == pytest.approx(
        100 * 48 * least / (48 * 0.1e-3))
    assert _read("qmatmul_w8a8_roofline", ctx) is None
    # 2 decode tokens (indices 1 and 2 of a 100-token prompt) attending
    # 101 and 102 positions, each through the head, over 10 ms of int8 peak
    m = ctx.dims
    want = (2 * flops.linear_flops_per_token(m)
            + flops.attention_flops(m, 203) + 2 * flops.head_flops(m))
    assert _read("step.mfu.batch", ctx) == pytest.approx(
        100 * want / (0.01 * 393e12))


def test_every_per_layer_metric_of_the_benchmark_has_a_reader():
    bench = json.loads((layout.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"] + bench["end_to_end"]:
        assert callable(layout._reader(layout.BENCH_DIR, m["name"]))
