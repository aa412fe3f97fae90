"""The reduction from a profiler trace to device times: on a synthetic
trace whose answers are known, and on a short trace recorded on the chip."""
from pathlib import Path

import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


def _ev(name, s, e):
    return T.Event(name, s, e)


def _synthetic():
    ops = [_ev("%fusion.1 = f32[8] fusion(%p)", 100, 200),
           _ev("%fusion.2 = f32[8] fusion(%fusion.1)", 150, 300),
           _ev("%fused_decode_pallas.3 = s8[8] custom-call(%fusion.2)", 400,
               450), _ev("%fusion.4 = f32[8] fusion(%fused_decode_pallas.3)",
                         700, 800)]
    mods = [_ev("jit(_decode_horizon_impl)", 90, 460),
            _ev("jit(_prefill_multi_impl)", 690, 810)]
    host = [_ev(T.WINDOW_SPAN, 0, 1000), _ev("bench.engine_step", 50, 500),
            _ev("bench.wait_arrival", 500, 690)]
    return T.Trace({"/device:TPU:0": {T.OPS: ops, T.MODULES: mods}}, host)


def test_busy_is_the_union_of_op_intervals():
    tr = _synthetic()
    assert T.window(tr) == (0, 1000)
    ops = tr.devices["/device:TPU:0"][T.OPS]
    assert T.union(ops) == [[100, 300], [400, 450], [700, 800]]
    assert T.busy_ns(ops) == 350
    assert T.busy_ns(T.clip(ops, 120, 420)) == 180 + 20


def test_ops_inside_programs_and_top_ops():
    tr = _synthetic()
    d = tr.devices["/device:TPU:0"]
    dec = T.ops_in(d[T.OPS], d[T.MODULES], lambda n: "decode" in n)
    assert [T.op_name(e) for e in dec] == ["fusion.1", "fusion.2",
                                            "fused_decode_pallas.3"]
    assert T.top_ops(d[T.OPS]) == [["fusion", pytest.approx(350e-9)],
                                   ["fused_decode_pallas",
                                    pytest.approx(50e-9)]]


def test_idle_gaps_are_named_by_the_host_span_over_them():
    tr = _synthetic()
    gaps = T.idle_gaps(tr.devices["/device:TPU:0"][T.OPS], tr.host, 0, 1000)
    assert gaps[0] == ["bench.wait_arrival", pytest.approx(250e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [100e-9, 100e-9, 200e-9, 250e-9])


def test_json_round_trip(tmp_path):
    tr = _synthetic()
    T.save_json(tr, tmp_path / "t.json.gz")
    back = T.load_json(tmp_path / "t.json.gz")
    assert back.to_json() == tr.to_json()


@pytest.mark.parametrize("recipe,per_step,steps", [
    ("w8a8-kv8", {"qmatmul_w8a8": 168, "quantize_act": 72,
                  "fused_decode": 24}, 1),
    ("w8a16", {"qmatmul_w8a16": 168}, 8)])
def test_recorded_decode_step(recipe, per_step, steps):
    """One decode program recorded on a TPU v5e at 32 slots x 2560 (names
    cut to 160 characters): every Pallas call of the recipe is found by
    its own op name, once per layer and projection, and consumers that
    only name a kernel's output as an operand are not counted."""
    from chipbench import kernels

    tr = T.load_json(DATA / f"decode_step_{recipe}.json.gz")
    lo, hi = T.window(tr)
    d = tr.devices["/device:TPU:0"]
    assert 0.9 < T.busy_ns(d[T.OPS]) / (hi - lo) <= 1.0
    dec = T.ops_in(d[T.OPS], d[T.MODULES], kernels.is_decode)
    for k in kernels.RATE:
        n = sum(kernels.matches(e, k) for e in dec)
        assert n == per_step.get(k, 0) * steps, k
        assert n <= sum(k in e.name for e in dec)
    top = [name for name, _ in T.top_ops(d[T.OPS])]
    assert "while" not in top and len(top) == 10
    assert any(name.endswith("_pallas") for name in top)
