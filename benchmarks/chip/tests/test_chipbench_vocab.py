"""``step.vocab_ms.batch``: the shape matcher that finds the vocabulary-wide
ops in an op's HLO text, and the reader on the two decode programs recorded
on a TPU v5e (``tests/data``)."""
from pathlib import Path

import pytest

from chipbench import layout, trace as T
from chipbench.context import Context

DATA = Path(__file__).parent / "data"
V = 151936


@pytest.mark.parametrize("text,hit", [
    # a tuple result: the second array has the vocabulary
    ("%is-finite_reduce_fusion = (pred[32]{0:T(512)(128)(4,1)S(1)}, "
     "bf16[32,151936]{1,0:T(8,128)(2,1)S(1)}) fusion(bf16[151936,896]"
     "{1,0:T(8,128)(2,1)} %convert_element_type.130)", True),
    # an operand only, behind a tiled layout
    ("%fusion = bf16[32,896]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[151936,896]"
     "{1,0:T(8,128)(2,1)} %convert_element_type.130, s32[1024]{0:T(1024)S(1)}"
     " %pad_clamp_fusion)", True),
    ("%copy = f32[<=151936,8]{1,0} copy(f32[<=151936,8]{1,0} %p)", True),
    # a dimension that only starts with the vocabulary's digits
    ("%fusion.3 = f32[1519360]{0} fusion(f32[32,1519360]{1,0} %p)", False),
    # the digits as an instance number and in a layout, not in a shape
    ("%fusion.151936 = bf16[32,896]{1,0:T(8,128)(2,1)} fusion(bf16[32,896]"
     "{1,0:T(151936,128)} %add.151936)", False),
])
def test_the_vocabulary_is_a_whole_dimension(text, hit):
    assert T.has_dim(T.Event(text, 0, 1), V) is hit


def test_shapes_of_a_tuple_and_of_scalars():
    text = ("%t = (s32[32]{0:T(128)}, f32[]{:S(2)}, s8[24,32,2560,2,64]"
            "{2,4,3,1,0:T(8,128)(4,1)}) tuple(f8e4m3fn[4,8]{1,0} %a)")
    assert T.shapes(text) == [(32,), (), (24, 32, 2560, 2, 64), (4, 8)]


@pytest.mark.parametrize("recipe,steps,ops,want_ms", [
    # K=1: the table's conversion 1243.331, the lookup 1.277, the logits
    # 361.885, the argmax 18.373 us. The previous program's argmax (18.371
    # us, ending before this program starts) is no op of it
    ("w8a8-kv8", 1, 4, 1.624866),
    # K=8: one conversion, then a lookup, logits and argmax a step
    ("w8a16", 8, 25, 4.295501 / 8)])
def test_recorded_decode_step(recipe, steps, ops, want_ms):
    ctx = Context(layout.load_cell(f"qwen2-0.5b.{recipe}.decode-heavy"), 1,
                  "TPU v5 lite")
    snaps = [{"stats": {"decode_steps": 10}},
             {"stats": {"decode_steps": 10 + steps}}]
    ctx.traced = ctx.reduce_trace(
        T.load_json(DATA / f"decode_step_{recipe}.json.gz"), snaps)
    got = layout._reader(layout.BENCH_DIR, "step.vocab_ms.batch")(ctx)
    assert got == pytest.approx(want_ms, rel=1e-9)
    assert f" {ops} ops with a {V}-wide dimension" in ctx.notes[-1]
    names = ("convert_element_type", "fusion", "is-finite_reduce_fusion",
             "iota_reduce_fusion")
    assert all(n in ctx.notes[-1] for n in names)


def test_nothing_to_read_is_no_reading():
    ctx = Context(layout.load_cell("qwen2-0.5b.w8a8-kv8.decode-heavy"), 1,
                  "TPU v5 lite")
    ops = [T.Event("%fusion.1 = bf16[32,896]{1,0} fusion(%p)", 100, 200),
           # the horizon's loop carries the converted table in its state
           T.Event("%while.63 = (s32[], bf16[151936,896]{1,0}) while((s32[], "
                   "bf16[151936,896]{1,0}) %tuple)", 60, 290),
           T.Event("%fusion.2 = bf16[32,151936]{1,0} fusion(%p)", 600, 700)]
    mods = [T.Event("jit(_decode_horizon_impl)", 50, 300),
            T.Event("jit(_prefill_multi_impl)", 500, 800)]
    tr = T.Trace({"/device:TPU:0": {T.OPS: ops, T.MODULES: mods}},
                 [T.Event(T.WINDOW_SPAN, 0, 1000)])
    read = layout._reader(layout.BENCH_DIR, "step.vocab_ms.batch")
    # the loop is a container, and the vocabulary-wide op runs in a
    # prefill program: none in decode
    ctx.traced = ctx.reduce_trace(tr, [{"stats": {"decode_steps": 0}},
                                       {"stats": {"decode_steps": 3}}])
    assert read(ctx) is None
    # no decode step between the traced window's ends
    mods[1] = T.Event("jit(_decode_horizon_impl)", 500, 800)
    ctx.traced = ctx.reduce_trace(tr, [{"stats": {"decode_steps": 3}},
                                       {"stats": {"decode_steps": 3}}])
    assert read(ctx) is None
