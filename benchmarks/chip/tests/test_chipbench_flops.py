"""The table of peaks and the operation and byte counts, each checked
against a count worked by hand at qwen2-0.5b widths."""
import json

import pytest

from chipbench import flops, layout, model

M = model.dims(json.loads(
    (layout.BENCH_DIR / "configs" / "qwen2-0.5b.w8a8-kv8.json").read_text()))


def test_peaks_keyed_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert (p["int8"], p["bf16"], p["hbm_bytes_per_s"]) == (393e12, 197e12,
                                                            819e9)
    assert "TPU v5e" in p["source"]
    with pytest.raises(flops.UnknownDevice):
        flops.peaks("cpu")


def test_qmatmul_w8a8_gate_projection():
    # decode, 128 slots: x [128, 896] int8 @ W [896, 4864] int8 -> bf16
    ops, nbytes = flops.qmatmul_w8a8(128, 896, 4864)
    assert ops == 2 * 128 * 896 * 4864 == 1_115_684_864
    # A 114,688 + W 4,358,144 + row scales 512 + col scales and bias
    # 38,912 + out 1,245,184
    assert nbytes == 5_757_440
    t, bound = flops.least_time(ops, nbytes, flops.peaks("TPU v5 lite"),
                                "int8")
    assert bound == "memory" and t == pytest.approx(5_757_440 / 819e9)


def test_qmatmul_w8a16_down_projection():
    ops, nbytes = flops.qmatmul_w8a16(2048, 4864, 896)
    assert ops == 2 * 2048 * 4864 * 896
    # A bf16 19,922,944 + W 4,358,144 + scales and bias 7,168 + out
    # 3,670,016
    assert nbytes == 27_958_272
    t, bound = flops.least_time(ops, nbytes, flops.peaks("TPU v5 lite"),
                                "bf16")
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


def test_quantize_act_rows():
    assert flops.quantize_act(128, 4864) == (3 * 128 * 4864,
                                             2 * 622_592 + 622_592 + 512)


def test_fused_decode_whole_ring():
    ops, nbytes = flops.fused_decode(128, 2560, 14, 2, 64)
    assert ops == 4 * 128 * 14 * 2560 * 64
    # K and V with their scales, 128 x 2560 x 2 x 68 bytes each, read and
    # written back: 4 x 44,564,480; q bf16 and out fp32 688,128; the new
    # K and V bf16 65,536
    assert nbytes == 4 * 44_564_480 + 128 * 14 * 64 * 6 + 65_536


def test_decode_step_calls_cover_every_kernel_of_the_recipe():
    calls = flops.decode_step_calls(M, 128, 2560, "serve-w8a8-kv8")
    assert {k: len(v["calls"]) for k, v in calls.items()} == {
        "qmatmul_w8a8": 7 * 24, "quantize_act": 3 * 24, "fused_decode": 24}
    # each kernel's peak comes with its calls
    assert {k: v["rate"] for k, v in calls.items()} == {
        "qmatmul_w8a8": "int8", "quantize_act": "bf16",
        "fused_decode": "bf16"}
    w8a16 = flops.decode_step_calls(M, 128, 2560, "serve-w8a16")
    assert list(w8a16) == ["qmatmul_w8a16"]
    assert w8a16["qmatmul_w8a16"]["rate"] == "bf16"


def test_model_flops_per_token():
    # 24 x (896x896 + 2 x 896x128 + 896x896 + 3 x 896x4864) x 2
    assert flops.linear_flops_per_token(M) == 2 * 24 * 14_909_440
    assert flops.attention_flops(M, 1000) == 4 * 24 * 14 * 64 * 1000
    assert flops.head_flops(M) == 2 * 896 * 151936
