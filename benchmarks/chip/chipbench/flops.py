"""Peaks of the chips, and the operations and bytes that the work needs,
computed from shapes.

A kernel's least time is max(operations / peak rate, bytes / HBM
bandwidth); its roofline share is that over the time the trace gives it.
Bytes count what the kernel must move between HBM and the core as it is
written today, once each: its inputs read and its outputs written.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, TPU v5e",
        "bf16": 197e12, "int8": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(KeyError):
    """A device that the table of peaks does not hold."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; the table holds "
            f"{sorted(PEAKS)}") from None


def least_time(ops: float, nbytes: float, peak: dict, rate: str) -> tuple:
    """(seconds, bound): the larger of the compute and the memory time."""
    tc, tm = ops / peak[rate], nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# --------------------------------------------------------------- kernels
# Each returns (ops, bytes) for one call.

def qmatmul_w8a8(M, K, N, out_bytes=2):
    """int8 x int8 -> out: A, W, row and column scales, bias, out."""
    return 2 * M * K * N, M * K + K * N + 4 * M + 8 * N + out_bytes * M * N


def qmatmul_w8a16(M, K, N, a_bytes=2, out_bytes=2):
    """bf16 x int8 (dequantized in the kernel) -> out."""
    return 2 * M * K * N, a_bytes * M * K + K * N + 8 * N + out_bytes * M * N


def quantize_act(M, K, in_bytes=2):
    """per-row absmax, divide, round: read x, write int8 and a scale."""
    return 3 * M * K, in_bytes * M * K + M * K + 4 * M


def fused_decode(B, S, Hq, Hkv, hd, q_bytes=2):
    """One decode token per slot attending its whole ring of S positions
    over an int8 cache (payload + float32 per-position, per-head scales),
    which the kernel reads and writes back in full; plus q, the new K/V
    and the output."""
    ops = 4 * B * Hq * S * hd
    ring = B * S * Hkv * (hd + 4)            # one of K or V, with scales
    return ops, 4 * ring + B * Hq * hd * (q_bytes + 4) + 2 * B * Hkv * hd * 2


# the peak each kernel of the default decode plan is held to
RATE = {"qmatmul_w8a8": "int8", "qmatmul_w8a16": "bf16",
        "quantize_act": "bf16", "fused_decode": "bf16"}


def decode_step_calls(m: dict, B: int, S: int, recipe: str) -> dict:
    """{kernel: {"rate": the peak its operations are held to, "calls":
    [(ops, bytes) per call]}} for one decode step of the whole model at
    slot batch B and ring S, as the recipe's decode program calls its
    Pallas kernels (per layer: q, k, v, o, gate, up, down). The harness's
    default, for a dense GQA stack; an architecture file may give its
    own."""
    D, F, Hq, Hkv, hd, L = m["D"], m["F"], m["Hq"], m["Hkv"], m["hd"], m["L"]
    proj = [(D, Hq * hd), (D, Hkv * hd), (D, Hkv * hd), (Hq * hd, D),
            (D, F), (D, F), (F, D)]
    if recipe.startswith("serve-w8a8"):
        calls = {
            "qmatmul_w8a8": [qmatmul_w8a8(B, k, n) for k, n in proj],
            # one shared quantize for q/k/v, one for gate/up, one for down
            # (o reads the int8 epilogue of fused_decode)
            "quantize_act": [quantize_act(B, D), quantize_act(B, D),
                             quantize_act(B, F)],
            "fused_decode": [fused_decode(B, S, Hq, Hkv, hd)],
        }
    elif recipe.startswith("serve-w8a16"):
        calls = {"qmatmul_w8a16": [qmatmul_w8a16(B, k, n) for k, n in proj]}
    else:
        raise ValueError(f"no decode kernel plan for recipe {recipe!r}")
    return {k: {"rate": RATE[k], "calls": v * L} for k, v in calls.items()}


# ----------------------------------------------------------------- model

def linear_flops_per_token(m: dict) -> float:
    """2 x the projection weights of all layers (no head, no attention)."""
    D, F, Hq, Hkv, hd = m["D"], m["F"], m["Hq"], m["Hkv"], m["hd"]
    per_layer = D * Hq * hd + 2 * D * Hkv * hd + Hq * hd * D + 3 * D * F
    return 2.0 * m["L"] * per_layer


def attention_flops(m: dict, positions: float) -> float:
    """q k^T and p v for one query over ``positions`` keys, all layers."""
    return 4.0 * m["L"] * m["Hq"] * m["hd"] * positions


def head_flops(m: dict) -> float:
    return 2.0 * m["D"] * m["V"]


def model_flops(m: dict, tokens: float, attended: float,
                heads: float) -> float:
    """The model's operations for ``tokens`` processed, ``attended`` key
    positions summed over them, and ``heads`` rows through the head (the
    harness's default, for a dense GQA stack)."""
    return (tokens * linear_flops_per_token(m) + attention_flops(m, attended)
            + heads * head_flops(m))
