"""A tiny qwen2-shaped cell laid out in a temporary checkout, runnable on
the CPU, for the chip benchmark's tests."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

TINY_CONFIG = {
    "name": "tiny", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "weights": {"embed_std": 0.02, "norm_std": 0.1, "qkv_bias_std": 0.05},
    "serving": {"recipe": "serve-w8a8-kv8", "kv_bits": 8,
                "compute_dtype": "bfloat16", "num_slots": 4, "max_len": 96,
                "prefill_chunk": 16, "decode_horizon": 4},
    "precision": {"weights": 8, "activations": 8, "kv_cache": 8,
                  "head": "float32", "matmul_peak": "int8"},
    "control": {"weights": 4, "activations": 4, "kv_cache": 4,
                "head": "bfloat16"},
}
# a mistral-shaped configuration (no biases, untied head, head_dim not
# hidden / heads) made and quantized layer by layer
TINY_MISTRAL = dict(
    TINY_CONFIG, name="tiny-mistral", model_type="mistral", head_dim=32,
    num_hidden_layers=3, tie_word_embeddings=False,
    weights={"embed_std": 0.02, "norm_std": 0.1, "by_layer": True})
TINY_MIXES = {
    "closed": {"loop": "closed", "backlog_per_slot": 2,
               "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 32},
               "output": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 8, "max": 64},
               "sample": {"requests": 3}},
    "open": {"loop": "open", "arrivals": {"dist": "gamma", "cv": 2.0},
             "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                        "min": 4, "max": 48},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                        "min": 2, "max": 32},
             "sample": {"requests": 3}},
}


def make_tiny_checkout(root: Path, gap_limit: float = 10.0,
                       rate: float = 4.0) -> Path:
    """A checkout holding BENCHMARK.json and the benchmark's files (metric
    readers and architecture files), with the tiny configuration and its
    two cells (``tiny.closed``, ``tiny.open``); the program is linked in.
    Returns the benchmark's directory."""
    bench = root / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copytree(BENCH / "chipbench" / "archs", bench / "chipbench" / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", root / "src")
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    cells = []
    for mix, spec in TINY_MIXES.items():
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(spec))
        pinned = {"max_logit_gap": gap_limit, "rate_per_s": rate}
        (bench / "cells" / f"tiny.{mix}.json").write_text(json.dumps(pinned))
        cells.append({"name": f"tiny.{mix}", "config": "tiny",
                      "traffic": mix, "chips": 1, "why": "test"})
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench_json = {
        "configs": [{"name": "tiny", "source": "test",
                     "file": "benchmarks/chip/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": cells,
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench_json))
    return bench
