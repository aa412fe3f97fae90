"""run.py end to end on the CPU at a tiny size: it refuses to run without a
TPU or without the program, a sound run is correct, the control judged in
the program's place is not, and a planted fault turns ``correct`` false."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import BENCH, REPO

import run

# The tiny cells' limit on the widest logit gap. On the CPU, at the tiny
# size, sound runs read at most 0.028 over 6 seeds, the int4 control at
# least 0.147, and the two planted faults at least 0.34.
TINY_GAP_LIMIT = 0.08


def _cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2-0.5b.w8a8-kv8.decode-heavy", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _cmd(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "program is not in this checkout" in p.stderr
    assert p.stdout.strip() == ""


@pytest.fixture
def tiny_run(tmp_path, monkeypatch):
    from chipbench_tiny import make_tiny_checkout

    bench = make_tiny_checkout(tmp_path, gap_limit=TINY_GAP_LIMIT)
    monkeypatch.setattr(run, "_configure_jax", lambda root: None)

    def go(cell, seed=3, control=False):
        args = argparse.Namespace(workload=cell, seed=seed, seconds=1.0,
                                  trace=0, keep_trace=None)
        return run.run_cell(args, tmp_path, bench, require_tpu=False,
                            control=control)
    return go


@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_sound_run_is_correct_and_the_control_is_not(tiny_run, cell):
    r = tiny_run(cell, seed=2 ** 31 + 3, control=True)
    json.dumps(r)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    c = r["checks"]
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert c["control_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert r["control_correct"] is False
    assert (c["control_logit_gap"]["value"]
            >= 3 * c["max_logit_gap"]["value"])
    assert set(r["metrics"]) == {"setup_s", "decode_tok_s", "ttft_p95_ms",
                                 "tpot_p95_ms"}
    assert list(r)[-1] == "checks"


def _plant(monkeypatch, fault):
    """Break the decode step the window drives, where its tokens and its
    cache are produced."""
    import jax
    import jax.numpy as jnp
    from repro.serving import ServingEngine

    init = ServingEngine.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        step = self._decode_horizon_fn

        def token(params, tokens, cache, remaining, *, k):
            toks, bad, cache = step(params, tokens, cache, remaining, k=k)
            return (toks + 1) % self.cfg.vocab_size, bad, cache

        def unchanged(params, tokens, cache, remaining, *, k):
            toks, bad, _ = step(params, tokens,
                                jax.tree.map(jnp.copy, cache), remaining, k=k)
            return toks, bad, cache

        self._decode_horizon_fn = {"token": token,
                                   "state": unchanged}[fault]

    monkeypatch.setattr(ServingEngine, "__init__", broken_init)


@pytest.mark.parametrize("fault", ["token", "state"])
@pytest.mark.parametrize("cell", ["tiny.closed", "tiny.open"])
def test_a_planted_fault_is_not_correct(tiny_run, monkeypatch, cell, fault):
    _plant(monkeypatch, fault)
    r = tiny_run(cell)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT
