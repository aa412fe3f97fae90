"""A cell on several chips: set-up builds the configuration's mesh over the
cell's devices, makes the weights where the program's serve-mode partition
specs place them, and drives the sharded engine through a window. On four
virtual CPU devices, in a process of its own (the device count is fixed
when JAX starts)."""
import json
import os
import subprocess
import sys

from chipbench_tiny import BENCH, REPO

DRIVE = r"""
import argparse, json, sys
from pathlib import Path
sys.path.insert(0, {bench!r}); sys.path.insert(0, {tests!r})
import jax
import chipbench_tiny as t
import run

root = Path({root!r})
bench = t.make_tiny_checkout(root, gap_limit=10.0)
cfg = json.loads((bench / "configs" / "tiny.json").read_text())
# widths the program shards (it leaves dims under 128 whole)
cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
           intermediate_size=256)
cfg["serving"]["mesh"] = {{"data": 2, "model": 2}}
(bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
b = json.loads((root / "BENCHMARK.json").read_text())
for w in b["workloads"]:
    w["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(b))
run._configure_jax = lambda root: None

args = argparse.Namespace(workload="tiny.closed", seed=2 ** 31 + 5,
                          seconds=1.0, trace=0, keep_trace=None)
su = run.prepare(args, root, bench, require_tpu=False)
e = su.engine
out = {{"mesh": dict(e.mesh.shape),
        "wq": str(e.params["blocks"]["attn"]["wq"].q.sharding.spec),
        "pool_devices": len(e.pool.cache["k"].sharding.device_set)}}
del su, e
out["result"] = run.run_cell(args, root, bench, require_tpu=False)
print(json.dumps(out))
"""


def test_a_four_chip_cell_runs_on_its_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    code = DRIVE.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                        root=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       capture_output=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["mesh"] == {"data": 2, "model": 2}
    assert "model" in out["wq"]                  # q's columns over "model"
    assert out["pool_devices"] == 4
    r = out["result"]
    assert r["correct"] and r["attempted"] > 0
    assert r["device"]["count"] == 4
    assert "mesh {'data': 2, 'model': 2}" in p.stderr
    assert "needs" in p.stderr and "bytes a device" in p.stderr


SHARE = r"""
import json, sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {tests!r})
import jax
import numpy as np
from jax.sharding import Mesh
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serving.cache_pool import CachePool
import chipbench_tiny as t
import run
from chipbench import model

out = []
for hkv, shape in [(2, (1, 4)), (2, (2, 2)), (4, (1, 4))]:
    cfg = dict(t.TINY_CONFIG, num_attention_heads=4, num_key_value_heads=hkv)
    s = cfg["serving"]
    prog = build_model(model.program_config(cfg, ModelConfig))
    mesh = Mesh(np.array(jax.devices()).reshape(shape), ("data", "model"))
    share = run.pool_share(prog, s, mesh)
    pool = CachePool(prog, s["num_slots"], s["max_len"],
                     kv_bits=s["kv_bits"], mesh=mesh)
    held = {{}}
    for leaf in jax.tree.leaves(pool.cache):
        for sh in leaf.addressable_shards:
            held[sh.device] = held.get(sh.device, 0) + sh.data.nbytes
    whole = sum(leaf.nbytes for leaf in jax.tree.leaves(pool.cache))
    out.append({{"hkv": hkv, "mesh": shape, "share": share,
                 "held": max(held.values()) / whole}})
print(json.dumps(out))
"""


def test_the_pool_check_counts_what_a_device_holds(tmp_path):
    """The pool check's share of the pool is what the fullest device
    holds: the whole pool where neither the slots over "data" nor the kv
    heads over "model" divide (2 kv heads on 4 model shards), a quarter
    where both split (bookkeeping over "data" alone aside)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    code = SHARE.format(bench=str(BENCH), tests=str(BENCH / "tests"))
    p = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                       capture_output=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    rows = json.loads(p.stdout.strip().splitlines()[-1])
    for r in rows:
        assert abs(r["share"] - r["held"]) < 1e-12, r
    by = {(r["hkv"], tuple(r["mesh"])): r["share"] for r in rows}
    assert by[(2, (1, 4))] == 1.0
    assert 0.25 <= by[(2, (2, 2))] < 0.3
    assert 0.25 <= by[(4, (1, 4))] < 0.3
