"""Where the benchmark's pieces live, found by the names in ``BENCHMARK.json``.

    <root>/BENCHMARK.json                       cells, configurations, metrics
    <root>/benchmarks/chip/configs/<config>.json    one per configuration
    <root>/benchmarks/chip/traffic/<mix>.json       one per traffic mix
    <root>/benchmarks/chip/cells/<cell>.json        pinned numbers of one cell
    <root>/benchmarks/chip/metrics/<metric>.py      one reader per metric
    <root>/benchmarks/chip/chipbench/archs/<model_type>.py
                                        one per architecture (``model.py``)

A later change adds a configuration, a mix, a cell, a metric or an
architecture by adding files and entries; no existing file has to change.
A configuration file names its architecture by ``model_type``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


class LayoutError(RuntimeError):
    """A cell, configuration, mix or metric named in BENCHMARK.json is
    missing or malformed."""


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise LayoutError(f"missing file {path}") from None


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable[[Any], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                    # configs/<config>.json
    mix: dict                       # traffic/<mix>.json
    pinned: dict                    # cells/<cell>.json
    end_to_end: list                # [Metric] this cell reports untraced
    per_layer: list                 # [Metric] this cell reports traced
    arch: Any = None                # chipbench/archs/<model_type>.py


def _module(path: Path, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(bench_dir: Path, name: str) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise LayoutError(f"metric {name!r} has no reader {path}")
    mod = _module(path, "chipbench_metric_", name)
    if not callable(getattr(mod, "read", None)):
        raise LayoutError(f"{path} defines no read(ctx)")
    return mod.read


ARCH_NEEDS = ("dims", "program_config", "param_shapes", "init_leaf", "layer",
              "head")
_ARCHS: dict = {}


def load_arch(model_type: str, bench_dir: Path = BENCH_DIR):
    """The architecture file of ``model_type``, loaded once per path."""
    path = bench_dir / "chipbench" / "archs" / f"{model_type}.py"
    if path not in _ARCHS:
        if not path.is_file():
            raise LayoutError(f"model_type {model_type!r} has no "
                              f"architecture file {path}")
        mod = _module(path, "chipbench_arch_", str(model_type))
        missing = [f for f in ARCH_NEEDS if not callable(getattr(mod, f,
                                                                 None))]
        if missing:
            raise LayoutError(f"{path} defines no {', '.join(missing)}")
        _ARCHS[path] = mod
    return _ARCHS[path]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` with everything it needs, read from ``root``'s
    BENCHMARK.json and the files under ``bench_dir``."""
    bench_dir = bench_dir or root / "benchmarks" / "chip"
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LayoutError(f"no workload {name!r} in BENCHMARK.json; known: "
                          f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise LayoutError(f"workload {name!r} names unknown config "
                          f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    mix = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    pinned = _load_json(bench_dir / "cells" / f"{name}.json")

    def metrics(kind):
        return [Metric(m["name"], m["unit"], _reader(bench_dir, m["name"]))
                for m in bench[kind] if _applies(m, name)]

    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                pinned=pinned, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"),
                arch=load_arch(config.get("model_type"), bench_dir))
