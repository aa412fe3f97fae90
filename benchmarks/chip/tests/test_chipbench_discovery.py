"""Cells, configurations, mixes and metrics are found by the names in
BENCHMARK.json, as files; adding one edits no existing file."""
import json

import pytest

from chipbench import layout


def test_every_benchmark_entry_has_its_files():
    bench = json.loads((layout.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = layout.load_cell(w["name"])
        names = {m.name for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names
        assert cell.config["name"] == w["config"]


def test_added_files_are_found(tiny):
    root, bench = tiny
    # a new configuration, mix, cell and metric, added as files only
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny2"
    (bench / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "open.json").read_text())
    mix["arrivals"]["cv"] = 1.0
    (bench / "traffic" / "steady.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny2.steady.json").write_text(
        json.dumps({"max_logit_gap": 1.0, "rate_per_s": 2.0}))
    (bench / "metrics" / "answer.per_run.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny2", "source": "test", "reduced": [],
                         "file": "benchmarks/chip/configs/tiny2.json",
                         "why": "test"})
    b["workloads"].append({"name": "tiny2.steady", "config": "tiny2",
                           "traffic": "steady", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "answer.per_run", "unit": "%",
                           "workloads": ["tiny2.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = layout.load_cell("tiny2.steady", root, bench)
    assert cell.config["name"] == "tiny2"
    assert cell.mix["arrivals"]["cv"] == 1.0
    assert cell.pinned["rate_per_s"] == 2.0
    assert [m.name for m in cell.per_layer] == ["answer.per_run"]
    assert cell.per_layer[0].read(None) == 42.0
    assert layout.load_cell("tiny.open", root, bench).per_layer == []


def test_missing_files_are_named(tiny):
    root, bench = tiny
    (bench / "cells" / "tiny.open.json").unlink()
    with pytest.raises(layout.LayoutError, match="tiny.open.json"):
        layout.load_cell("tiny.open", root, bench)
    with pytest.raises(layout.LayoutError, match="no workload"):
        layout.load_cell("nope", root, bench)
