"""The benchmark harness reports a failing benchmark and exits non-zero
instead of printing an ERROR row and succeeding."""
import sys

import pytest


def test_failing_benchmark_exits_nonzero(monkeypatch, capsys):
    from benchmarks import run, tables

    def boom():
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(tables, "ALL_TABLES",
                        {"ok": lambda: [("row", 1)], "boom": boom})
    monkeypatch.setattr(sys, "argv",
                        ["run", "--skip-lm", "--only", "ok,boom"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code == "benchmark(s) failed: boom"
    out = capsys.readouterr()
    assert "ok.row," in out.out and "boom.ERROR" in out.out
    assert "RuntimeError: kernel refused" in out.err
