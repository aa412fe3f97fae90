"""Fixtures of the chip benchmark's tests."""
import pytest

from chipbench_tiny import make_tiny_checkout


@pytest.fixture
def tiny(tmp_path):
    return tmp_path, make_tiny_checkout(tmp_path)
