"""Shared pytest config: import path and the ``slow`` marker.

Tests marked ``slow`` are skipped unless ``--run-slow`` is given.
"""

from __future__ import annotations

import os
import sys

import pytest

# `python -m pytest` without PYTHONPATH=src must still collect everything
_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test; needs --run-slow to execute"
    )


def pytest_addoption(parser):
    parser.addoption(
        "--run-slow", action="store_true", default=False,
        help="run tests marked slow",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --run-slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
