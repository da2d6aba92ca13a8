"""Every benchmark test writes its run's files (the serve loop's output, a
trace) under its own temporary directory: the workers of one test run share
the checkout, and two serve runs in one ``.bench_out/`` race."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(autouse=True)
def private_out_dir(tmp_path, monkeypatch):
    from benchmark import run

    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path / "bench_out"))
