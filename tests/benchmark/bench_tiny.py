"""Shared by the driver tests: one run of a tiny cell on the CPU, in
process, through the same ``run.run`` the command uses."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny")
MANIFEST = os.path.join(TINY, "BENCHMARK.json")


def run_cell(traffic: str, seed: int = 3, seconds: float = 0.5, extra=()):
    from benchmark import run

    args = run._args(["--workload", f"fira-tiny.{traffic}", "--seed",
                      str(seed), "--seconds", str(seconds), "--trace", "0",
                      "--allow-cpu"])
    return run.run(args, MANIFEST, TINY, extra=extra)
