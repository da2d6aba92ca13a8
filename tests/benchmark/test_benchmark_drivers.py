"""Each driver end to end at fira-tiny on the CPU (``--allow-cpu``): the
result line has the contract's keys, carries no time, rate or share, and the
timed path agrees with the plain reference to float32 rounding."""

import json

import pytest

from bench_tiny import MANIFEST, TINY

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("traffic", ["train", "drain", "serve"])
def test_driver_prints_the_contracts_line(traffic, capsys):
    from benchmark import run

    rc = run.main(["--workload", f"fira-tiny.{traffic}", "--seed",
                   str(2 ** 31 + 17), "--seconds", "0.5", "--trace", "0",
                   "--allow-cpu"], MANIFEST, TINY)
    assert rc == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line) and list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}            # a CPU run reports no device number
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each number compared beside its limit: in the line and on stderr
    assert all(c["value"] <= c["limit"] for c in line["check"].values())
    last_err = err.strip().splitlines()[-len(line["check"]):]
    assert all(l.startswith("check ") and "limit" in l for l in last_err)


def test_same_seed_same_inputs():
    import numpy as np

    from benchmark import common, weights

    with open(TINY + "/configs/fira-tiny.json") as f:
        config = json.load(f)
    big = 2 ** 31 + 12345                    # wider than 32 signed bits
    leaves = [np.asarray(weights.make_params(config["model"], s)
                         ["out_fc"]["kernel"]) for s in (big, big, big + 1, 12345)]
    assert (leaves[0] == leaves[1]).all()
    assert not (leaves[0] == leaves[2]).all()
    assert not (leaves[0] == leaves[3]).all()   # the high bits count
    cfg = common.program_cfg(config, "train_knobs")
    splits = [common.make_corpus(cfg, config, 8, s)[1] for s in (big, big, 7)]
    a, b, c = (s.arrays["diff"] for s in splits)
    assert (a == b).all() and a.shape == c.shape and not (a == c).all()
    eos = weights.make_params(config["model"], 1, eos_bias=2.5)
    plain = weights.make_params(config["model"], 1)
    delta = np.asarray(eos["out_fc"]["bias"]) - np.asarray(plain["out_fc"]["bias"])
    assert delta[weights.EOS_ID] == pytest.approx(2.5) and delta.sum() == pytest.approx(2.5)
    # arrivals: every seed offers the same set of gaps, in another order
    from benchmark import arrivals

    x = arrivals.arrivals_for_window(40.0, 5.0, seed=1)
    y = arrivals.arrivals_for_window(40.0, 5.0, seed=big)
    assert len(x) == len(y) == 200
    assert (x[1:] >= x[:-1]).all() and x[-1] < 5.0 and y[-1] < 5.0
    assert sorted(np.diff(x, prepend=0.0).round(9)) == \
        sorted(np.diff(y, prepend=0.0).round(9))
    assert not (x == y).all()
    assert (x == arrivals.arrivals_for_window(40.0, 5.0, seed=1)).all()


def test_unknown_workload_and_bare_directory(tmp_path):
    import shutil
    import subprocess
    import sys

    from benchmark import run

    with pytest.raises(SystemExit):
        run.main(["--workload", "no-such.cell", "--seed", "1", "--seconds",
                  "1", "--allow-cpu"], MANIFEST, TINY)
    # a directory that holds only BENCHMARK.json and the benchmark's paths:
    # no result, another exit code than 0 (the system under test is missing;
    # nothing here can reach jax, let alone an accelerator)
    import os
    root = os.path.dirname(os.path.dirname(TINY.rstrip("/")))
    root = os.path.dirname(root)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fira-full.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
