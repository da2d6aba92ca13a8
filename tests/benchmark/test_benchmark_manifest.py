"""BENCHMARK.json and the benchmark's data files: legal, consistent, and
driven by data. Plus the yardstick's small pure functions on hand-made
inputs. No accelerator, no subprocess."""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, flops, trace_reduce  # noqa: E402
from benchmark.readers import (counter_ratio, device_idle, mfu,  # noqa: E402
                               module_time, record_percentile)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_manifest_is_legal_and_consistent():
    bm = _manifest()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= bm["run_seconds"] <= 51 and isinstance(bm["run_seconds"], int)
    assert all(_line(w) for w in bm["command"]) and len(bm["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    cells = {w["name"]: w for w in bm["workloads"]}
    configs = {c["name"]: c for c in bm["configs"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert len(cells) == len(bm["workloads"]) <= 24
    assert len(e2e) == len(bm["end_to_end"]) and "setup_s" in e2e
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bm["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bm["workloads"])
    pairs = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"]) and (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = common.load_json(os.path.join(ROOT, "benchmark"),
                                   "traffic", w["traffic"])
        importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
        limits = common.load_json(os.path.join(ROOT, "benchmark"),
                                  "limits", w["name"])
        assert limits["limits"] and all(v >= 0 for v in limits["limits"].values())
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(cells) // 4)
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert all(w in cells for w in m.get("workloads", cells))
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))

    def reports(cell, metric):
        return cell in e2e[metric].get("workloads", cells)

    for cell in cells:
        assert sum(reports(cell, m) for m in e2e) >= 2   # setup_s + one more
        assert any(cell in m.get("workloads", cells) for m in bm["per_layer"])
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in e2e
        assert all(reports(c, m["moves"]) for c in m.get("workloads", cells))
        # the metric's own file names a reader that exists, and nothing
        # that BENCHMARK.json already says
        spec = common.load_json(os.path.join(ROOT, "benchmark"),
                                "layer_metrics", m["name"])
        assert set(spec) == {"reader", "args"}
        assert hasattr(importlib.import_module(
            f"benchmark.readers.{spec['reader']}"), "read")
    # a whole step's share of the peak stands beside each kernel-level time
    for sfx in (".train", ".drain", ".serve"):
        assert any("mfu" in m["name"] and sfx.strip(".") in
                   " ".join(m["workloads"]) for m in bm["per_layer"])
    # run.py holds no cell's or configuration's name
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        src = f.read()
    assert not any(word in src for word in list(cells) + list(configs))


def test_yardstick_arithmetic_on_hand_made_inputs():
    assert common.percentile([], 50) is None
    assert common.percentile([3.0], 95) == 3.0
    assert common.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert common.percentile(range(101), 95) == pytest.approx(95.0)
    assert common.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    recs = [{"status": "done", "arrival_t": float(i), "seat_t": i + 0.1 * i}
            for i in range(11)]
    recs.append({"status": "shed_deadline", "arrival_t": 1.0,
                 "seat_t": float("nan")})
    ctx = {"records": recs}
    assert record_percentile.read(ctx, end="seat_t", start="arrival_t",
                                  q=50) == pytest.approx(0.5)
    assert record_percentile.read({"records": []}, end="seat_t",
                                  start="arrival_t", q=95) is None
    # flops.py is a copy of the program's own count
    import bench
    from fira_tpu.config import get_config

    cfg = get_config("fira-full").replace(vocab_size=24650)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "fira-full.json")) as f:
        mcfg = json.load(f)["model"]
    mine = flops.train_step_flops({**mcfg, "adjacency_impl": "dense"}, 170)
    assert mine == bench._analytic_flops(cfg, 170)
    assert mine == pytest.approx(2.03e12, rel=0.01)
    assert flops.prefill_flops(mcfg) > 0
    assert flops.decode_position_flops(mcfg, 8.0) > \
        flops.decode_position_flops(mcfg, 2.0)
    # counters and the whole step's share of the peak
    ctx = {"counters": {"feed_stall_s": 2.0, "window_s": 10.0, "slots": 4,
                        "steps": 5, "occupied_slot_steps": 10, "flops": 1e14},
           "window_s": 10.0, "peak_flops": 1e14}
    assert counter_ratio.read(ctx, num="feed_stall_s", den=["window_s"],
                              scale=100.0) == pytest.approx(20.0)
    assert counter_ratio.read(ctx, num="occupied_slot_steps",
                              den=["slots", "steps"], scale=100.0) == 50.0
    assert counter_ratio.read(ctx, num="missing", den=["steps"]) is None
    assert mfu.read(ctx) == pytest.approx(10.0)
    assert mfu.read({**ctx, "peak_flops": None}) is None


def test_trace_reduction_on_a_hand_made_event_list():
    ms = 1_000_000
    ops = [("%cut", 2 * ms, 3 * ms),              # the device's trace begins
           ("%while", 10 * ms, 40 * ms),          # holds the two below
           ("%fusion.1", 10 * ms, 15 * ms), ("%fusion.2", 30 * ms, 20 * ms),
           ("%copy.3", 70 * ms, 10 * ms), ("%late", 120 * ms, 10 * ms)]
    modules = [("jit_multi_step(123)", 2 * ms, 3 * ms),    # cut off at the start
               ("jit_multi_step(123)", 10 * ms, 40 * ms),
               ("jit__step_fn(9)", 70 * ms, 10 * ms),
               ("jit_multi_step(123)", 95 * ms, 10 * ms)]   # leaves the window
    host = [("bench.window", 0, 100 * ms), ("feed.next", 50 * ms, 20 * ms),
            ("dispatch", 80 * ms, 5 * ms)]
    r = trace_reduce.reduce_events(ops, modules, host)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.053)     # [2,5) + [10,50) + [70,80)
    assert r["modules"]["jit_multi_step"] == {"count": 1,
                                              "seconds": pytest.approx(0.04)}
    assert dict(r["device_ops"])["%fusion.2"] == pytest.approx(0.020)
    assert "%while" not in dict(r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert gaps["feed.next"] == pytest.approx(0.020)       # [50, 70)
    assert gaps["dispatch"] == pytest.approx(0.020)        # [80, 100)
    assert gaps["host:untraced"] == pytest.approx(0.007)   # [0, 2) + [5, 10)
    ctx = {"trace": r, "counters": {"steps_per_dispatch": 8}}
    assert device_idle.read(ctx) == pytest.approx(47.0)
    assert module_time.read(ctx, module="multi_step",
                            per="steps_per_dispatch") == pytest.approx(5.0)
    assert module_time.read(ctx, module="nowhere") is None
    assert device_idle.read({"trace": None}) is None
    empty = trace_reduce.reduce_events([], [], [])
    assert device_idle.read({"trace": empty}) is None      # never a 0 share
