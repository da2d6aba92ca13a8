"""The start-up layer's three metrics (``readers/setup_stat.py``): the
set-up stretch found from the program's roots on hand-made rings, on the
rings that tiny train, drain and serve runs of the benchmark's own drivers
leave, and the manifest's three new entries."""

import json
import math
import os
import time

import pytest

import bench_tiny
from fira_tpu.utils import profiling
from benchmark import run
from benchmark.readers import setup_stat

ROOT = bench_tiny.ROOT
NEW = {"setup_build_s": ("s", "lower", "program_counter", "build_s"),
       "setup_cache_hit_share": ("%", "higher", "program_counter",
                                 "cache_hit_share"),
       "setup_unspanned_s": ("s", "lower", "program_span", "unspanned_s")}
STATS = [spec[3] for spec in NEW.values()]


def ev(name, t0, t1, **ids):
    return profiling.Event(0, 0, name, t0, t1, 0, ids or None)


def _builds():
    """From a process start at 0: tracing 1-3 holding a nested trace,
    its lowering 3-4, a backend build 4-6 the cache served, one 10-12 that
    compiled, and a build inside the window at 50 that set-up never saw."""
    return [ev("corpus.build", 0.5, 2.0),
            ev("jax.trace", 1.0, 3.0, program="f"),
            ev("jax.trace", 1.5, 2.5, program="g"),
            ev("jax.lower", 3.0, 4.0, program="jit(f)"),
            ev("xla.compile", 4.0, 6.0, program="jit(f)", cache="hit",
               load_s=0.5),
            ev("engine.prewarm", 9.0, 13.0),
            ev("xla.compile", 10.0, 12.0, program="jit(h)", cache="miss"),
            ev("jax.trace", 50.0, 50.5, program="late")]


@pytest.mark.parametrize("events, counters, rooted", [
    # serve: the start of the last serve.run (the burst, 14-15, is an
    # earlier one)
    ([ev("serve.run", 14.0, 15.0), ev("serve.run", 20.0, 60.0)],
     {"window_s": 40.0}, 1.0),
    # drain: the end of the last engine.run less the window; the root's
    # warm-up, 15-20, is set-up the ring covers
    ([ev("engine.run", 15.0, 60.0)], {"window_s": 40.0}, 5.0),
    # train: the start of the dispatches-th last feeder.next (set-up's own
    # is 14-15)
    ([ev("feeder.next", 14.0, 15.0)]
     + [ev("feeder.next", 20.0 + 10 * i, 20.1 + 10 * i) for i in range(4)],
     {"window_s": 40.0, "dispatches": 4}, 1.0),
], ids=["serve", "drain", "train"])
def test_the_stretch_from_each_root_on_hand_made_rings(events, counters,
                                                       rooted):
    end = 20.0
    ring = _builds() + events
    assert setup_stat.window_start(ring, counters) == end

    def m(stat):
        return setup_stat.measure(ring, 0.0, counters, stat)

    # builds: the union of 1-3 (holding 1.5-2.5), 3-4, 4-6, 10-12
    assert m("build_s") == pytest.approx(7.0)
    # covered: 0.5-6 (corpus and builds), 9-13 (prewarm and its build) and
    # the roots' set-up; the rest is nobody's
    assert m("unspanned_s") == pytest.approx(end - 5.5 - 4.0 - rooted)
    assert m("cache_hit_share") == pytest.approx(50.0)
    assert m("build_s") + m("unspanned_s") <= end


def test_nothing_to_read():
    ring = _builds() + [ev("engine.run", 15.0, 60.0)]
    counters = {"window_s": 40.0}
    for stat in STATS:
        # no process start (the recorder before this PR), a wrapped ring,
        # no window in the counters (no run's context), no root, a window
        # that ends before the process started
        assert setup_stat.measure(ring, None, counters, stat) is None
        assert setup_stat.measure(ring, 0.0, counters, stat, dropped=3) \
            is None
        assert setup_stat.measure(ring, 0.0, {}, stat) is None
        assert setup_stat.measure(_builds(), 0.0, counters, stat) is None
        assert setup_stat.measure(ring, 30.0, counters, stat) is None
    # a train ring with fewer feeder.next than dispatches
    assert setup_stat.window_start(
        [ev("feeder.next", 1.0, 2.0)], {"window_s": 1.0, "dispatches": 2}) \
        is None
    # no backend build in the stretch: no share to read
    assert setup_stat.measure([ev("engine.run", 15.0, 60.0)], 0.0,
                              counters, "cache_hit_share") is None
    with pytest.raises(ValueError):
        setup_stat.measure(ring, 0.0, counters, "nonsense")


def test_reader_over_a_program_without_the_clock(monkeypatch):
    """The driver lays the reader over the parent commit too, whose
    recorder has no ``process_start``: the metric is left out, no raise."""
    monkeypatch.delattr(profiling, "process_start")
    for stat in STATS:
        assert setup_stat.read({"counters": {"window_s": 1.0}},
                               stat=stat) is None


def test_union_is_never_a_sum():
    assert setup_stat.union_s([(0, 4), (1, 2), (3, 5), (7, 8)], 0, 10) == 6
    assert setup_stat.union_s([(0, 4), (1, 2)], 2, 3) == 1
    assert setup_stat.union_s([], 0, 1) == 0


# --------------------------------------------------------------------------
# the rings the benchmark's own drivers leave
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_runs():
    """Each tiny run with the stretch of the ring it added: its events
    from the ring, as a process that started where the run did."""
    out = {}
    for traffic in ("train", "drain", "serve"):
        t0 = time.perf_counter()
        res = bench_tiny.run_cell(traffic, seed=2 ** 31 + 11)
        assert res["correct"], traffic
        events = [e for e in profiling.events() if e.t_start >= t0]
        out[traffic] = (res, events, t0)
    return out


@pytest.mark.parametrize("traffic", ["train", "drain", "serve"])
def test_setup_stat_on_tiny_runs(tiny_runs, traffic):
    res, events, t0 = tiny_runs[traffic]
    counters = res["info"]["counters"]
    assert counters["window_s"] == res["info"]["window_s"]
    end = setup_stat.window_start(events, counters)
    # the window's start the rule finds is the driver's or just after it,
    # within a dispatch (T_START is the process's: here the run began at t0)
    stretch = end - t0
    late = stretch - (res["info"]["setup_s"] - (t0 - run.T_START))
    assert -1e-6 <= late <= 1.0, late
    got = {stat: setup_stat.measure(events, t0, counters, stat)
           for stat in STATS}
    assert all(v is not None and math.isfinite(v) for v in got.values()), got
    assert 0 < got["build_s"] and 0 <= got["unspanned_s"]
    assert got["build_s"] + got["unspanned_s"] <= stretch + 1e-9
    assert 0.0 <= got["cache_hit_share"] <= 100.0
    # the whole process's ring through read(): the same rule, a stretch
    # from the worker's own start
    for stat in STATS:
        v = setup_stat.read({"counters": counters}, stat=stat)
        assert v is None or math.isfinite(v)


@pytest.mark.parametrize("traffic", ["train", "drain", "serve"])
def test_no_build_inside_a_tiny_window(tiny_runs, traffic):
    """The "window compiles nothing" pin, extended to retraces and
    lowerings: no build stage at all starts inside the window."""
    res, events, _t0 = tiny_runs[traffic]
    counters = res["info"]["counters"]
    lo = setup_stat.window_start(events, counters)
    # the window ends with its root where it has one: serve's window_s
    # runs from before its serve.run opened, and the check's reference
    # programs are traced as soon as the root has closed
    roots = [e for e in events if e.name in ("serve.run", "engine.run")]
    hi = (max(e.t_end for e in roots) if roots
          else lo + counters["window_s"])
    late = [e for e in events if e.name in profiling.BUILD_EVENTS
            and lo <= e.t_start <= hi]
    assert late == [], [(e.name, e.ids) for e in late]


def test_set_up_spans_in_the_tiny_rings(tiny_runs):
    names = {e.name for _r, events, _t in tiny_runs.values()
             for e in events}
    assert {"engine.init", "engine.prewarm", "corpus.build",
            "jax.trace", "jax.lower", "xla.compile"} <= names


# --------------------------------------------------------------------------
# the manifest's new entries
# --------------------------------------------------------------------------

def test_new_entries_are_appended_and_name_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = [w["name"] for w in bm["workloads"]]
    assert [m["name"] for m in bm["per_layer"][-3:]] == list(NEW)
    by = {m["name"]: m for m in bm["per_layer"]}
    for name, (unit, better, source, stat) in NEW.items():
        m = by[name]
        assert (m["unit"], m["better"], m["source"]) == (unit, better, source)
        assert m["layer"] == by["prewarm_s"]["layer"]
        assert m["moves"] == "setup_s" and m["workloads"] == cells
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            assert json.load(f) == {"reader": "setup_stat",
                                    "args": {"stat": stat}}
