"""The per-layer metrics read off the program's own spans: the reader on a
hand-made event list, the reader on the ring that tiny runs of the
benchmark's own drivers leave behind, the new metric files against the
manifest, and a pin on the jitted programs' module names that the
trace-reading metrics match. CPU, no accelerator."""

import json
import math
import os
import re

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import common
from benchmark.readers import module_time, span_stat

ROOT = bench_tiny.ROOT
NEW = {"serve_round_ms": "fira-full.serve",
       "serve_round_host_ms": "fira-full.serve",
       "admit_ms.serve": "fira-full.serve",
       "harvest_read_ms.serve": "fira-full.serve",
       "harvest_read_ms.drain": "fira-large.drain",
       "prewarm_s": "fira-large.drain"}


def _args(metric):
    spec = common.load_json(os.path.join(ROOT, "benchmark"), "layer_metrics",
                            metric)
    assert set(spec) == {"reader", "args"} and spec["reader"] == "span_stat"
    return spec["args"]


# --------------------------------------------------------------------------
# the reader on hand-made spans
# --------------------------------------------------------------------------

def _serve_spans():
    """A warm-up burst (an earlier serve.run), then the window: an idle
    pass that only waited, and two rounds that dispatched."""
    s = [("engine.prewarm", 0.0, 7.5),
         ("serve.run", 10.0, 12.0),
         ("serve.round", 10.0, 12.0), ("serve.step_dispatch", 10.1, 10.2),
         ("serve.admit", 10.0, 10.1), ("engine.harvest.read", 11.0, 11.9),
         ("engine.harvest.wait", 10.2, 11.0),
         ("serve.run", 20.0, 22.5)]
    s += [("serve.round", 20.0, 20.1), ("serve.admit", 20.0, 20.01),
          ("serve.idle_wait", 20.01, 20.1)]
    for t0, admit, wait, read in ((20.1, 0.05, 0.7, 0.1),
                                  (21.1, 0.03, 0.9, 0.2)):
        end = t0 + (1.0 if t0 < 21 else 1.4)
        s += [("serve.round", t0, end),
              ("serve.admit", t0, t0 + admit),
              ("serve.step_dispatch", t0 + admit, t0 + admit + 0.01),
              ("engine.harvest.wait", t0 + 0.1, t0 + 0.1 + wait),
              ("engine.harvest.read", t0 + 0.1 + wait,
               t0 + 0.1 + wait + read)]
    return s


def test_span_stat_serve_root_on_hand_made_spans():
    spans = _serve_spans()

    def m(name):
        return span_stat.measure(spans, window_s=2.5, **_args(name))

    # the last serve.run only, and of its three passes the two that
    # dispatched: (1.0 + 1.4) / 2 s
    assert m("serve_round_ms") == pytest.approx(1200.0)
    # ... less each round's wait for its step: (0.3 + 0.5) / 2 s
    assert m("serve_round_host_ms") == pytest.approx(400.0)
    assert m("admit_ms.serve") == pytest.approx(40.0)
    assert m("harvest_read_ms.serve") == pytest.approx(150.0)
    assert m("prewarm_s") == pytest.approx(7.5)
    # rounds x mean = the root, less the idle pass
    assert 2 * m("serve_round_ms") / 1e3 == pytest.approx(2.5 - 0.1)


def test_span_stat_drain_tail_window_and_no_root():
    # a generator's life: 30 s of warm-up harvests reading 1 s each, then
    # a 10 s window whose harvests read 0.02 s each
    spans = [("engine.run", 100.0, 140.0)]
    spans += [("engine.harvest.read", 100.0 + 2 * i, 101.0 + 2 * i)
              for i in range(15)]
    spans += [("engine.harvest.read", 130.5 + i, 130.52 + i)
              for i in range(9)]
    args = _args("harvest_read_ms.drain")
    assert span_stat.measure(spans, window_s=10.0, **args) == \
        pytest.approx(20.0)
    # the whole root when the window covers it
    assert span_stat.measure(spans, window_s=100.0, **args) == \
        pytest.approx(1e3 * (15 * 1.0 + 9 * 0.02) / 24)
    # nothing to read: no window, no root, no such span
    assert span_stat.measure(spans, window_s=None, **args) is None
    assert span_stat.measure([s for s in spans if s[0] != "engine.run"],
                             window_s=10.0, **args) is None
    assert span_stat.measure(spans[:1], window_s=10.0, **args) is None
    assert span_stat.measure([], window_s=1.0,
                             **_args("serve_round_ms")) is None
    assert span_stat.measure(_serve_spans()[1:], window_s=1.0,
                             **_args("prewarm_s")) is None


def test_reader_returns_nothing_for_a_program_without_the_recorder(
        monkeypatch):
    """The driver lays this reader over the parent commit too, whose
    ``utils/profiling.py`` has no ring: the metric is left out, no raise."""
    from fira_tpu.utils import profiling

    monkeypatch.delattr(profiling, "events")
    assert span_stat.program_spans() is None
    for name in NEW:
        assert span_stat.read({"window_s": 1.0}, **_args(name)) is None


# --------------------------------------------------------------------------
# the reader on the ring of the benchmark's own drivers
# --------------------------------------------------------------------------

def test_span_stat_on_tiny_serve_and_drain_runs():
    """run.py computes no layer metric off the chip, so the reader is
    called here, on the ring the two tiny runs leave in this process."""
    drain = bench_tiny.run_cell("drain", seed=5)
    serve = bench_tiny.run_cell("serve", seed=5)
    assert drain["correct"] and serve["correct"]
    spans = span_stat.program_spans()
    names = {s[0] for s in spans}
    assert {"serve.run", "engine.run", "engine.prewarm", "corpus.build",
            "engine.harvest.wait", "engine.harvest.read"} <= names
    got = {}
    for name, cell in NEW.items():
        window = (drain if cell.endswith("drain") else serve)["info"]
        got[name] = span_stat.read({"window_s": window["window_s"]},
                                   **_args(name))
        assert got[name] is not None and math.isfinite(got[name]) \
            and got[name] > 0, name
    assert got["serve_round_host_ms"] <= got["serve_round_ms"]
    assert got["admit_ms.serve"] <= got["serve_round_ms"]
    # the serve window is the LAST serve.run (the burst is an earlier one)
    runs = sorted(s for s in spans if s[0] == "serve.run")
    assert len(runs) >= 2
    assert runs[-1][2] - runs[-1][1] == pytest.approx(
        serve["info"]["window_s"], rel=0.05, abs=0.05)
    # the drain root ends within one dispatch of the driver's window
    # (the last one: this process may have drained in other tests before)
    root = max((s for s in spans if s[0] == "engine.run"),
               key=lambda s: s[2])
    assert root[2] - root[1] >= drain["info"]["window_s"]
    # nothing compiled under the windows' roots
    from fira_tpu.utils import profiling

    lo = root[2] - drain["info"]["window_s"]
    late = [e for e in profiling.events() if e.name == "xla.compile"
            and (lo <= e.t_start <= root[2]
                 or runs[-1][1] <= e.t_start <= runs[-1][2])]
    assert late == [], [e.ids for e in late]


# --------------------------------------------------------------------------
# the manifest's new entries
# --------------------------------------------------------------------------

def test_new_entries_are_appended_and_name_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    names = [m["name"] for m in bm["per_layer"]]
    # after every metric the benchmark already had, in the issue's order
    assert [n for n in names if n in NEW] == list(NEW)
    assert names.index("serve_round_ms") > names.index(
        "device_idle_share.serve")
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    cells = [w["name"] for w in bm["workloads"]]
    for m in (m for m in bm["per_layer"] if m["name"] in NEW):
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert NEW[m["name"]] in m["workloads"]
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
        _args(m["name"])
    by = {m["name"]: m for m in bm["per_layer"]}
    assert by["prewarm_s"]["moves"] == "setup_s"
    assert by["prewarm_s"]["workloads"] == ["fira-large.drain",
                                            "fira-full.serve"]
    layers = {m["layer"] for m in bm["per_layer"] if m["name"] not in NEW}
    assert by["serve_round_ms"]["layer"] in layers
    assert by["harvest_read_ms.drain"]["layer"] in layers


# --------------------------------------------------------------------------
# the jitted programs' module names
# --------------------------------------------------------------------------

def _module_name(jitted, *args):
    return re.search(r"module @(\S+)", jitted.lower(*args).as_text()).group(1)


def test_module_names_that_the_trace_metrics_match():
    """``module_time`` finds a program in the device trace by its XLA
    module name, ``jit_<function>``: a rename must fail here, not turn a
    metric into ``null`` on the chip."""
    import jax

    from fira_tpu.config import get_config
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.synthetic import make_memory_split
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    cfg = get_config("fira-tiny", fused_steps=2, engine_slots=4)
    cfg, split, _vocab = make_memory_split(cfg, 16, seed=0)
    model = FiraModel(cfg)
    batch = make_batch(split, np.arange(cfg.batch_size), cfg,
                       batch_size=cfg.batch_size)
    state = init_state(model, cfg, batch)
    stacked = step_lib.stack_batches([batch, batch])
    names = {"train": _module_name(
        step_lib.jit_multi_step(model, cfg, None, state, stacked),
        state, stacked)}
    eng = SlotEngine(model, state.params, cfg, slots=4)
    warm = make_batch(split, np.arange(0), cfg,
                      batch_size=cfg.test_batch_size)
    eng.prewarm([(warm, None)])
    wire = {k: v for k, v in warm.items() if not k.startswith("_")}
    chunk = jax.eval_shape(eng._prefill, eng.params, wire)
    C = chunk["diff"].shape[0]
    names["prefill"] = _module_name(eng._prefill, eng.params, wire)
    names["step"] = _module_name(eng._step, eng._decode_params, eng._state)
    names["insert"] = _module_name(
        eng._insert, eng._state, chunk, np.zeros((C,), np.int32),
        np.zeros((C,), np.int32),
        np.zeros((C, eng._table_width), np.int32) if eng._paged else None)
    assert names == {"train": "jit_multi_step", "step": "jit__step_fn",
                     "prefill": "jit__prefill_fn",
                     "insert": "jit__insert_fn"}
    # and the accepted metrics' readers match them, as the trace spells
    # them: jit_<function>(<fingerprint>), reduced to the part before "("
    trace = {"modules": {n: {"count": 2, "seconds": 0.5}
                         for n in names.values()}}
    ctx = {"trace": trace, "counters": {"steps_per_dispatch": 2}}
    for metric, want in (("train_step_device_ms", 125.0),
                         ("engine_step_device_ms.drain", 250.0),
                         ("engine_step_device_ms.serve", 250.0)):
        spec = common.load_json(os.path.join(ROOT, "benchmark"),
                                "layer_metrics", metric)
        assert spec["reader"] == "module_time"
        assert module_time.read(ctx, **spec["args"]) == pytest.approx(want)
