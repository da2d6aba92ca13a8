"""The program's one recorder (fira_tpu/utils/profiling.py) and the spans the
layers take of themselves: the ring and its parents, the compile listener,
the serve loop's and the engine's names in the ring AND on the profiler's
host plane, the drain generator's explicit root. CPU, fira-tiny."""

import glob
import json
import os
import statistics
import threading
import time

import jax
import numpy as np
import pytest

from fira_tpu.config import fira_tiny
from fira_tpu.data.batching import make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder, assembly_tasks
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.engine import EngineStats, SlotEngine
from fira_tpu.model.model import FiraModel
from fira_tpu.serve import arrivals, serve_split
from fira_tpu.train.state import init_state
from fira_tpu.utils import profiling

SERVE_NAMES = {"serve.run", "serve.round", "serve.poll", "serve.admit",
               "serve.form_batch", "serve.step_dispatch", "serve.harvest",
               "serve.emit", "serve.idle_wait", "serve.snapshot"}
ENGINE_NAMES = {"engine.admit", "engine.refill", "engine.step_dispatch",
                "engine.harvest", "engine.harvest.wait",
                "engine.harvest.read"}
PREWARM_NAMES = {"engine.prewarm", "engine.prewarm.prefill",
                 "engine.prewarm.insert", "engine.prewarm.step"}


def inside(ev, outer):
    return ev.t_start >= outer.t_start and ev.t_end <= outer.t_end


def _since(t0):
    """The ring's events begun since ``t0``: by time, not by index, since
    a full ring holds its length while it drops its oldest."""
    return [e for e in profiling.events() if e.t_start >= t0]


# --------------------------------------------------------------------------
# the recorder on its own
# --------------------------------------------------------------------------

def test_nested_spans_parents_self_time_and_phases():
    rec = profiling.Recorder()
    phases = rec.collect()
    with rec.span("outer", round=7) as outer:
        with rec.span("child"):
            time.sleep(0.01)
        with rec.span("child"):
            with rec.span("grandchild"):
                time.sleep(0.005)
    by_name = {}
    for ev in rec.events():
        by_name.setdefault(ev.name, []).append(ev)
    (o,), kids, (g,) = by_name["outer"], by_name["child"], by_name["grandchild"]
    assert o.parent_id == 0 and o.ids == {"round": 7}
    assert all(k.parent_id == o.span_id for k in kids)
    assert g.parent_id == kids[1].span_id and g.ids is None
    # events land in the ring as they CLOSE: children before their parent
    assert [e.name for e in rec.events()] == [
        "child", "grandchild", "child", "outer"]
    self_time = o.duration_s - sum(k.duration_s for k in kids)
    assert 0.0 <= self_time < o.duration_s
    assert kids[0].duration_s >= 0.01 and outer.duration_s == o.duration_s
    s = phases.summary()
    assert s["child"]["count"] == 2 and s["outer"]["count"] == 1
    assert s["child"]["total_s"] == pytest.approx(
        sum(k.duration_s for k in kids), abs=2e-6)
    assert s["child"]["max_s"] == pytest.approx(
        max(k.duration_s for k in kids), abs=2e-6)
    assert list(s)[:3] == sorted(list(s)[:3])       # stable key order
    # a collector made later sees only what closes from then on
    late = rec.collect()
    with rec.span("child"):
        pass
    assert late.summary()["child"]["count"] == 1
    assert phases.summary()["child"]["count"] == 3


def test_ring_is_bounded_and_totals_are_not():
    rec = profiling.Recorder(maxlen=8)
    for i in range(50):
        with rec.span("tick", i=i):
            pass
    events = rec.events()
    assert len(events) == 8
    assert [e.ids["i"] for e in events] == list(range(42, 50))
    assert rec.total.summary()["tick"]["count"] == 50


def test_each_thread_has_its_own_parent_stack():
    rec = profiling.Recorder()
    seen = {}

    def work():
        with rec.span("worker.task") as sp:
            seen["parent"] = sp.parent_id

    with rec.span("main.loop") as main:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with rec.span("main.child") as child:
            pass
    assert seen["parent"] == 0                  # not main.loop's child
    assert child.parent_id == main.span_id
    threads = {e.name: e.thread for e in rec.events()}
    assert threads["worker.task"] != threads["main.loop"]


def test_root_is_a_parent_only_inside_its_stretches():
    """A generator's root (SlotEngine.run): what the consumer opens between
    two items is not the root's child."""
    rec = profiling.Recorder()

    def gen():
        root = rec.begin("gen.run")
        try:
            for i in range(3):
                with root:
                    with rec.span("gen.work", i=i):
                        pass
                yield i
        finally:
            root.end()

    with rec.span("consumer"):
        for _ in gen():
            with rec.span("consumer.between"):
                pass
    ev = {e.name: e for e in rec.events()}
    root, consumer = ev["gen.run"], ev["consumer"]
    assert root.parent_id == consumer.span_id
    assert ev["gen.work"].parent_id == root.span_id
    assert ev["consumer.between"].parent_id == consumer.span_id
    assert inside(ev["gen.work"], root) and inside(root, consumer)
    assert sum(e.name == "gen.run" for e in rec.events()) == 1


def test_span_as_a_decorator_opens_a_fresh_span_each_call():
    rec = profiling.Recorder()

    @rec.span("phase", kind="whole")
    def work(depth):
        """doc kept"""
        if depth:
            work(depth - 1)     # re-entrant: no state shared between calls
        return depth

    assert work(1) == 1 and work.__doc__ == "doc kept"
    inner, outer = rec.events()
    assert (inner.name, outer.name) == ("phase", "phase")
    assert inner.parent_id == outer.span_id and outer.parent_id == 0
    assert inside(inner, outer) and outer.ids == {"kind": "whole"}
    assert rec.total.spans["phase"][0] == 2


def test_the_serve_round_stays_where_firacheck_scans_it():
    """The scheduler round is a loop body of a driver module: that is what
    SCHED-BLOCK and HOST-SYNC scan. A refactor that moves the round into a
    method called as ``self._round()`` takes it out of the hot regions (the
    closure follows bare names only) — and a blocking call added to the
    round would then pass unseen. Pin every span of the round, and the
    waived sleep, inside a hot region."""
    import ast

    from fira_tpu.analysis import astutil
    from fira_tpu.serve import server

    src = open(server.__file__).read()
    tree = ast.parse(src)
    hot = astutil.hot_spans(tree, server.__file__, astutil.parent_map(tree))

    def hot_lines(needle):
        at = [i + 1 for i, line in enumerate(src.split("\n"))
              if needle in line]
        assert at, needle
        return [astutil.hot_region_at(hot, n) is not None for n in at]

    for name in ("serve.round", "serve.poll", "serve.admit",
                 "serve.step_dispatch", "serve.harvest", "serve.emit",
                 "serve.idle_wait"):
        assert all(hot_lines(f'profiling.span("{name}"')), name
    # these two also run outside the round (start of the run; admission)
    for name in ("serve.snapshot", "serve.journal"):
        assert any(hot_lines(f'profiling.span("{name}"')), name
    assert all(hot_lines("time.sleep(0.01)  # firacheck: allow[SCHED-BLOCK]"))


def test_stopwatch_times_and_records_nothing():
    before = len(profiling.events())
    with profiling.stopwatch("feeder.next") as sw:
        time.sleep(0.002)
    assert sw.duration_s >= 0.002
    assert len(profiling.events()) == before


def test_dump_writes_the_ring_as_json_lines(tmp_path):
    rec = profiling.Recorder()
    with rec.span("a", round=np.int64(2)):
        with rec.span("b"):
            pass
    path = rec.dump(str(tmp_path / "spans.jsonl"))
    head, *rows = [json.loads(line) for line in open(path)]
    assert head["recorder"]["events"] == 2 == head["recorder"]["recorded"]
    assert [r["name"] for r in rows] == ["b", "a"]
    assert rows[0]["parent_id"] == rows[1]["span_id"]
    assert set(rows[0]) == set(profiling.Event._fields)


def test_compile_listener_names_the_program_and_its_span():
    def _spans_probe_fn(x):
        return x * 3 + 1

    phases = profiling.collect()
    before = profiling.counters()["compiles"]
    with profiling.span("probe.compile") as sp:
        jax.jit(_spans_probe_fn)(np.arange(7.0)).block_until_ready()
    mine = [e for e in profiling.events()
            if e.name == profiling.COMPILE_EVENT
            and e.parent_id == sp.span_id]
    assert any(e.ids["program"] == "jit(_spans_probe_fn)" for e in mine)
    assert all(inside(e, sp) or e.t_start < sp.t_start for e in mine)
    s = phases.summary()
    assert s["compiles"] == len(mine) >= 1
    assert s["compile_s"] == pytest.approx(sum(e.duration_s for e in mine),
                                           abs=1e-5)
    assert profiling.counters()["compiles"] == before + len(mine)
    # a second call of the same program compiles nothing
    again = profiling.counters()["compiles"]
    jax.jit(_spans_probe_fn)(np.arange(7.0)).block_until_ready()
    assert profiling.counters()["compiles"] == again


# --------------------------------------------------------------------------
# the layers' spans: engine prewarm, serve rounds, the drain generator
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("spans_corpus"))
    write_corpus_dir(data_dir, n_commits=24, seed=5)
    cfg = fira_tiny(batch_size=8, test_batch_size=4, decode_engine=True,
                    engine_slots=4)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    batch = make_batch(split, np.arange(4), cfg, batch_size=4)
    model = FiraModel(cfg)
    params = eos_biased_params(init_state(model, cfg, batch).params,
                               delta=4.0)
    return cfg, dataset, model, params


@pytest.fixture(scope="module")
def served(setup, tmp_path_factory):
    """The benchmark's order on one warmed engine: prewarm, a burst through
    every program, then the window — the window under a profiler session."""
    cfg, dataset, model, params = setup
    split = dataset.splits["train"]
    # jax keeps one in-process cache a jitted FUNCTION: an engine of the
    # same configuration built earlier on this worker (which files share a
    # worker differs from run to run) could leave prewarm nothing to
    # compile, and the tests below assert those compiles. Start as a
    # fresh process does.
    jax.clear_caches()
    eng = SlotEngine(model, params, cfg, slots=cfg.engine_slots)
    warm = make_batch(split, np.arange(0), cfg, batch_size=cfg.test_batch_size)
    mark = time.perf_counter()
    eng.prewarm([(warm, None)])
    out = str(tmp_path_factory.mktemp("spans_serve"))
    n = len(split)

    def serve(times, sub):
        return serve_split(model, params, dataset, cfg, arrival_times=times,
                           out_dir=os.path.join(out, sub), split="train",
                           engine=eng, clock="virtual")

    burst = serve(np.zeros(n), "burst")
    eng.stats = EngineStats(slots=eng.slots)
    trace_dir = os.path.join(out, "trace")
    with profiling.trace(trace_dir):
        window = serve(arrivals.poisson_times(n, rate=0.5, seed=3), "window")
    events = _since(mark)
    return {"events": events, "burst": burst, "window": window, "eng": eng,
            "trace_dir": trace_dir}


def _roots(events, name):
    return [e for e in events if e.name == name]


def test_prewarm_spans_hold_the_compiles(served):
    events = served["events"]
    (prewarm,) = _roots(events, "engine.prewarm")
    by_id = {e.span_id: e for e in events}
    names = {e.name for e in events}
    assert PREWARM_NAMES <= names
    for e in events:
        if e.name.startswith("engine.prewarm."):
            assert e.parent_id == prewarm.span_id and inside(e, prewarm)
    compiles = [e for e in events if e.name == profiling.COMPILE_EVENT
                and inside(e, prewarm)]
    assert compiles, "prewarm compiled nothing: the listener is inert"
    # each compile names its program and lies under the child that paid it
    under = {by_id[e.parent_id].name: e.ids["program"] for e in compiles
             if e.parent_id in by_id}
    assert under.get("engine.prewarm.step") == "jit(_step_fn)"
    assert under.get("engine.prewarm.prefill") == "jit(_prefill_fn)"
    assert under.get("engine.prewarm.insert") == "jit(_insert_fn)"


def test_window_after_the_burst_compiles_nothing(served):
    events = served["events"]
    burst_run, window_run = _roots(events, "serve.run")
    assert burst_run.t_end <= window_run.t_start
    in_window = [e for e in events if e.name == profiling.COMPILE_EVENT
                 and inside(e, window_run)]
    assert in_window == [], [e.ids for e in in_window]
    assert served["window"]["serve"]["phases"]["compiles"] == 0
    assert served["window"]["engine"]["phases"]["compiles"] == 0


def test_one_read_a_harvest_and_no_harvest_program(served):
    """A harvest that settled rows holds ONE ``engine.harvest.read`` (the
    host's share after the one transfer), the reads are the engine's
    ``harvest_reads`` and their ``rows`` its ``harvest_row_reads``; no
    program is built under any harvest, burst or window, nor under
    prewarm for one: the harvest reads what the step wrote out."""
    events = served["events"]
    window_run = _roots(events, "serve.run")[-1]
    mine = [e for e in events if inside(e, window_run)]
    reads = [e for e in mine if e.name == "engine.harvest.read"]
    stats = served["eng"].stats
    assert len(reads) == stats.harvest_reads > 0
    assert len({e.parent_id for e in reads}) == len(reads)
    assert sum(e.ids["rows"] for e in reads) == stats.harvest_row_reads \
        == stats.commits
    assert max(e.ids["rows"] for e in reads) > 1     # rows shared a read
    harvests = [e for e in events if e.name == "engine.harvest"]
    assert harvests
    builds = [e for e in events if e.name in (
        profiling.TRACE_EVENT, profiling.LOWER_EVENT,
        profiling.COMPILE_EVENT)]
    assert builds
    assert not [e for e in builds if any(inside(e, h) for h in harvests)]
    assert {e.ids["program"] for e in builds
            if e.name == profiling.COMPILE_EVENT} >= {
        "jit(_step_fn)", "jit(_prefill_fn)", "jit(_insert_fn)"}


def test_serve_round_structure_and_harvest_split(served):
    events = served["events"]
    window_run = _roots(events, "serve.run")[-1]
    mine = [e for e in events if inside(e, window_run)]
    names = {e.name for e in mine}
    assert (SERVE_NAMES - {"serve.snapshot"}) | ENGINE_NAMES <= names
    by_id = {e.span_id: e for e in mine}

    def parent_name(e):
        return by_id[e.parent_id].name if e.parent_id in by_id else None

    want = {"serve.round": "serve.run", "serve.poll": "serve.round",
            "serve.admit": "serve.round", "serve.form_batch": "serve.admit",
            "serve.step_dispatch": "serve.round",
            "serve.harvest": "serve.round", "serve.emit": "serve.round",
            "serve.idle_wait": "serve.round",
            "engine.admit": "serve.admit", "engine.refill": "serve.admit",
            "engine.step_dispatch": "serve.step_dispatch",
            "engine.harvest": "serve.harvest",
            "engine.harvest.wait": "engine.harvest",
            "engine.harvest.read": "engine.harvest"}
    for e in mine:
        if e.name in want:
            assert parent_name(e) == want[e.name], (e.name, parent_name(e))
            assert inside(e, by_id[e.parent_id])
    # every harvest splits into the wait for the step and the row reads
    harvests = [e for e in mine if e.name == "engine.harvest"]
    assert harvests
    for h in harvests:
        parts = sorted((e for e in mine if e.parent_id == h.span_id),
                       key=lambda p: p.t_start)
        # the read is there only where rows settled: a harvest that
        # settles nothing must not thin the mean of `harvest_read_ms.*`
        assert [p.name for p in parts] in (
            ["engine.harvest.wait"],
            ["engine.harvest.wait", "engine.harvest.read"])
        assert sum(p.duration_s for p in parts) <= h.duration_s
        assert all(a.t_end <= b.t_start for a, b in zip(parts, parts[1:]))
    reads = [e for e in mine if e.name == "engine.harvest.read"]
    assert reads and all(e.ids["rows"] >= 1 for e in reads)
    assert sum(e.ids["rows"] for e in reads) == \
        served["eng"].stats.harvest_row_reads
    # a round that dispatched holds one step dispatch; the loop's own
    # `rounds` counter counts exactly those
    rounds = [e for e in mine if e.name == "serve.round"]
    dispatched = [r for r in rounds if any(
        e.name == "serve.step_dispatch" and e.parent_id == r.span_id
        for e in mine)]
    assert len(dispatched) == served["window"]["serve"]["rounds"]
    assert [r.ids["round"] for r in dispatched] == list(range(len(dispatched)))
    # no span per request: the per-request feeder records none
    assert not any(e.name.startswith("feeder.") for e in mine)
    assert len(mine) <= 16 * len(rounds) + 4


def test_summary_phases_hold_the_rings_totals(served):
    events = served["events"]
    window_run = _roots(events, "serve.run")[-1]
    mine = [e for e in events if inside(e, window_run)]
    phases = served["window"]["serve"]["phases"]
    eng_phases = served["window"]["engine"]["phases"]
    for name in ("serve.round", "serve.admit", "serve.emit",
                 "engine.harvest.wait", "engine.harvest.read"):
        ring = [e.duration_s for e in mine if e.name == name]
        assert phases[name]["count"] == len(ring)
        assert phases[name]["total_s"] == pytest.approx(sum(ring), abs=1e-4)
        assert phases[name]["max_s"] == pytest.approx(max(ring), abs=2e-6)
    # the engine's stats were reset after the burst: its phases start there
    assert eng_phases["engine.harvest"]["count"] == \
        phases["engine.harvest"]["count"]
    assert "engine.prewarm" not in eng_phases
    # wall_s IS the root's duration
    assert served["window"]["serve"]["phases"]["serve.run"]["total_s"] == \
        pytest.approx(window_run.duration_s, abs=2e-6)


def test_records_link_requests_to_rounds(served):
    recs = served["window"]["request_records"]
    done = [r for r in recs if r["status"] == "done"]
    assert done and len(done) == len(recs)
    for r in done:
        assert 0 <= r["arrival_round"] <= r["seat_round"] < r["done_round"]


def test_spans_are_on_the_profilers_host_plane(served):
    """Every serve.* / engine.* name of the window is an event on /host:CPU
    of the .xplane.pb — the device trace's clock — with the ring's
    duration. The annotation is entered before the ring's clock is read
    and left after it (profiling._Span), so it is never the shorter of the
    two; the two gaps are microseconds unless the host takes the thread
    away inside one (seen: 3.6 ms on one span of a loaded run). So every
    pair is bounded — the event encloses its span and is longer by at
    most a scheduler quantum — and every name's median pair agrees within
    0.2 ms: a span annotated wrongly fails on its own name however few
    events it has, one preemption does not."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(served["trace_dir"], "**",
                                     "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("serve.", "engine.")):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns))
    events = served["events"]
    window_run = _roots(events, "serve.run")[-1]
    mine = [e for e in events if inside(e, window_run)]
    for name in sorted({e.name for e in mine}):
        ring = sorted((e.t_start, e.duration_s) for e in mine
                      if e.name == name)
        traced = sorted(host.get(name, []))
        assert len(traced) == len(ring), name
        longer = [got_ns / 1e9 - want
                  for (_t, want), (_s, got_ns) in zip(ring, traced)]
        assert min(longer) >= -2e-4, name               # it encloses
        assert max(longer) <= 2e-2, name                # one quantum
        assert abs(statistics.median(longer)) <= 2e-4, name


def test_drain_generator_root_and_feeder_spans(setup):
    cfg, dataset, model, params = setup
    split = dataset.splits["train"]
    eng = SlotEngine(model, params, cfg, slots=cfg.engine_slots)
    chunks = [np.arange(i, i + 4) for i in range(0, 12, 4)]
    mark = time.perf_counter()
    with Feeder(assembly_tasks(split, chunks, cfg, batch_size=4),
                num_workers=1, depth=2) as feed:
        stall0 = feed.stats()["feed_stall_s"]
        got = 0
        with profiling.span("test.consumer") as consumer:
            for _item in eng.run(feed):
                got += 1
                with profiling.span("test.between"):
                    pass
        stalled = feed.stats()["feed_stall_s"] - stall0
    assert got == 12
    events = _since(mark)
    (root,) = _roots(events, "engine.run")
    assert root.parent_id == consumer.span_id
    for e in events:
        if e.name in ("engine.admit", "engine.refill",
                      "engine.step_dispatch", "engine.harvest",
                      "feeder.next"):
            assert e.parent_id == root.span_id, e.name
        if e.name == "test.between":
            assert e.parent_id == consumer.span_id
    # the worker's spans are on the worker's thread, with no parent there
    assemble = _roots(events, "feeder.assemble")
    assert len(assemble) == len(_roots(events, "feeder.put")) == 3
    assert all(e.parent_id == 0 and e.thread != root.thread
               for e in assemble)
    # one source: the feeder's stall IS the consumer span's duration (the
    # fourth call found the stream exhausted and emitted nothing)
    nexts = sorted(_roots(events, "feeder.next"), key=lambda e: e.t_start)
    assert len(nexts) == 4
    assert stalled == pytest.approx(sum(e.duration_s for e in nexts[:3]),
                                    abs=1e-9)
    # at most six engine spans a dispatch
    dispatches = len(_roots(events, "engine.step_dispatch"))
    engine_spans = [e for e in events if e.name.startswith("engine.")
                    and e.name != "engine.run"]
    assert len(engine_spans) <= 6 * dispatches + 6
