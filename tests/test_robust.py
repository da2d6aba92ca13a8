"""Fault-injection harness + graceful degradation (fira_tpu/robust —
docs/FAULTS.md).

Pins the whole chaos contract:

- spec grammar + parse-time validation with named-knob messages and CLI
  exit 2 (inject_faults / dispatch_watchdog_s / robust_retries);
- injector determinism: whether an event fires is a pure function of
  (seed, site, event key) — replayable across processes and threads;
- the dispatch watchdog: inline at timeout 0, value/exception pass-
  through, timeout raises and abandons;
- the feeder's per-task error channel: record mode delivers a poisoned
  item WITH its error (stream continues), retries absorb transient
  faults, and the wrapped FeederTaskError names the poisoned sample;
- poison-request quarantine in serve: assembly/prefill/admission faults
  are retried then shed with a recorded error and an empty output line,
  UNAFFECTED requests' bytes identical to the no-fault run;
- replica retirement + requeue: a replica whose dispatch raises (or
  hangs past the watchdog) retires, its in-flight requests complete on
  survivors with output bytes IDENTICAL to the no-fault run, retirements
  and requeues machine-recorded (ServeStats and FleetStats);
- zero post-warmup retraces with faults armed (host-side faults compile
  nothing new);
- serve_metrics.json written atomically, with a valid partial snapshot
  surviving SIGKILL mid-serve (the kill-contract satellite);
- the train dev-gate watchdog: a wedged gate is skipped with a recorded
  warning, training continues.
"""

import dataclasses
import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from fira_tpu import cli
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder, FeederTaskError
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.runner import run_test
from fira_tpu.model.model import FiraModel
from fira_tpu.robust import faults as faults_lib
from fira_tpu.robust.watchdog import WatchdogTimeout, run_with_watchdog
from fira_tpu.serve import poisson_times, serve_split
from fira_tpu.serve.server import write_metrics_atomic
from fira_tpu.train.state import init_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("chaos_corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=6, decode_engine=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, dataset, eos_biased_params(params, delta=4.0)


@pytest.fixture(scope="module")
def trace(setup):
    cfg, dataset, _ = setup
    n = len(dataset.splits["train"])
    return poisson_times(n, rate=0.4, seed=3)


@pytest.fixture(scope="module")
def drain_lines(setup, tmp_path_factory):
    """No-fault drain bytes: the reference every degraded run's
    unaffected positions must reproduce exactly."""
    cfg, dataset, params = setup
    out = str(tmp_path_factory.mktemp("drain"))
    m = run_test(FiraModel(cfg), params, dataset, cfg, out_dir=out,
                 split="train")
    return open(m["output_path"]).read().split("\n")


def _assert_degraded_bytes(m, ref_lines):
    """Shed positions hold empty lines; every completed position holds
    the no-fault line (requeued requests included — per-row beam
    independence makes a re-served request bit-exact)."""
    got = open(m["output_path"]).read().split("\n")
    assert len(got) == len(ref_lines)
    shed = {r["position"] for r in m["request_records"]
            if r["status"] != "done"}
    for pos in shed:
        assert got[pos] == ""
    for pos, (a, b) in enumerate(zip(ref_lines, got)):
        if pos not in shed:
            assert a == b, f"completed position {pos} differs"


# --------------------------------------------------------------------------
# spec grammar + parse-time validation
# --------------------------------------------------------------------------

def test_fault_spec_parses_and_rejects():
    specs = faults_lib.parse_fault_specs(
        "feeder.assemble:raise:0.1:7, engine.step:hang:1:0")
    assert [s.site for s in specs] == ["feeder.assemble", "engine.step"]
    assert specs[0].rate == 0.1 and specs[1].kind == "hang"
    for bad, msg in [
        ("feeder.assemble:raise:0.1", "site:kind:rate:seed"),
        ("nowhere:raise:0.1:7", "not a registered fault site"),
        ("engine.step:explode:0.1:7", "not one of"),
        ("engine.step:corrupt:0.1:7", "corrupt"),
        ("engine.step:raise:1.5:7", "must be in"),
        ("engine.step:raise:x:7", "not a float"),
        ("engine.step:raise:0.1:x", "not an integer"),
        ("engine.step:raise:0.1:7,engine.step:raise:0.2:8", "twice"),
    ]:
        with pytest.raises(ValueError, match=msg):
            faults_lib.parse_fault_specs(bad)


def test_robust_errors_named_messages():
    cfg = fira_tiny()
    assert faults_lib.robust_errors(cfg) == []
    errs = faults_lib.robust_errors(cfg.replace(inject_faults="bogus"))
    assert errs and "inject_faults" in errs[0]
    errs = faults_lib.robust_errors(cfg.replace(dispatch_watchdog_s=-1.0))
    assert errs and "dispatch_watchdog_s" in errs[0]
    errs = faults_lib.robust_errors(cfg.replace(robust_retries=-1))
    assert errs and "robust_retries" in errs[0]
    errs = faults_lib.robust_errors(cfg.replace(fault_hang_s=0.0))
    assert errs and "fault_hang_s" in errs[0]


def test_cli_robust_knob_validation_exit2(tmp_path, capsys):
    data = str(tmp_path / "DataSet")
    write_corpus_dir(data, n_commits=16, seed=5)
    base = ["test", "--config", "fira-tiny", "--data-dir", data,
            "--out-dir", str(tmp_path / "OUT")]
    assert cli.main(base + ["--inject-faults", "nowhere:raise:0.1:7"]) == 2
    assert "not a registered fault site" in capsys.readouterr().err
    assert cli.main(base + ["--dispatch-watchdog-s", "-2"]) == 2
    assert "dispatch_watchdog_s" in capsys.readouterr().err
    assert cli.main(base + ["--robust-retries", "-1"]) == 2
    assert "robust_retries" in capsys.readouterr().err


# --------------------------------------------------------------------------
# injector determinism + watchdog unit contract
# --------------------------------------------------------------------------

def test_injector_fires_deterministically():
    spec = "engine.step:raise:0.3:42"
    pattern = []
    for _run in range(2):
        inj = faults_lib.FaultInjector(faults_lib.parse_fault_specs(spec))
        fires = []
        for k in range(50):
            try:
                inj.check("engine.step")
                fires.append(False)
            except faults_lib.InjectedFault:
                fires.append(True)
        pattern.append(fires)
        assert inj.fired["engine.step"] == sum(fires) > 0
        assert inj.summary() == {"engine.step": sum(fires)}
    assert pattern[0] == pattern[1]
    # a different seed is a different pattern; an unarmed site never fires
    inj2 = faults_lib.FaultInjector(
        faults_lib.parse_fault_specs("engine.step:raise:0.3:43"))
    fires2 = []
    for k in range(50):
        try:
            inj2.check("engine.step")
            fires2.append(False)
        except faults_lib.InjectedFault:
            fires2.append(True)
    assert fires2 != pattern[0]
    inj2.check("engine.harvest")  # unarmed: no-op


def test_injector_corrupt_scrambles_in_place_deterministically():
    inj = faults_lib.FaultInjector(
        faults_lib.parse_fault_specs("feeder.assemble:corrupt:1:7"))
    batch = {"diff": np.arange(6).reshape(1, 6), "valid": np.ones(1, bool)}
    out = inj.corrupt("feeder.assemble", 0, dict(batch))
    assert out["diff"].shape == batch["diff"].shape
    assert not np.array_equal(out["diff"], batch["diff"])
    np.testing.assert_array_equal(out["diff"], np.roll(batch["diff"], 1,
                                                       axis=-1))
    # raise/hang checks ignore a corrupt spec entirely
    inj.check("feeder.assemble", key=0)


def test_watchdog_inline_value_exception_and_timeout():
    assert run_with_watchdog(lambda: 7, 0.0) == 7       # inline, off
    assert run_with_watchdog(lambda: 7, 5.0) == 7       # threaded, fast
    with pytest.raises(KeyError, match="boom"):
        run_with_watchdog(lambda: (_ for _ in ()).throw(KeyError("boom")),
                          5.0)
    t0 = time.perf_counter()
    with pytest.raises(WatchdogTimeout, match="watchdog"):
        run_with_watchdog(lambda: time.sleep(3.0), 0.1, label="slow")
    assert time.perf_counter() - t0 < 1.0  # abandoned, not awaited


# --------------------------------------------------------------------------
# feeder: per-task error channel + wrapped context
# --------------------------------------------------------------------------

def test_feeder_record_mode_keeps_stream_alive():
    def make(i):
        def task():
            if i == 1:
                raise RuntimeError(f"poisoned sample {i}")
            return {"valid": np.ones(1, bool), "x": np.full(1, i)}
        task.note = f"split positions [{i}]"
        return task

    with Feeder([make(i) for i in range(4)], num_workers=2, put=False,
                on_error="record") as feed:
        items = list(feed)
    assert len(items) == 4
    assert items[1].error is not None and items[1].host is None
    assert isinstance(items[1].error, FeederTaskError)
    assert "split positions [1]" in str(items[1].error)
    assert "poisoned sample 1" in str(items[1].error)
    assert [int(i.host["x"][0]) for i in items if i.error is None] == [0, 2, 3]


def test_feeder_retries_absorb_transient_faults():
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise RuntimeError("transient")
        return {"valid": np.ones(1, bool)}

    with Feeder([flaky], num_workers=0, put=False, retries=1,
                retry_backoff_s=0.0) as feed:
        item = next(feed)
    assert item.error is None and item.retries == 1
    assert feed.stats()["task_retries"] == 1.0
    assert feed.stats()["task_errors"] == 0.0


def test_feeder_raise_mode_names_the_poisoned_chunk():
    def boom():
        raise ValueError("bad sample bytes")
    boom.note = "split positions [3, 4]; bucket a16.e400.t12"

    with pytest.raises(FeederTaskError, match=r"bucket a16\.e400\.t12"):
        with Feeder([boom], num_workers=1, put=False) as feed:
            next(feed)


# --------------------------------------------------------------------------
# poison-request quarantine (serve): retried once, then recorded-shed
# --------------------------------------------------------------------------

def test_serve_quarantines_poisoned_assembly(setup, trace, drain_lines,
                                             tmp_path):
    """feeder.assemble raises on seeded requests with zero retries: each
    fire is a shed with a recorded error and an empty output line; every
    unaffected position's bytes equal the no-fault run."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, inject_faults="feeder.assemble:raise:0.1:7",
                            robust_retries=0)
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "poison"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert m["faults"]["feeder.assemble"] > 0
    assert sv["shed_error"] == m["faults"]["feeder.assemble"]
    assert sv["completed"] + sv["shed_error"] == sv["offered"]
    shed = [r for r in m["request_records"] if r["status"] == "shed_error"]
    assert shed and all("split positions" in r["error"] for r in shed)
    assert all(math.isnan(r["seat_t"]) for r in shed)
    _assert_degraded_bytes(m, drain_lines)


def test_serve_retry_budget_absorbs_transient_faults(setup, trace,
                                                     drain_lines, tmp_path):
    """The same fault pattern WITH a retry budget: every attempt is a
    fresh draw, so at this rate the retries absorb every fire — all
    requests complete, bytes identical to no-fault, retries recorded."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, inject_faults="feeder.assemble:raise:0.1:7",
                            robust_retries=2)
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "retry"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert m["faults"]["feeder.assemble"] > 0
    assert sv["completed"] == sv["offered"]
    assert sv["request_retries"] > 0
    _assert_degraded_bytes(m, drain_lines)


def test_serve_quarantines_prefill_and_admission(setup, trace, drain_lines,
                                                 tmp_path):
    cfg, dataset, params = setup
    model = FiraModel(cfg)
    for site, rate_seed in (("engine.prefill", "0.15:9"),
                            ("serve.admit", "0.08:13")):
        c = dataclasses.replace(cfg, inject_faults=f"{site}:raise:{rate_seed}",
                                robust_retries=1)
        m = serve_split(model, params, dataset, c, arrival_times=trace,
                        out_dir=str(tmp_path / site), split="train",
                        clock="virtual")
        sv = m["serve"]
        assert m["faults"][site] > 0, site
        assert sv["completed"] + sv["shed_error"] == sv["offered"], site
        assert sv["replica_retirements"] == 0, site
        _assert_degraded_bytes(m, drain_lines)


def test_serve_corrupt_blast_radius_is_one_request(setup, trace, tmp_path):
    """A corrupted payload decodes to garbage, not a crash: the run
    completes, and only positions the corruption touched may differ from
    the no-fault run (per-row beam independence bounds the blast
    radius)."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg,
                            inject_faults="feeder.assemble:corrupt:0.08:7")
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "corrupt"), split="train",
                    clock="virtual")
    assert m["faults"]["feeder.assemble"] > 0
    assert m["serve"]["completed"] == m["serve"]["offered"]


# --------------------------------------------------------------------------
# replica retirement + requeue
# --------------------------------------------------------------------------

def test_serve_retires_replica_and_requeues(setup, trace, drain_lines,
                                            tmp_path):
    """2 replicas, a seeded step-dispatch fault: the hit replica retires,
    its in-flight requests requeue onto the survivor and COMPLETE, and
    the full output file bytes equal the no-fault run (requeued requests
    are bit-exact wherever they land). Retirements/requeues recorded."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, engine_replicas=2,
                            inject_faults="engine.step:raise:0.02:18")
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "retire"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert m["faults"]["engine.step"] >= 1
    assert sv["replica_retirements"] >= 1
    assert sv["requeued_requests"] >= 1
    assert sv["completed"] == sv["offered"]
    requeued = [r for r in m["request_records"] if r["requeues"] > 0]
    assert requeued and all(r["status"] == "done" for r in requeued)
    assert open(m["output_path"]).read() == "\n".join(drain_lines)
    # the retired replica is named in the record
    assert sv["retired_replicas"] and sv["retired_replicas"][0].startswith("r")


def test_serve_watchdog_retires_hung_replica(setup, trace, drain_lines,
                                             tmp_path):
    """An injected hang past the dispatch watchdog: the hung dispatch is
    abandoned, the replica retired, and the run still completes with
    no-fault bytes — never a wedge.

    Both real-clock limits are set against the HANG, not against the
    host's speed (the test used to hold a 0.25 s watchdog and a 60 s wall
    limit, and a loaded host — six test workers beside it — ran the 7 s
    of compiles past the 60): the hang outlasts any run of this test, so
    finishing before it ends IS the proof that the dispatch was abandoned
    rather than waited out, and the watchdog leaves a healthy dispatch
    (6 ms alone, 0.09 s at a twentieth of the speed) a second. The
    abandoned daemon thread sleeps on and bails on ``retired`` when it
    wakes."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, engine_replicas=2,
                            inject_faults="engine.step:hang:0.02:18",
                            fault_hang_s=600.0, dispatch_watchdog_s=1.0)
    t0 = time.perf_counter()
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "hang"), split="train",
                    clock="virtual")
    assert time.perf_counter() - t0 < c.fault_hang_s     # abandoned
    sv = m["serve"]
    assert m["faults"]["engine.step"] >= 1
    assert sv["replica_retirements"] >= 1
    assert sv["requeued_requests"] >= 1   # what it owed went to a survivor
    assert sv["completed"] == sv["offered"]
    assert sv["retired_replicas"]  # the abandoned replica is named
    assert open(m["output_path"]).read() == "\n".join(drain_lines)


def test_watchdog_inside_the_harvest_read_requeues_every_settled_slot(setup):
    """A hang injected INSIDE the harvest's single transfer: the
    watchdog abandons the harvest with every settled slot still seated
    (the readback touches no bookkeeping), ``retire()`` requeues each owed
    request exactly once — the settled ones among them — and the abandoned
    thread, when it wakes, delivers nothing and counts nothing."""
    import threading

    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks

    cfg, dataset, params = setup
    eng = engine_lib.SlotEngine(FiraModel(cfg), params, cfg)
    tasks, _ = _decode_tasks(dataset.splits["train"], cfg)
    with Feeder(tasks, num_workers=0, depth=1, put=False) as feed:
        it = iter(feed)
        eng.begin_stream()
        while eng.wants_input():
            item = next(it)
            eng.admit(item.host, item.index, None)
        eng.refill()
        while True:
            eng.step_dispatch()
            done = np.array(eng._state["done"])
            settled = [s for s in eng._busy if done[s]]
            if len(settled) > 1:
                break
            assert len(eng.harvest()) == len(settled)   # none or one: on
            eng.refill()
    reads, rows, commits = (eng.stats.harvest_reads,
                            eng.stats.harvest_row_reads, eng.stats.commits)
    owed = sorted(eng.pending_positions())
    settled_pos = {eng._busy[s][0] for s in settled}
    entered, release, woke = (threading.Event() for _ in range(3))
    out = {}

    class HungTransfer:
        """A leaf of the step's outputs whose host copy hangs: the harvest's
        one ``device_get`` blocks on it."""

        def __init__(self, a):
            self.a = a

        def __array__(self, dtype=None, copy=None):
            entered.set()
            release.wait(120.0)
            return np.asarray(self.a)

    def abandoned_harvest():
        out["items"] = eng.harvest()
        woke.set()

    eng._pending_out = dict(eng._pending_out,
                            tokens=HungTransfer(eng._pending_out["tokens"]))
    with pytest.raises(WatchdogTimeout):
        run_with_watchdog(abandoned_harvest, 1.0, label="harvest")
    assert entered.wait(60.0)           # the thread hangs in the ONE read
    assert all(s in eng._busy for s in settled)
    payloads = eng.retire()
    requeued = []
    for p in payloads:
        v = np.asarray(p["valid"], dtype=bool)
        requeued += [int(x) for x in np.asarray(p["_positions"])[v]]
    assert sorted(requeued) == owed     # every owed request, exactly once
    assert settled_pos <= set(requeued)
    release.set()
    assert woke.wait(60.0) and out["items"] == []
    assert (eng.stats.harvest_reads, eng.stats.harvest_row_reads,
            eng.stats.commits) == (reads, rows, commits)
    assert eng.allocator_invariants() == []


def test_serve_all_replicas_lost_sheds_with_reason(setup, trace, tmp_path):
    """Single replica, step fault at rate 1: the only replica retires on
    its first dispatch and everything still in flight is recorded-shed —
    position-complete output, honest metrics, no hang, no crash."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, inject_faults="engine.step:raise:1.0:0")
    m = serve_split(FiraModel(cfg), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path / "lost"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert sv["replica_retirements"] == 1
    assert sv["completed"] == 0
    assert sv["shed_error"] == sv["offered"]
    lines = open(m["output_path"]).read().splitlines()
    assert len(lines) == sv["offered"]
    assert any("no live replicas" in (r["error"] or "")
               for r in m["request_records"])


def test_drain_fleet_retires_and_requeues(setup, tmp_path, drain_lines):
    """Drain mode (run_test, 2-replica fleet): a seeded replica fault
    retires one replica mid-drain; output bytes equal the no-fault run
    and FleetStats records the retirement + requeues."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, engine_replicas=2,
                            inject_faults="fleet.replica:raise:0.05:8")
    m = run_test(FiraModel(cfg), params, dataset, c,
                 out_dir=str(tmp_path / "fleetchaos"), split="train")
    assert open(m["output_path"]).read() == "\n".join(drain_lines)
    eng = m["engine"]
    assert eng["retirements"] >= 1 and eng["requeues"] >= 1
    assert eng["retired_replicas"]


def test_drain_fleet_prefill_fault_keeps_chunk(setup, tmp_path,
                                               drain_lines):
    """A replica that dies MID-ADMISSION (prefill raises before its chunk
    is staged): the chunk being admitted must survive at the head of the
    fleet's pending queue and complete on the survivor — before the fix,
    _retire only requeued staged/seated requests and the in-admission
    chunk's positions were silently lost (the ordered writer then failed
    at close with missing lines)."""
    cfg, dataset, params = setup
    c = dataclasses.replace(cfg, engine_replicas=2,
                            inject_faults="engine.prefill:raise:0.15:7")
    m = run_test(FiraModel(cfg), params, dataset, c,
                 out_dir=str(tmp_path / "prefillchaos"), split="train")
    assert open(m["output_path"]).read() == "\n".join(drain_lines)
    eng = m["engine"]
    assert eng["retirements"] >= 1


def test_serve_zero_retraces_with_faults_armed(setup, trace, tmp_path):
    """Faults act host-side only: a bucketed chaos run under the armed
    compile guard shows ZERO post-warmup compiles — no fault path ever
    leaves the declared program family."""
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),),
                              engine_replicas=2,
                              inject_faults="engine.step:raise:0.02:18")
    model = FiraModel(cfg)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(model, params, dataset, cfg, arrival_times=trace,
                        out_dir=str(tmp_path / "guarded"), split="train",
                        clock="virtual", guard=guard)
        assert guard.compiles_after_warmup() == 0
    assert m["serve"]["replica_retirements"] >= 0
    assert (m["serve"]["completed"] + m["serve"]["shed_error"]
            == m["serve"]["offered"])


# --------------------------------------------------------------------------
# train: dev-gate watchdog
# --------------------------------------------------------------------------

def test_train_dev_gate_watchdog_skips_wedged_gate(tmp_path, monkeypatch):
    import fira_tpu.train.loop as loop_mod

    data_dir = str(tmp_path / "DataSet")
    write_corpus_dir(data_dir, n_commits=16, seed=5)
    cfg = fira_tiny(batch_size=8, epochs=1, dev_start_epoch=0,
                    dev_every_batches=2, dispatch_watchdog_s=0.1)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg

    def wedged_dev(*a, **k):
        time.sleep(2.0)
        return 0.5, "never observed\n"

    monkeypatch.setattr(loop_mod, "run_dev", wedged_dev)
    result = loop_mod.train(dataset, cfg, out_dir=str(tmp_path / "OUT"),
                            resume=False)
    assert result.epochs_run == 1
    assert any("dev gate" in w and "skipped" in w for w in result.warnings)
    assert result.best_bleu == 0.0  # the wedged gate's result never landed


# --------------------------------------------------------------------------
# serve_metrics.json: atomic write + kill-mid-serve partial snapshot
# --------------------------------------------------------------------------

def test_write_metrics_atomic_roundtrip(tmp_path):
    path = str(tmp_path / "m.json")
    write_metrics_atomic(path, {"a": 1})
    assert json.load(open(path)) == {"a": 1}
    write_metrics_atomic(path, {"a": 2})   # overwrite is atomic too
    assert json.load(open(path)) == {"a": 2}
    assert not os.path.exists(path + ".tmp")
    with pytest.raises(ValueError):
        write_metrics_atomic(path, {"bad": float("nan")})
    assert json.load(open(path)) == {"a": 2}  # failed write tore nothing


_KILL_CHILD = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.model.model import FiraModel
from fira_tpu.serve import poisson_times, serve_split
from fira_tpu.train.state import init_state
from fira_tpu.data.batching import make_batch

work = {work!r}
data_dir = os.path.join(work, "DataSet")
write_corpus_dir(data_dir, n_commits=160, seed=13)
cfg = fira_tiny(batch_size=8, test_batch_size=6, decode_engine=True)
dataset = FiraDataset(data_dir, cfg)
cfg = dataset.cfg
split = dataset.splits["train"]
batch = make_batch(split, np.arange(6), cfg)
params = eos_biased_params(init_state(FiraModel(cfg), cfg, batch).params,
                           delta=4.0)
times = poisson_times(len(split), rate=2000.0, seed=3)
serve_split(FiraModel(cfg), params, dataset, cfg, arrival_times=times,
            out_dir=os.path.join(work, "OUT"), split="train",
            clock="virtual",
            metrics_path=os.path.join(work, "OUT", "serve_metrics.json"))
print("CHILD_DONE", flush=True)
"""


def test_kill_mid_serve_leaves_partial_output_and_metrics(tmp_path):
    """SIGKILL mid-serve: the ordered writer's .partial prefix (plus any
    position-tagged tail) AND a valid-JSON serve_metrics.json.partial
    snapshot survive — nothing served is lost, the metrics artifact is
    never torn (the OrderedStreamWriter crash contract extended to serve
    mode)."""
    work = str(tmp_path)
    child = _KILL_CHILD.format(work=work)
    out_partial = os.path.join(work, "OUT", "output_fira.partial")
    met_partial = os.path.join(work, "OUT", "serve_metrics.json.partial")
    p = subprocess.Popen([sys.executable, "-c", child], cwd=REPO,
                         stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                         text=True)
    try:
        deadline = time.time() + 180
        # kill once real progress exists: some output lines flushed AND at
        # least one metrics snapshot on disk
        while time.time() < deadline:
            if (os.path.exists(met_partial)
                    and os.path.exists(out_partial)
                    and os.path.getsize(out_partial) > 0):
                break
            if p.poll() is not None:
                pytest.fail("serve child exited before the kill window")
            time.sleep(0.05)
        else:
            pytest.fail("serve child never reached the kill window")
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    # output crash pair: plain parseable prefix + optional tagged tail
    prefix = open(out_partial).read()
    assert prefix.endswith("\n") or prefix == ""
    tail_path = out_partial + ".tail"
    if os.path.exists(tail_path):
        for tagged in open(tail_path):
            pos_s, _line = tagged.split("\t", 1)
            assert pos_s.isdigit()
    # metrics partial: valid strict JSON, flagged in-progress, request
    # records present — a mid-run kill no longer loses all serve metrics
    rec = json.load(open(met_partial))
    assert rec["in_progress"] is True
    assert "serve" in rec and "request_records" in rec
    # one record per request of the served split (the 160-commit corpus
    # splits train/valid/test; offered counts the train split)
    assert len(rec["request_records"]) == rec["serve"]["offered"] > 0
    # the final artifact was never written (the run did not complete)
    assert not os.path.exists(os.path.join(work, "OUT",
                                           "serve_metrics.json"))


def test_cli_serve_metrics_written_atomically(setup, trace, tmp_path):
    """The library path the CLI rides: serve_split(metrics_path=...)
    writes the final artifact atomically and removes the partial."""
    cfg, dataset, params = setup
    mp = str(tmp_path / "serve_metrics.json")
    m = serve_split(FiraModel(cfg), params, dataset, cfg,
                    arrival_times=trace, out_dir=str(tmp_path / "OUT"),
                    split="train", clock="virtual", metrics_path=mp)
    assert m["metrics_path"] == mp
    rec = json.load(open(mp))
    assert rec["serve"]["completed"] == len(trace)
    assert not os.path.exists(mp + ".partial")
    assert not os.path.exists(mp + ".tmp")
