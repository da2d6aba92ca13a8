"""Online serving on the slot engine (fira_tpu/serve — docs/SERVING.md).

Pins the serving layer's whole contract:

- arrival-trace REPLAY equivalence: on a replayed trace with no shedding,
  serve-mode output file bytes are IDENTICAL to drain-mode decode and
  invariant to replica count (1/2), harvest cadence, and feeder worker
  count — per-sample beam math is batch-composition-invariant and the
  ordered writer keys by split position;
- scheduler determinism: the completion sequence (which request settles
  at which round) is identical across feeder worker counts, and seating
  follows arrival order (FIFO admission);
- zero post-warmup retraces under the declared engine program family —
  serve-mode batches reuse the drain packer's exact geometries/batch
  size, so no new program compiles;
- structured shed-on-backpressure: a bounded admission queue rejects on
  arrival, per-request deadlines shed queued requests, both recorded —
  and the run still terminates with a position-complete output file;
- the latency-aware prefill budget: admissions between step dispatches
  never exceed it;
- parse-time knob validation with named messages and CLI exit 2;
- the batched harvest readback metering (decode/engine.py satellite).
"""

import dataclasses
import math
import os

import numpy as np
import pytest

from fira_tpu import cli
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.decode.runner import run_test
from fira_tpu.model.model import FiraModel
from fira_tpu.serve import arrivals, serve_split
from fira_tpu.serve.server import serve_errors
from fira_tpu.train.state import init_state


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("serve_corpus"))
    write_corpus_dir(data_dir, n_commits=40, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=6, decode_engine=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(6), cfg)
    params = init_state(FiraModel(cfg), cfg, batch).params
    # moderate EOS bias: mixed settle depths — the schedule refill (and
    # arrival-timed admission) exists for
    return cfg, dataset, eos_biased_params(params, delta=4.0)


@pytest.fixture(scope="module")
def trace(setup):
    """One fixed arrival schedule (virtual-clock units) every replay
    variant below serves: moderate rate, so arrivals interleave with
    service and the queue is non-trivially exercised."""
    cfg, dataset, _ = setup
    n = len(dataset.splits["train"])
    return arrivals.poisson_times(n, rate=0.4, seed=3)


@pytest.fixture(scope="module")
def drain_bytes(setup, tmp_path_factory):
    """Drain-mode engine decode of the train split — the byte reference
    every serve replay must reproduce."""
    cfg, dataset, params = setup
    out = str(tmp_path_factory.mktemp("drain"))
    m = run_test(FiraModel(cfg), params, dataset, cfg, out_dir=out,
                 split="train")
    return m, open(m["output_path"], "rb").read()


# --------------------------------------------------------------------------
# arrival schedules
# --------------------------------------------------------------------------

def test_poisson_trace_deterministic_and_roundtrips(tmp_path):
    a = arrivals.poisson_times(50, rate=2.0, seed=9)
    b = arrivals.poisson_times(50, rate=2.0, seed=9)
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0
    # a different seed is a different schedule
    assert not np.array_equal(a, arrivals.poisson_times(50, 2.0, seed=10))
    path = str(tmp_path / "trace.txt")
    arrivals.write_trace(path, a)
    got = arrivals.read_trace(path)
    np.testing.assert_allclose(got, a, atol=1e-9)
    with open(path, "a") as f:
        f.write("bogus\n")
    with pytest.raises(ValueError, match="not a float"):
        arrivals.read_trace(path)


def test_trace_validation_rejects_malformed(tmp_path):
    with pytest.raises(ValueError, match="non-decreasing"):
        arrivals.write_trace(str(tmp_path / "t"), np.array([1.0, 0.5]))
    with pytest.raises(ValueError, match=">= 0"):
        arrivals.write_trace(str(tmp_path / "t"), np.array([-1.0, 0.5]))
    with pytest.raises(ValueError, match="rate"):
        arrivals.poisson_times(5, rate=0.0)


# --------------------------------------------------------------------------
# replay equivalence: serve bytes == drain bytes, every schedule knob
# --------------------------------------------------------------------------

def test_serve_replay_bytes_identical_to_drain(setup, trace, drain_bytes,
                                               tmp_path):
    """Replayed trace, no shedding: output file bytes equal drain mode,
    invariant to harvest cadence, feeder worker count, and prefill
    budget."""
    cfg, dataset, params = setup
    ref_metrics, ref = drain_bytes
    model = FiraModel(cfg)
    variants = [
        dict(engine_harvest_every=1, feeder_workers=0),
        dict(engine_harvest_every=4, feeder_workers=2),
        dict(engine_harvest_every=3, feeder_workers=1,
             serve_prefill_budget=4, engine_prefill_depth=4),
    ]
    for i, kw in enumerate(variants):
        c = dataclasses.replace(cfg, **kw)
        m = serve_split(model, params, dataset, c, arrival_times=trace,
                        out_dir=str(tmp_path / f"v{i}"), split="train",
                        clock="virtual")
        assert open(m["output_path"], "rb").read() == ref, kw
        assert m["sentence_bleu"] == ref_metrics["sentence_bleu"]
        sv = m["serve"]
        assert sv["completed"] == sv["offered"] == len(trace)
        assert sv["shed_queue_full"] == 0 and sv["shed_deadline"] == 0


def test_serve_replay_invariant_to_replica_count(setup, trace, drain_bytes,
                                                 tmp_path):
    cfg, dataset, params = setup
    _, ref = drain_bytes
    model = FiraModel(cfg)
    m = serve_split(model, params, dataset,
                    dataclasses.replace(cfg, engine_replicas=2),
                    arrival_times=trace, out_dir=str(tmp_path / "r2"),
                    split="train", clock="virtual")
    assert open(m["output_path"], "rb").read() == ref
    assert m["engine"]["replicas"] == 2
    assert all(c > 0 for c in m["engine"]["per_replica_commits"])


def test_serve_zero_retraces_on_bucketed_stream(setup, trace, tmp_path):
    """Bucketed serve under the armed sanitizer: the declared (geometry x
    {prefill, step, insert}) family warms once, then zero
    post-warmup compiles — serve-mode online batch formation reuses the
    drain packer's exact geometries, so no new program exists to
    compile. Bytes still equal the drain-mode engine on the same
    bucketed stream."""
    cfg0, dataset, params = setup
    cfg = dataclasses.replace(cfg0, buckets=((16, 400, 12),))
    model = FiraModel(cfg)
    ref = run_test(model, params, dataset, cfg,
                   out_dir=str(tmp_path / "drain"), split="train")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(model, params, dataset, cfg, arrival_times=trace,
                        out_dir=str(tmp_path / "serve"), split="train",
                        clock="virtual", guard=guard)
        assert guard.compiles_after_warmup() == 0
    assert (open(m["output_path"], "rb").read()
            == open(ref["output_path"], "rb").read())
    # the family serve dispatched: prefill, step and insert — the harvest
    # reads the step's own outputs and runs no program of its own
    assert {lbl.split("[")[0] for lbl in guard._seen} == {
        "engine_prefill", "engine_step", "engine_insert"}


# --------------------------------------------------------------------------
# scheduler determinism + latency records
# --------------------------------------------------------------------------

def test_serve_completion_sequence_stable_across_worker_counts(
        setup, trace, tmp_path):
    """The full per-round schedule — completion sequence AND latency
    stamps — is a pure function of the trace and the knobs: feeder
    worker count (host-side assembly parallelism) must not perturb it."""
    cfg, dataset, params = setup
    model = FiraModel(cfg)
    runs = []
    for i, workers in enumerate((0, 2)):
        c = dataclasses.replace(cfg, feeder_workers=workers)
        m = serve_split(model, params, dataset, c, arrival_times=trace,
                        out_dir=str(tmp_path / f"w{i}"), split="train",
                        clock="virtual")
        runs.append(m)
    assert runs[0]["request_records"] == runs[1]["request_records"]

    def scheduled(m):
        # `phases` is the recorder's block (utils/profiling.py): wall
        # seconds, the one part of the summary that is schedule-dependent
        return {k: v for k, v in m["serve"].items() if k != "phases"}

    assert scheduled(runs[0]) == scheduled(runs[1])
    # ... but how many rounds it timed is part of the schedule
    a, b = (m["serve"]["phases"] for m in runs)
    assert a["serve.round"]["count"] == b["serve.round"]["count"]
    assert a["serve.step_dispatch"]["count"] == runs[0]["serve"]["rounds"]


def test_serve_latency_records_complete_and_ordered(setup, trace, tmp_path):
    cfg, dataset, params = setup
    m = serve_split(FiraModel(cfg), params, dataset, cfg,
                    arrival_times=trace, out_dir=str(tmp_path / "lat"),
                    split="train", clock="virtual")
    recs = m["request_records"]
    assert len(recs) == len(trace)
    for r in recs:
        assert r["status"] == "done"
        # lifecycle is ordered: arrival <= admit <= seat <= first step
        # <= done, every latency non-negative
        assert (r["arrival_t"] <= r["admit_t"] <= r["seat_t"]
                <= r["first_step_t"] <= r["done_t"])
    # FIFO admission: seat times are non-decreasing in arrival
    # (= position) order — an earlier arrival is never seated later
    seats = [r["seat_t"] for r in recs]
    assert seats == sorted(seats)
    sv = m["serve"]
    assert sv["p50_ttft_s"] <= sv["p99_ttft_s"]
    assert sv["p50_e2e_s"] <= sv["p99_e2e_s"]
    assert sv["p50_ttft_s"] <= sv["p50_e2e_s"]


# --------------------------------------------------------------------------
# backpressure: bounded queue, deadlines, budget
# --------------------------------------------------------------------------

def test_serve_bounded_queue_sheds_and_terminates(setup, tmp_path):
    """A burst (every request at t=0) against a 2-deep admission queue:
    overflow arrivals are rejected on the spot, recorded, and the run
    still terminates with a position-complete output file — never a
    hang, never a writer gap."""
    cfg, dataset, params = setup
    n = len(dataset.splits["train"])
    m = serve_split(FiraModel(cfg), params, dataset,
                    dataclasses.replace(cfg, serve_queue_cap=2),
                    arrival_times=np.zeros(n),
                    out_dir=str(tmp_path / "cap"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert sv["shed_queue_full"] > 0
    assert sv["completed"] + sv["shed_queue_full"] == n
    lines = open(m["output_path"]).read().splitlines()
    assert len(lines) == n  # shed positions hold an empty line
    shed = [r for r in m["request_records"]
            if r["status"] == "shed_queue_full"]
    assert len(shed) == sv["shed_queue_full"]
    assert all(math.isnan(r["seat_t"]) for r in shed)


def test_serve_deadline_sheds_queued_requests(setup, tmp_path):
    """A burst against a tiny arena with a 1-step deadline: requests
    still queued after one step dispatch are shed, seated ones complete."""
    cfg, dataset, params = setup
    n = len(dataset.splits["train"])
    m = serve_split(FiraModel(cfg),
                    params, dataset,
                    dataclasses.replace(cfg, serve_deadline_steps=1,
                                        engine_slots=4),
                    arrival_times=np.zeros(n),
                    out_dir=str(tmp_path / "dl"), split="train",
                    clock="virtual")
    sv = m["serve"]
    assert sv["shed_deadline"] > 0 and sv["completed"] > 0
    assert sv["completed"] + sv["shed_deadline"] == n
    # completed requests' lines match drain-mode content per position
    for r in m["request_records"]:
        assert r["status"] in ("done", "shed_deadline")


def test_serve_prefill_budget_caps_admissions_per_round(setup, tmp_path):
    """The latency-aware refill knob: admissions between consecutive
    step dispatches never exceed the budget, and a deeper budget does
    admit more under a burst (the knob binds in both directions)."""
    cfg0, dataset, params = setup
    n = len(dataset.splits["train"])
    model = FiraModel(cfg0)
    burst = np.zeros(n)
    maxes = {}
    for budget in (1, 2):
        c = dataclasses.replace(cfg0, serve_prefill_budget=budget,
                                engine_prefill_depth=2,
                                engine_slots=12)
        m = serve_split(model, params, dataset, c, arrival_times=burst,
                        out_dir=str(tmp_path / f"b{budget}"),
                        split="train", clock="virtual")
        maxes[budget] = m["serve"]["max_admits_per_round"]
        assert m["serve"]["completed"] == n
        assert maxes[budget] <= budget  # single replica
    assert maxes[2] > maxes[1]


# --------------------------------------------------------------------------
# parse-time validation (satellite: named-knob messages, CLI exit 2)
# --------------------------------------------------------------------------

def test_serve_errors_named_messages():
    cfg = fira_tiny(decode_engine=True, test_batch_size=6)
    assert serve_errors(cfg.replace(serve_rate=1.0), trace=False) == []
    assert serve_errors(cfg, trace=True) == []
    errs = serve_errors(cfg, trace=False)
    assert errs and "serve_rate" in errs[0]
    errs = serve_errors(cfg.replace(serve_rate=-1.0), trace=True)
    assert errs and "serve_rate" in errs[0]
    errs = serve_errors(cfg.replace(serve_rate=1.0,
                                    serve_prefill_budget=0), trace=False)
    assert errs and "serve_prefill_budget" in errs[0]
    # budget caps at the PER-REPLICA slot count
    errs = serve_errors(cfg.replace(serve_rate=1.0, engine_slots=8,
                                    engine_replicas=2,
                                    serve_prefill_budget=5), trace=False)
    assert errs and "serve_prefill_budget" in errs[0]
    errs = serve_errors(cfg.replace(serve_rate=1.0,
                                    serve_deadline_steps=-1), trace=False)
    assert errs and "serve_deadline_steps" in errs[0]
    errs = serve_errors(cfg.replace(serve_rate=1.0, serve_queue_cap=-2),
                        trace=False)
    assert errs and "serve_queue_cap" in errs[0]


def test_cli_serve_knob_validation_exit2(tmp_path, capsys):
    data = str(tmp_path / "DataSet")
    write_corpus_dir(data, n_commits=16, seed=5)
    base = ["serve", "--config", "fira-tiny", "--data-dir", data,
            "--out-dir", str(tmp_path / "OUT")]
    # no rate, no trace
    assert cli.main(base) == 2
    assert "serve_rate" in capsys.readouterr().err
    # budget out of range
    assert cli.main(base + ["--serve-rate", "5",
                            "--serve-prefill-budget", "0"]) == 2
    assert "serve_prefill_budget" in capsys.readouterr().err
    # negative deadline
    assert cli.main(base + ["--serve-rate", "5",
                            "--serve-deadline-steps", "-1"]) == 2
    assert "serve_deadline_steps" in capsys.readouterr().err


def test_cli_serve_end_to_end(tmp_path):
    """train 1 epoch, then `serve` with a replayed trace: output file +
    serve_metrics.json land in out-dir."""
    data = str(tmp_path / "DataSet")
    out = str(tmp_path / "OUTPUT")
    rc = cli.main(["train", "--config", "fira-tiny", "--synthetic", "24",
                   "--epochs", "1", "--data-dir", data, "--out-dir", out])
    assert rc == 0
    from fira_tpu.data.dataset import FiraDataset

    args = cli.build_parser().parse_args(
        ["serve", "--config", "fira-tiny", "--data-dir", data])
    n = len(FiraDataset(data, cli._resolve_cfg(args)).splits["test"])
    trace_path = str(tmp_path / "trace.txt")
    arrivals.write_trace(trace_path,
                         arrivals.poisson_times(n, rate=0.5, seed=1))
    rc = cli.main(["serve", "--config", "fira-tiny", "--data-dir", data,
                   "--out-dir", out, "--serve-trace", trace_path,
                   "--serve-clock", "virtual"])
    assert rc == 0
    assert os.path.exists(os.path.join(out, "output_fira"))
    import json

    with open(os.path.join(out, "serve_metrics.json")) as f:
        rec = json.load(f)
    assert rec["serve"]["completed"] == n
    assert len(rec["request_records"]) == n
    # an over-long trace is a parse-time error, not a mid-run crash
    arrivals.write_trace(trace_path,
                         arrivals.poisson_times(n + 5, rate=0.5, seed=1))
    rc = cli.main(["serve", "--config", "fira-tiny", "--data-dir", data,
                   "--out-dir", out, "--serve-trace", trace_path])
    assert rc == 2


# --------------------------------------------------------------------------
# batched harvest readback (decode/engine.py satellite)
# --------------------------------------------------------------------------

def test_harvest_sliced_readback_metered(setup):
    """Harvest reads every settled slot's rows of a dispatch from the one
    transfer of the step's outputs: one row delivered per commit, no more
    reads than rows, and the metered bytes are what really crossed."""
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks

    cfg, dataset, params = setup
    data = dataset.splits["train"]
    eng = engine_lib.SlotEngine(FiraModel(cfg), params, cfg)
    tasks, _ = _decode_tasks(data, cfg)
    with Feeder(tasks, num_workers=0, depth=1) as feed:
        for _ in eng.run(feed):
            pass
    st = eng.stats
    assert st.harvest_row_reads == st.commits == len(data)
    # one read a harvest that settled rows, and the bytes that really
    # crossed: every slot's tokens and probs, at every harvest
    assert 0 < st.harvest_reads <= st.harvest_row_reads
    state = eng._state
    assert st.harvest_bytes_read == st.step_dispatches * (
        state["tokens"].nbytes + state["probs"].nbytes)
    s = st.summary()
    assert s["harvest_reads"] == st.harvest_reads
    assert s["harvest_bytes_read"] == st.harvest_bytes_read


def test_serve_stats_serialize_completion_order_and_stable_heartbeats():
    """The two firacheck v3 self-applications in ServeStats.summary():
    ``completions`` (recorded since PR 11) must actually serialize
    (STATS-SCHEMA), and ``heartbeats`` — a dict keyed by replica tag in
    first-dispatch settle order — must serialize byte-identically
    regardless of insertion order (DET-TAINT)."""
    import json

    from fira_tpu.serve.server import ServeStats

    a = ServeStats(records=[])
    a.completions = [4, 1, 3]
    a.heartbeats["r1"] = {"round": 2, "dispatches": 7}
    a.heartbeats["r0"] = {"round": 2, "dispatches": 9}
    b = ServeStats(records=[])
    b.completions = [4, 1, 3]
    b.heartbeats["r0"] = {"round": 2, "dispatches": 9}
    b.heartbeats["r1"] = {"round": 2, "dispatches": 7}
    sa, sb = a.summary(), b.summary()
    assert sa["completion_order"] == [4, 1, 3]
    assert (json.dumps(sa["heartbeats"], sort_keys=False)
            == json.dumps(sb["heartbeats"], sort_keys=False))
