"""Disaggregated serving tiers (fira_tpu/serve/disagg.py —
docs/SERVING.md "Disaggregated tiers").

Pins the prefill-pool split's whole contract:

- trace-replay BYTE-IDENTITY to in-process serving, invariant to
  prefill-worker count (1/2) and decode-replica count (1/2) — the
  worker computes the exact prefix-cache payload with the same jitted
  prefill, and the cache-hit seat is bit-identical to a direct prefill;
- ZERO decode-tier prefill dispatches and zero post-warmup compiles
  with tiers on: every request seats through the prefix cache's
  all-hit admission path (host assemble + one device_put);
- per-request tier stamps (prefill_queue_s / transport_s /
  artifact_bytes) and the serve_metrics ``tiers`` block, present ONLY
  when tiers ran;
- lifecycle under the retirement machinery: a dead worker retires and
  its rows requeue to survivors byte-identically; a corrupt artifact is
  checksum-caught and re-prefilled — NEVER a wrong answer;
- the bounded artifact in-flight budget holds under a one-sided flood;
- parse-time knob validation with named messages and CLI exit 2.

The process-spawning runs are deliberately small (12-row mixes, single
geometry): each one pays worker-spawn latency on top of fresh-engine
compiles. The bucketed zero-retrace variant lives in the check.sh leg
(scripts/serve_bench.py --disagg-smoke).
"""

import numpy as np
import pytest

from fira_tpu import cli
from fira_tpu.analysis import sanitizer
from fira_tpu.config import fira_tiny
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.synthetic import write_corpus_dir
from fira_tpu.decode.beam import eos_biased_params
from fira_tpu.model.model import FiraModel
from fira_tpu.robust import faults as faults_lib
from fira_tpu.serve import arrivals, serve_split
from fira_tpu.serve.disagg import disagg_errors
from fira_tpu.train.state import init_state

MIX = list(range(12))          # all-distinct: every request is a tier job


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("disagg_corpus"))
    write_corpus_dir(data_dir, n_commits=24, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=4, decode_engine=True,
                    engine_slots=4, prefix_cache=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    from fira_tpu.data.batching import make_batch

    batch = make_batch(dataset.splits["train"], np.arange(4), cfg,
                       batch_size=4)
    params = init_state(FiraModel(cfg), cfg, batch).params
    return cfg, dataset, eos_biased_params(params, delta=4.0)


@pytest.fixture(scope="module")
def trace():
    return arrivals.poisson_times(len(MIX), rate=1.0, seed=3)


@pytest.fixture(scope="module")
def inproc_ref(setup, trace, tmp_path_factory):
    """The in-process (tiers-off) serve of the same mix — the byte
    reference every disagg variant must reproduce."""
    cfg, dataset, params = setup
    out = str(tmp_path_factory.mktemp("inproc_ref"))
    m = serve_split(FiraModel(cfg), params, dataset, cfg,
                    arrival_times=trace, out_dir=out, split="train",
                    clock="virtual", request_mix=MIX)
    assert m["serve"]["completed"] == len(MIX)
    # the tiers block exists ONLY when tiers ran
    assert "tiers" not in m["serve"]
    return m, open(m["output_path"], "rb").read()


def _tier_cfg(cfg, workers, replicas):
    c = cfg.replace(serve_tiers="prefill-pool", prefill_workers=workers)
    if replicas > 1:
        c = c.replace(engine_replicas=replicas,
                      engine_slots=cfg.engine_slots * replicas)
    return c


# --------------------------------------------------------------------------
# byte identity x (workers, replicas), zero decode prefills, stamps
# --------------------------------------------------------------------------

@pytest.mark.parametrize("workers,replicas",
                         [(1, 1), (2, 1), (1, 2), (2, 2)])
def test_disagg_bytes_identical_to_inprocess(setup, trace, inproc_ref,
                                             tmp_path, workers, replicas):
    """Disagg serve bytes == in-process serve bytes at every worker x
    replica shape, with every row transport-delivered, ZERO decode-tier
    prefill dispatches, zero post-warmup compiles, and the per-request
    tier stamps + tiers block recorded."""
    cfg, dataset, params = setup
    _, ref = inproc_ref
    c = _tier_cfg(cfg, workers, replicas)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(FiraModel(c), params, dataset, c,
                        arrival_times=trace, out_dir=str(tmp_path),
                        split="train", clock="virtual", guard=guard,
                        request_mix=MIX)
        extra = guard.compiles_after_warmup()
    assert open(m["output_path"], "rb").read() == ref
    assert extra == 0
    sv = m["serve"]
    assert sv["completed"] == len(MIX)
    tiers = sv["tiers"]
    assert tiers["workers"] == workers
    assert tiers["rows_delivered"] == len(MIX)
    assert tiers["rows_given_up"] == 0 and not tiers["fallback"]
    # decode replicas seated exclusively through the all-hit cache path
    assert m["engine"]["prefills"] == 0
    assert m["engine"]["cache_hits"] == len(MIX)
    done = [r for r in m["request_records"] if r["status"] == "done"]
    assert done and all(r["transport_s"] is not None
                        and r["artifact_bytes"] > 0
                        and r["prefill_queue_s"] is not None
                        for r in done)


# --------------------------------------------------------------------------
# lifecycle: worker death => retire + requeue; corrupt => re-prefill
# --------------------------------------------------------------------------

def test_worker_death_requeues_to_survivor(setup, trace, inproc_ref,
                                           tmp_path):
    """A seeded disagg.worker fault kills one of two workers mid-run
    (the child exits on the injector's deterministic draw); its pending
    rows requeue to the survivor and the bytes stay identical."""
    cfg, dataset, params = setup
    _, ref = inproc_ref
    c = _tier_cfg(cfg, 2, 1).replace(
        inject_faults="disagg.worker:raise:0.12:5")
    m = serve_split(FiraModel(c), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path), split="train", clock="virtual",
                    request_mix=MIX)
    tiers = m["serve"]["tiers"]
    assert tiers["workers_lost"] >= 1
    assert m["serve"]["completed"] == len(MIX)
    assert open(m["output_path"], "rb").read() == ref


def test_all_workers_lost_falls_back_in_process(setup, trace, inproc_ref,
                                                tmp_path):
    """Every worker dead => the tier records fallback and the loop
    serves the remainder in-process — same bytes, nothing hangs."""
    cfg, dataset, params = setup
    _, ref = inproc_ref
    c = _tier_cfg(cfg, 1, 1).replace(
        inject_faults="disagg.worker:raise:0.6:7")
    m = serve_split(FiraModel(c), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path), split="train", clock="virtual",
                    request_mix=MIX)
    tiers = m["serve"]["tiers"]
    assert tiers["workers_lost"] == 1 and tiers["fallback"]
    assert tiers["fallback_reason"]
    assert m["serve"]["completed"] == len(MIX)
    assert open(m["output_path"], "rb").read() == ref
    # the fallback rows were prefilled in-process on the decode tier
    assert m["engine"]["prefills"] > 0


def test_corrupt_artifact_checksum_caught_and_reprefilled(
        setup, trace, inproc_ref, tmp_path):
    """A corrupted transport payload is caught by the artifact checksum
    at seat time and the row re-prefilled — integrity drops metered,
    bytes EXACTLY the no-fault bytes. Never a wrong answer."""
    cfg, dataset, params = setup
    _, ref = inproc_ref
    c = _tier_cfg(cfg, 1, 1).replace(
        inject_faults="disagg.transport:corrupt:0.4:7")
    inj = faults_lib.injector_from(c)
    m = serve_split(FiraModel(c), params, dataset, c, arrival_times=trace,
                    out_dir=str(tmp_path), split="train", clock="virtual",
                    request_mix=MIX, faults=inj)
    tiers = m["serve"]["tiers"]
    assert sum(m.get("faults", {}).values()) > 0
    assert tiers["transport_integrity_drops"] > 0
    assert tiers["rows_resubmitted"] > 0
    assert m["serve"]["completed"] == len(MIX)
    assert open(m["output_path"], "rb").read() == ref


# --------------------------------------------------------------------------
# backpressure: the bounded artifact in-flight budget under a flood
# --------------------------------------------------------------------------

def test_artifact_budget_bounds_inflight_under_flood(setup, inproc_ref,
                                                     tmp_path):
    """One-sided flood (every arrival at t=0) against a 1 MB in-flight
    budget: submissions serialize — peak in-flight artifact bytes stay
    within the budget (single-geometry groups here are far smaller than
    1 MB, so the bound binds strictly) — and the flood still completes
    byte-identically."""
    cfg, dataset, params = setup
    _, ref = inproc_ref
    c = _tier_cfg(cfg, 2, 1).replace(serve_artifact_budget_mb=1)
    m = serve_split(FiraModel(c), params, dataset, c,
                    arrival_times=[0.0] * len(MIX),
                    out_dir=str(tmp_path), split="train", clock="virtual",
                    request_mix=MIX)
    tiers = m["serve"]["tiers"]
    assert tiers["rows_delivered"] == len(MIX)
    assert 0 < tiers["peak_inflight_bytes"] <= 1 << 20
    assert tiers["inflight_bytes"] == 0        # all accounted back down
    assert m["serve"]["completed"] == len(MIX)
    assert open(m["output_path"], "rb").read() == ref


# --------------------------------------------------------------------------
# knob validation (parse-time, named messages) + CLI exit 2
# --------------------------------------------------------------------------

def test_disagg_errors_named_messages():
    cfg = fira_tiny(decode_engine=True, prefix_cache=True)
    assert disagg_errors(cfg) == []
    ok = cfg.replace(serve_tiers="prefill-pool")
    assert disagg_errors(ok) == []
    errs = disagg_errors(cfg.replace(serve_tiers="bogus"))
    assert any("serve_tiers" in e for e in errs)
    errs = disagg_errors(ok.replace(prefix_cache=False))
    assert any("prefix_cache" in e for e in errs)
    errs = disagg_errors(ok.replace(decode_engine=False))
    assert any("decode_engine" in e for e in errs)
    errs = disagg_errors(ok.replace(prefill_workers=0))
    assert any("prefill_workers" in e for e in errs)
    errs = disagg_errors(ok.replace(serve_artifact_budget_mb=-1))
    assert any("serve_artifact_budget_mb" in e for e in errs)


def test_disagg_refuses_an_accelerator_parent():
    # the workers are processes and an accelerator belongs to one process:
    # armed on anything but the CPU the tier is a parse-time error (ROADMAP
    # S6), never a pool of dead children and a recorded fallback
    ok = fira_tiny(decode_engine=True, prefix_cache=True,
                   serve_tiers="prefill-pool")
    assert disagg_errors(ok, platform="cpu") == []
    assert disagg_errors(ok) == []          # asks jax: this session is cpu
    errs = disagg_errors(ok, platform="tpu")
    assert len(errs) == 1 and "serve_tiers=prefill-pool" in errs[0]
    assert "'tpu'" in errs[0] and "ROADMAP S6" in errs[0]
    # tier off: nothing to refuse wherever it runs
    assert disagg_errors(ok.replace(serve_tiers="off"), platform="tpu") == []


def test_cli_disagg_knob_validation_exit2(tmp_path, capsys):
    data = str(tmp_path / "DataSet")
    write_corpus_dir(data, n_commits=16, seed=5)
    base = ["serve", "--config", "fira-tiny", "--data-dir", data,
            "--out-dir", str(tmp_path / "OUT"), "--serve-rate", "5",
            "--engine", "--prefix-cache", "on",
            "--serve-tiers", "prefill-pool"]
    assert cli.main(base + ["--prefill-workers", "0"]) == 2
    assert "prefill_workers" in capsys.readouterr().err
    assert cli.main(base + ["--serve-artifact-budget-mb", "-1"]) == 2
    assert "serve_artifact_budget_mb" in capsys.readouterr().err
    # prefill-pool with the prefix cache explicitly OFF is a parse-time
    # error too (serve defaults the cache ON, so the conflict needs the
    # explicit off)
    no_cache = [("off" if a == "on" else a) for a in base]
    assert cli.main(no_cache + ["--prefill-workers", "2"]) == 2
    assert "prefix_cache" in capsys.readouterr().err


def test_payload_checksum_detects_mutation():
    from fira_tpu.decode.prefix_cache import payload_checksum

    payload = {"a": np.arange(6, dtype=np.int32).reshape(2, 3),
               "b": np.ones((3,), dtype=np.float32)}
    ck = payload_checksum(payload)
    assert ck == payload_checksum(
        {k: v.copy() for k, v in payload.items()})
    mutated = {k: v.copy() for k, v in payload.items()}
    mutated["b"][1] = 2.0
    assert payload_checksum(mutated) != ck
