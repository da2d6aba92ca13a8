"""Chaos bench: seeded faults through the serving stack -> docs/CHAOS_BENCH_r01.jsonl.

The graceful-degradation machinery (fira_tpu/robust — docs/FAULTS.md) is
only real if it is exercised: this script injects a seeded fault at each
registered site through a serve run (the serve_bench.py --smoke shape:
fixed trace, virtual clock, armed compile guard) and checks the
degradation contracts machine-verifiably:

- the run neither hangs nor crashes — every request ends ``done`` or
  recorded-shed, the output file stays position-complete;
- a retired replica's in-flight requests are requeued onto survivors and
  COMPLETED, with output bytes identical to the no-fault run (per-row
  beam independence makes a re-served request bit-exact);
- requests shed by the poison quarantine hold an empty output line and a
  recorded error; every position not shed matches the no-fault bytes;
- zero post-warmup retraces with faults armed (faults act on the host
  side only — no new program exists to compile).

Modes:
  --smoke     one seeded fault per site + a corrupt leg + a watchdog-hang
              leg, each checked against the contracts above under the
              armed compile guard, plus the disaggregated-tier legs
              (docs/SERVING.md "Disaggregated tiers"): transport
              raise/corrupt (lost message => resubmit, corrupt artifact
              => checksum-caught re-prefill), worker death => retire +
              requeue to survivors, all-workers-lost => recorded
              in-process fallback — bytes equal to the no-fault drain
              in every case. Exit nonzero on any violation — the
              scripts/check.sh tier-1 leg.
  --recovery-smoke
              the SELF-HEALING contracts (robust/recovery.py; docs/
              FAULTS.md "Recovery contracts"), each machine-checked
              under the armed compile guard: (a) a seeded replica fault
              mid-serve ends with a respawned replica serving and final
              bytes identical to the no-fault run at zero post-warmup
              compiles; (b) a warm-spare attach replaces a dead replica
              with zero mid-run compiles; (c) a respawn storm exhausts
              max_respawns and degrades like PR 9 (recorded sheds, no
              hang); (d) SIGKILL mid-serve (subprocess) followed by a
              journal resume yields bytes identical to an uninterrupted
              run. The scripts/check.sh recovery leg.
  --resume-child
              internal: the subprocess the kill-mid-serve legs SIGKILL
              (deterministic setup from --data-dir, wall-clock serve
              with the write-ahead journal into --out-dir).
  (default)   measure throughput / shed-rate / retirement rows across
              injected fault rates, write --out (the committed artifact
              docs/CHAOS_BENCH_r01.jsonl), then the RECOVERY rows —
              capacity-restored-over-time under respawn and the
              journal/resume overhead — into --out2 (the committed
              artifact docs/CHAOS_BENCH_r02.jsonl); echo a final JSON
              line with all rows.

Env knobs: FIRA_CHAOS_COMMITS (measure-mode corpus size, default 240),
FIRA_CHAOS_RATES (default "0.0,0.05,0.2" per-event fire probabilities),
FIRA_CHAOS_SEED (default 11), FIRA_CHAOS_SLOTS (default 8),
FIRA_CHAOS_BATCH (default 6).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

DEFAULT_OUT = os.path.join(REPO_ROOT, "docs", "CHAOS_BENCH_r01.jsonl")
DEFAULT_OUT2 = os.path.join(REPO_ROOT, "docs", "CHAOS_BENCH_r02.jsonl")


def _setup(n_commits: int, *, batch: int, slots: int, replicas: int = 1,
           buckets=(), **cfg_kw):
    """Synthetic corpus + tiny engine config + EOS-biased params (the
    serve_bench recipe, chaos knobs riding on top)."""
    import numpy as np

    from fira_tpu.config import fira_tiny
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import init_state

    data_dir = tempfile.mkdtemp(prefix="fira_chaos_bench_")
    write_corpus_dir(data_dir, n_commits=n_commits, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=batch,
                    decode_engine=True, engine_slots=slots * replicas,
                    engine_replicas=replicas, buckets=buckets, **cfg_kw)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    sample = make_batch(split, np.arange(min(batch, len(split))), cfg,
                        batch_size=batch)
    model = FiraModel(cfg)
    params = eos_biased_params(init_state(model, cfg, sample).params,
                               delta=4.0)
    return dataset, cfg, model, params


def _check_degraded_bytes(ref_lines, got_lines, records):
    """Every position that was NOT recorded-shed (or corrupted) must hold
    the no-fault line; shed positions must hold an empty line. Returns a
    list of violations (empty = contract holds)."""
    bad = []
    if len(ref_lines) != len(got_lines):
        return [f"line count {len(got_lines)} != no-fault {len(ref_lines)}"]
    for rec in records:
        pos = rec["position"]
        if rec["status"] == "done":
            continue  # checked in bulk below
        if got_lines[pos] != "":
            bad.append(f"shed position {pos} line is not empty")
    shed = {r["position"] for r in records if r["status"] != "done"}
    for pos, (a, b) in enumerate(zip(ref_lines, got_lines)):
        if pos in shed:
            continue
        if a != b:
            bad.append(f"completed position {pos} differs from no-fault")
    return bad


def smoke() -> int:
    """One seeded fault per site through a fixed-trace virtual-clock
    serve under the armed compile guard; contracts checked per leg."""
    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.serve import poisson_times, serve_split

    # 2 replicas so replica-level faults have survivors to degrade onto;
    # bucketed so the declared program family (and its zero-retrace
    # contract) is non-trivial
    dataset, cfg, model, params = _setup(
        40, batch=6, slots=6, replicas=2, buckets=((16, 400, 12),),
        dispatch_watchdog_s=0.0, robust_retries=1, fault_hang_s=1.0)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_chaos_smoke_")

    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "drain"), split="train")
    ref_lines = open(drain["output_path"]).read().split("\n")

    # (site, kind, rate, extra cfg) — rates/seeds chosen so each leg's
    # fault actually FIRES on this fixed schedule (asserted below: a leg
    # whose fault never fired proves nothing)
    legs = [
        # (site, kind, rate, seed, extra-cfg) — seeds picked so the fault
        # FIRES mid-run on this fixed schedule (the deterministic draw is
        # a pure function of (seed, site, event key))
        ("feeder.assemble", "raise", 0.08, 7, {}),
        ("feeder.device_put", "raise", 0.08, 8, {}),
        ("engine.prefill", "raise", 0.15, 9, {}),
        ("engine.step", "raise", 0.02, 18, {}),
        ("engine.harvest", "raise", 0.02, 11, {}),
        ("fleet.replica", "raise", 0.02, 2, {}),
        ("serve.admit", "raise", 0.08, 13, {}),
        ("feeder.assemble", "corrupt", 0.08, 7, {}),
        ("engine.step", "hang", 0.02, 18, {"dispatch_watchdog_s": 0.25}),
    ]
    results = []
    ok = True
    for i, (site, kind, rate, seed, extra) in enumerate(legs):
        from fira_tpu.robust import faults as faults_lib

        c = cfg.replace(inject_faults=f"{site}:{kind}:{rate}:{seed}",
                        **extra)
        # build the injector HERE so the smoke can read fired_keys after
        # the run (serve request tasks are single-row in split order, so
        # a feeder-site fire key IS the affected split position)
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_split(model, params, dataset, c, arrival_times=times,
                            out_dir=os.path.join(work, f"leg{i}"),
                            split="train", clock="virtual", guard=guard,
                            faults=inj)
            extra_compiles = guard.compiles_after_warmup()
        sv = m["serve"]
        got_lines = open(m["output_path"]).read().split("\n")
        fired = sum(m.get("faults", {}).values())
        accounted = (sv["completed"] + sv["shed_queue_full"]
                     + sv["shed_deadline"] + sv["shed_error"])
        if kind == "corrupt":
            # blast-radius contract: ONLY the corrupted positions may
            # differ from the no-fault bytes (per-row beam independence)
            corrupted = set(inj.fired_keys.get(site, []))
            bad = [f"non-corrupted position {pos} differs from no-fault"
                   for pos, (a, b) in enumerate(zip(ref_lines, got_lines))
                   if pos not in corrupted and a != b]
        else:
            bad = _check_degraded_bytes(ref_lines, got_lines,
                                        m["request_records"])
        replica_fault = site in ("engine.step", "engine.harvest",
                                 "fleet.replica")
        leg_ok = (fired > 0 and accounted == n and not bad
                  and extra_compiles == 0
                  and (not replica_fault or sv["replica_retirements"] >= 1)
                  and len(got_lines) == len(ref_lines))
        ok = ok and leg_ok
        results.append({
            "leg": f"{site}:{kind}", "rate": rate, "ok": leg_ok,
            "fired": fired, "completed": sv["completed"],
            "shed_error": sv["shed_error"],
            "retirements": sv["replica_retirements"],
            "requeued": sv["requeued_requests"],
            "retries": sv["request_retries"],
            "compiles_after_warmup": extra_compiles,
            **({"byte_violations": bad[:3]} if bad else {}),
        })
    # --- prefix-cache chaos legs (docs/DECODE_ENGINE.md "Prefix cache &
    # dedup"): faults at the cache.lookup site must degrade to MISSES —
    # never a wrong answer — and a replica retiring with shared (fan-out)
    # blocks in flight must requeue its followers and leak zero blocks.
    # Repeated traffic via a fixed request mix (request i serves sample
    # mix[i]); the byte reference is the cache-ON no-fault run, itself
    # checked byte-equal to cache-OFF.
    from fira_tpu.robust import faults as faults_lib

    mix = [i % 7 for i in range(48)]
    cache_times = poisson_times(len(mix), rate=1.5, seed=3)
    ccfg = cfg.replace(prefix_cache=True)
    m_off = serve_split(model, params, dataset, cfg,
                        arrival_times=cache_times,
                        out_dir=os.path.join(work, "cache_off"),
                        split="train", clock="virtual", request_mix=mix)
    m_ref = serve_split(model, params, dataset, ccfg,
                        arrival_times=cache_times,
                        out_dir=os.path.join(work, "cache_ref"),
                        split="train", clock="virtual", request_mix=mix)
    ref_cache_bytes = open(m_ref["output_path"], "rb").read()
    base_ok = (ref_cache_bytes == open(m_off["output_path"], "rb").read()
               and m_ref["engine"]["cache_hits"] > 0)
    ok = ok and base_ok
    results.append({"leg": "cache:baseline", "ok": base_ok,
                    "cache_hits": m_ref["engine"]["cache_hits"],
                    "dedup_coalesced": m_ref["serve"]["dedup_coalesced"]})

    for kind, seed in (("raise", 7), ("corrupt", 7)):
        c = ccfg.replace(inject_faults=f"cache.lookup:{kind}:0.5:{seed}")
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_split(model, params, dataset, c,
                            arrival_times=cache_times,
                            out_dir=os.path.join(work, f"cache_{kind}"),
                            split="train", clock="virtual", guard=guard,
                            faults=inj, request_mix=mix)
            extra_compiles = guard.compiles_after_warmup()
        fired = sum(m.get("faults", {}).values())
        e = m["serve"]
        # the whole contract in one line: a cache fault is a MISS —
        # bytes stay EXACTLY the no-fault bytes, nothing is shed, and a
        # corrupt read is caught by the checksum and the entry dropped
        leg_ok = (fired > 0 and extra_compiles == 0
                  and e["completed"] == len(mix)
                  and open(m["output_path"], "rb").read() == ref_cache_bytes
                  and (kind != "corrupt"
                       or m["engine"]["cache_integrity_drops"] > 0))
        ok = ok and leg_ok
        results.append({
            "leg": f"cache.lookup:{kind}", "ok": leg_ok, "fired": fired,
            "completed": e["completed"],
            "cache_hits": m["engine"]["cache_hits"],
            "integrity_drops": m["engine"]["cache_integrity_drops"],
            "compiles_after_warmup": extra_compiles,
        })

    # retirement with shared blocks in flight: a replica dies mid-decode
    # while seats serve coalesced fan-out groups; followers requeue with
    # their leaders onto the survivor, every request completes with the
    # no-fault bytes, and the pool accounting of EVERY replica (retired
    # included) returns to baseline — zero leaked blocks.
    from fira_tpu.data import buckets as buckets_lib
    from fira_tpu.parallel import fleet as fleet_lib

    burst_mix = [i % 13 for i in range(40)]
    burst_times = [0.0] * len(burst_mix)   # all in flight together
    m_ref2 = serve_split(model, params, dataset, ccfg,
                         arrival_times=burst_times,
                         out_dir=os.path.join(work, "retire_ref"),
                         split="train", clock="virtual",
                         request_mix=burst_mix)
    # rate/seed picked so ONE replica retires mid-burst on this fixed
    # schedule (survivor absorbs the requeue; the all-replicas-lost path
    # has its own legacy leg above)
    rcfg = ccfg.replace(inject_faults="engine.step:raise:0.06:11")
    inj = faults_lib.injector_from(rcfg)
    # LeakGuard armed around CONSTRUCTION (guards are captured when the
    # owner is built): every paged block the burst grants — retired
    # replica included — must check back in, and the guard must agree
    # with the allocator-invariant sweep below.
    with sanitizer.leak_guarding() as lg:
        fleet = fleet_lib.EngineFleet(model, params, rcfg, replicas=2,
                                      faults=inj)
        data = dataset.splits["train"]
        table = buckets_lib.decode_table(rcfg)
        fleet.prewarm(
            (buckets_lib.warmup_batch(data, rcfg, g,
                                      rcfg.test_batch_size),
             buckets_lib.geom_tag(g)) for g in table)
        m = serve_split(model, params, dataset, rcfg,
                        arrival_times=burst_times,
                        out_dir=os.path.join(work, "retire"),
                        split="train", clock="virtual", engine=fleet,
                        faults=inj, request_mix=burst_mix)
    lg_sum = lg.summary()
    sv = m["serve"]
    leaks = []
    if lg_sum["open"] or not lg_sum["acquires"]:
        leaks.append(f"leak guard: {lg_sum['open']} open of "
                     f"{lg_sum['acquires']} acquire(s)")
    for eng in fleet.engines:
        leaks += eng.allocator_invariants()
        if len(eng._free_blocks) != eng._pool_blocks or eng._block_refs:
            leaks.append(f"replica {eng.tag}: "
                         f"{eng._pool_blocks - len(eng._free_blocks)} "
                         f"block(s) never returned")
    followers_done = sum(1 for r in m["request_records"]
                         if r["coalesced_into"] is not None
                         and r["status"] == "done")
    leg_ok = (sv["replica_retirements"] >= 1
              and sv["requeued_requests"] > 0
              and sv["completed"] == len(burst_mix)
              and sv["dedup_coalesced"] > 0 and followers_done > 0
              and not leaks
              and (open(m["output_path"], "rb").read()
                   == open(m_ref2["output_path"], "rb").read()))
    ok = ok and leg_ok
    results.append({
        "leg": "cache:retire_shared_blocks", "ok": leg_ok,
        "retirements": sv["replica_retirements"],
        "requeued": sv["requeued_requests"],
        "dedup_coalesced": sv["dedup_coalesced"],
        "followers_completed": followers_done,
        "shared_block_peak": m["engine"]["shared_block_peak"],
        "leak_guard_acquires": lg_sum["acquires"],
        "leak_guard_open": lg_sum["open"],
        **({"block_leaks": leaks[:3]} if leaks else {}),
    })

    # --- raw-diff ingest legs (docs/INGEST.md): requests that arrive as
    # unified-diff TEXT ride the same poison-request quarantine — a
    # malformed diff and a faulted ingest.parse site must both shed with
    # a recorded reason while every unaffected request's bytes equal the
    # no-fault ingest run. (The ingest-vs-corpus byte equality itself is
    # the serve_bench --ingest-smoke leg; here the contract under test
    # is degradation.)
    from fira_tpu.data.schema import Corpus
    from fira_tpu.ingest.difftext import reconstruct_request
    from fira_tpu.ingest.service import serve_diffs

    corpus = Corpus.load(dataset.data_dir)
    ing_reqs = [reconstruct_request(corpus.record(int(i)))
                for i in dataset.split_indices["train"]]
    ing_times = poisson_times(len(ing_reqs), rate=0.5, seed=3)
    m_ing_ref = serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, cfg,
                            requests=ing_reqs, arrival_times=ing_times,
                            out_dir=os.path.join(work, "ingest_ref"),
                            clock="virtual")
    ref_ing_lines = open(m_ing_ref["output_path"]).read().split("\n")

    # malformed-diff leg: fixed positions replaced with garbage text —
    # DiffParseError rides the error channel into the quarantine
    bad_pos = {1, 7}
    broken = list(ing_reqs)
    for b in bad_pos:
        broken[b] = "this is not a unified diff\n"
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_diffs(model, params, dataset.word_vocab,
                        dataset.ast_change_vocab, cfg, requests=broken,
                        arrival_times=ing_times,
                        out_dir=os.path.join(work, "ingest_malformed"),
                        clock="virtual", guard=guard)
        extra_compiles = guard.compiles_after_warmup()
    got = open(m["output_path"]).read().split("\n")
    sv = m["serve"]
    shed_recs = {r["position"]: r for r in m["request_records"]
                 if r["status"] == "shed_error"}
    bad = [f"position {p} {why}" for p, why in
           [(p, "not shed") for p in bad_pos if p not in shed_recs]
           + [(p, "has no recorded parse error") for p in bad_pos
              if p in shed_recs
              and "DiffParseError" not in (shed_recs[p]["error"] or "")]
           + [(p, "line not empty") for p in bad_pos if got[p] != ""]]
    bad += [f"unaffected position {p} differs from no-fault"
            for p in range(len(ing_reqs))
            if p not in bad_pos and got[p] != ref_ing_lines[p]]
    leg_ok = (sv["shed_error"] == len(bad_pos)
              and sv["completed"] == len(ing_reqs) - len(bad_pos)
              and not bad and extra_compiles == 0)
    ok = ok and leg_ok
    results.append({"leg": "ingest:malformed", "ok": leg_ok,
                    "shed_error": sv["shed_error"],
                    "completed": sv["completed"],
                    "compiles_after_warmup": extra_compiles,
                    **({"violations": bad[:3]} if bad else {})})

    # ingest.parse fault legs: raise (quarantine sheds past the retry
    # budget, unaffected bytes equal) and corrupt (a scrambled payload
    # is a garbage REQUEST — served or shed, never a crash; blast
    # radius is exactly the corrupted positions)
    for kind, rate, seed_ in (("raise", 0.08, 7), ("corrupt", 0.08, 7)):
        c = cfg.replace(inject_faults=f"ingest.parse:{kind}:{rate}:{seed_}")
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, c,
                            requests=ing_reqs, arrival_times=ing_times,
                            out_dir=os.path.join(work, f"ingest_{kind}"),
                            clock="virtual", guard=guard, faults=inj)
            extra_compiles = guard.compiles_after_warmup()
        got = open(m["output_path"]).read().split("\n")
        sv = m["serve"]
        fired = sum(m.get("faults", {}).values())
        accounted = (sv["completed"] + sv["shed_queue_full"]
                     + sv["shed_deadline"] + sv["shed_error"])
        if kind == "corrupt":
            touched = set(inj.fired_keys.get("ingest.parse", []))
            bad = [f"non-corrupted position {p} differs from no-fault"
                   for p, (a, b) in enumerate(zip(ref_ing_lines, got))
                   if p not in touched and a != b]
        else:
            bad = _check_degraded_bytes(ref_ing_lines, got,
                                        m["request_records"])
        leg_ok = (fired > 0 and accounted == len(ing_reqs) and not bad
                  and extra_compiles == 0)
        ok = ok and leg_ok
        results.append({
            "leg": f"ingest.parse:{kind}", "ok": leg_ok, "fired": fired,
            "completed": sv["completed"], "shed_error": sv["shed_error"],
            "retries": sv["request_retries"],
            "compiles_after_warmup": extra_compiles,
            **({"byte_violations": bad[:3]} if bad else {}),
        })

    # ingest.cache fault legs (docs/INGEST.md "Fast path"): the
    # whole-diff result cache's failure contract under the armed guard,
    # on a REPEATED diff trace (repeats are what give the cache
    # something to fault on). raise => every lookup degrades to a MISS:
    # full re-ingest, output bytes EXACTLY the no-fault bytes, nothing
    # shed; corrupt => the scrambled read is caught by the entry's
    # content checksum, the entry dropped, the request re-ingested —
    # integrity drops metered, bytes unchanged. Never a wrong answer.
    rep_reqs = [ing_reqs[j % 7] for j in range(36)]
    rep_times = poisson_times(len(rep_reqs), rate=1.0, seed=3)
    m_rep_ref = serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, cfg,
                            requests=rep_reqs, arrival_times=rep_times,
                            out_dir=os.path.join(work, "icache_ref"),
                            clock="virtual")
    ref_rep_bytes = open(m_rep_ref["output_path"], "rb").read()
    base_hits = (m_rep_ref["serve"]["ingest"].get("cache") or {}).get(
        "hits", 0)
    base_ok = base_hits > 0
    ok = ok and base_ok
    results.append({"leg": "ingest.cache:baseline", "ok": base_ok,
                    "whole_diff_hits": base_hits})
    for kind, seed_ in (("raise", 7), ("corrupt", 7)):
        c = cfg.replace(inject_faults=f"ingest.cache:{kind}:0.5:{seed_}")
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, c,
                            requests=rep_reqs, arrival_times=rep_times,
                            out_dir=os.path.join(work, f"icache_{kind}"),
                            clock="virtual", guard=guard, faults=inj)
            extra_compiles = guard.compiles_after_warmup()
        fired = sum(m.get("faults", {}).values())
        sv = m["serve"]
        meter = sv["ingest"].get("cache") or {}
        leg_ok = (fired > 0 and extra_compiles == 0
                  and sv["completed"] == len(rep_reqs)
                  and sv["shed_error"] == 0
                  and open(m["output_path"], "rb").read() == ref_rep_bytes
                  and (kind != "raise" or meter.get("fault_misses", 0) > 0)
                  and (kind != "corrupt"
                       or meter.get("integrity_drops", 0) > 0))
        ok = ok and leg_ok
        results.append({
            "leg": f"ingest.cache:{kind}", "ok": leg_ok, "fired": fired,
            "completed": sv["completed"],
            "whole_diff_hits": meter.get("hits"),
            "fault_misses": meter.get("fault_misses"),
            "integrity_drops": meter.get("integrity_drops"),
            "compiles_after_warmup": extra_compiles,
        })

    # --- disaggregated-tier legs (serve/disagg.py; docs/SERVING.md
    # "Disaggregated tiers"): faults on the prefill-pool's transport and
    # worker processes must degrade — lost message => resubmit, corrupt
    # artifact => checksum-caught re-prefill, dead worker => retire +
    # requeue to survivors, ALL workers lost => recorded in-process
    # fallback — and in every case the output bytes stay EXACTLY the
    # no-fault drain bytes. Never a wrong answer, never a hang.
    ref_bytes = "\n".join(ref_lines).encode()
    disagg_legs = [
        # (leg name, workers, fault spec, contract key)
        ("disagg.transport:raise", 2,
         "disagg.transport:raise:0.3:7", "transport_msgs_lost"),
        ("disagg.transport:corrupt", 2,
         "disagg.transport:corrupt:0.3:7", "transport_integrity_drops"),
        ("disagg.worker:raise", 2,
         "disagg.worker:raise:0.12:5", "workers_lost"),
        ("disagg.worker:all-lost", 1,
         "disagg.worker:raise:0.6:7", "fallback"),
    ]
    for leg, n_workers, spec, meter_key in disagg_legs:
        c = ccfg.replace(serve_tiers="prefill-pool",
                         prefill_workers=n_workers, inject_faults=spec)
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_split(model, params, dataset, c,
                            arrival_times=times,
                            out_dir=os.path.join(work,
                                                 leg.replace(":", "_")),
                            split="train", clock="virtual", guard=guard,
                            faults=inj)
            extra_compiles = guard.compiles_after_warmup()
        sv = m["serve"]
        tiers = sv.get("tiers") or {}
        # worker faults fire inside the CHILD process (its own injector,
        # rebuilt from cfg), so the parent-side fired count stays 0 for
        # them — the observable contract meter IS the firing evidence.
        fired = sum(m.get("faults", {}).values()) + tiers.get(
            "workers_lost", 0)
        metered = tiers.get(meter_key, 0)
        leg_ok = (fired > 0 and bool(metered)
                  and sv["completed"] == n and extra_compiles == 0
                  and open(m["output_path"], "rb").read() == ref_bytes)
        ok = ok and leg_ok
        results.append({
            "leg": leg, "ok": leg_ok, "fired": fired,
            "completed": sv["completed"],
            "workers_lost": tiers.get("workers_lost"),
            "fallback": tiers.get("fallback"),
            "msgs_lost": tiers.get("transport_msgs_lost"),
            "integrity_drops": tiers.get("transport_integrity_drops"),
            "rows_resubmitted": tiers.get("rows_resubmitted"),
            "compiles_after_warmup": extra_compiles,
        })

    print(json.dumps({"smoke": "ok" if ok else "FAIL", "n_requests": n,
                      "legs": results}), flush=True)
    return 0 if ok else 1


# --------------------------------------------------------------------------
# self-healing legs (robust/recovery.py; docs/FAULTS.md "Recovery
# contracts")
# --------------------------------------------------------------------------

def _resume_setup(data_dir: str):
    """Deterministic model/params/config over an EXISTING corpus dir —
    shared by the kill-mid-serve parent and its subprocess child, so
    both sides hold bit-identical params (threefry init + the eos bias
    are pure functions of the seed)."""
    import numpy as np

    from fira_tpu.config import fira_tiny
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import init_state

    cfg = fira_tiny(batch_size=8, test_batch_size=6, decode_engine=True)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]
    sample = make_batch(split, np.arange(min(6, len(split))), cfg,
                        batch_size=6)
    model = FiraModel(cfg)
    params = eos_biased_params(init_state(model, cfg, sample).params,
                               delta=4.0)
    return dataset, cfg, model, params


def resume_child(data_dir: str, out_dir: str, rate: float) -> int:
    """The SIGKILL target: a wall-clock serve with the write-ahead
    journal armed. The parent polls the journal for progress, kills
    this process hard, then resumes from what survived."""
    from fira_tpu.serve import poisson_times, serve_split

    dataset, cfg, model, params = _resume_setup(data_dir)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=rate, seed=3)
    os.makedirs(out_dir, exist_ok=True)
    serve_split(model, params, dataset, cfg, arrival_times=times,
                out_dir=out_dir, split="train", clock="wall",
                journal_path=os.path.join(out_dir, "output_fira.journal"))
    return 0


def kill_and_resume(data_dir: str, out_dir: str, *, rate: float = 8.0,
                    min_done: int = 5, timeout_s: float = 120.0) -> dict:
    """Spawn the child serve, SIGKILL it once >= ``min_done`` requests
    hold terminal journal records (never a graceful shutdown), then
    resume in-process and compare the final bytes to an uninterrupted
    run. Returns the machine record the smoke/measure rows read."""
    import signal
    import subprocess

    from fira_tpu.robust import recovery as recovery_lib
    from fira_tpu.serve import poisson_times, serve_split

    jp = os.path.join(out_dir, "output_fira.journal")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    os.makedirs(out_dir, exist_ok=True)
    err_path = os.path.join(out_dir, "child_stderr.log")
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--resume-child",
             "--data-dir", data_dir, "--child-out", out_dir,
             "--child-rate", str(rate)],
            stdout=subprocess.DEVNULL, stderr=err_f, env=env)
        t0 = time.perf_counter()
        done_at_kill = 0
        killed = False
        while time.perf_counter() - t0 < timeout_s:
            if proc.poll() is not None:
                break   # finished before we could kill: resume still
                #         runs (a completed journal resumes to a pure
                #         re-emit)
            _meta, term = recovery_lib.read_journal(jp)
            done_at_kill = len(term)
            if done_at_kill >= min_done:
                proc.send_signal(signal.SIGKILL)
                killed = True
                break
            time.sleep(0.05)
        if proc.poll() is None and not killed:
            proc.send_signal(signal.SIGKILL)   # timeout backstop
            killed = True
        proc.wait()
    if not killed and (proc.returncode != 0 or not os.path.exists(jp)):
        # the child died on its own before serving anything: a FAIL
        # verdict with its stderr, never an opaque resume traceback
        tail = open(err_path).read()[-500:]
        return {"n": 0, "killed": False, "done_at_kill": 0, "resumed": 0,
                "re_served": 0, "resume_wall_s": 0.0,
                "bytes_identical": False,
                "child_rc": proc.returncode, "child_stderr": tail}

    dataset, cfg, model, params = _resume_setup(data_dir)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=rate, seed=3)
    ref_dir = os.path.join(out_dir, "ref")
    ref = serve_split(model, params, dataset, cfg, arrival_times=times,
                      out_dir=ref_dir, split="train", clock="virtual")
    t1 = time.perf_counter()
    m = serve_split(model, params, dataset, cfg, arrival_times=times,
                    out_dir=out_dir, split="train", clock="virtual",
                    journal_path=jp, resume=True)
    resume_wall = time.perf_counter() - t1
    ref_bytes = open(ref["output_path"], "rb").read()
    got = open(m["output_path"], "rb").read()
    return {"n": n, "killed": killed, "done_at_kill": done_at_kill,
            "resumed": m["serve"]["resumed"],
            "re_served": m["serve"]["completed"] + m["serve"]["shed_error"]
            + m["serve"]["shed_deadline"] + m["serve"]["shed_queue_full"],
            "resume_wall_s": round(resume_wall, 3),
            "bytes_identical": got == ref_bytes}


def recovery_smoke() -> int:
    """The recovery contracts, machine-checked (the check.sh leg):
    respawn byte-identity, warm-spare attach at zero mid-run compiles,
    respawn-storm exhaustion, SIGKILL + resume."""
    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.robust import faults as faults_lib
    from fira_tpu.serve import poisson_times, serve_split

    dataset, cfg, model, params = _setup(
        40, batch=6, slots=6, replicas=2, buckets=((16, 400, 12),),
        dispatch_watchdog_s=0.0, robust_retries=1, fault_hang_s=1.0)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)
    work = tempfile.mkdtemp(prefix="fira_recovery_smoke_")
    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "drain"), split="train")
    ref_bytes = open(drain["output_path"], "rb").read()

    results = []
    ok = True
    # (a) respawn byte-identity + (b) warm-spare attach: same seeded
    # replica fault, once rebuilt mid-run and once spare-attached — both
    # must end with a replacement SERVING, all requests done, bytes
    # identical to the no-fault run, zero post-warmup compiles (a fresh
    # replacement's prewarm compiles are its own labels' warmup)
    for leg, spares in (("respawn:rebuild", 0), ("respawn:spare", 1)):
        c = cfg.replace(inject_faults="engine.step:raise:0.02:18",
                        max_respawns=3, engine_spares=spares,
                        respawn_backoff_s=0.05)
        inj = faults_lib.injector_from(c)
        with sanitizer.sanitize(nans=False, infs=False) as guard:
            m = serve_split(model, params, dataset, c, arrival_times=times,
                            out_dir=os.path.join(work, leg.replace(":", "_")),
                            split="train", clock="virtual", guard=guard,
                            faults=inj)
            extra = guard.compiles_after_warmup()
        sv = m["serve"]
        got = open(m["output_path"], "rb").read()
        leg_ok = (sv["replica_retirements"] >= 1 and sv["respawns"] >= 1
                  and sv["completed"] == n and got == ref_bytes
                  and extra == 0
                  and (spares == 0 or sv["spare_attaches"] >= 1))
        ok = ok and leg_ok
        results.append({
            "leg": leg, "ok": leg_ok,
            "retirements": sv["replica_retirements"],
            "respawns": sv["respawns"],
            "respawned": sv["respawned_replicas"],
            "spare_attaches": sv["spare_attaches"],
            "completed": sv["completed"],
            "bytes_identical": got == ref_bytes,
            "compiles_after_warmup": extra,
            "alive_trace_len": len(sv["replicas_alive_over_time"]),
        })

    # (c) respawn storm: the fault re-fires on every replacement until
    # max_respawns exhausts per lineage — then the run degrades exactly
    # like PR 9 (recorded sheds, position-complete file, no hang) and
    # every completed position still matches the no-fault bytes
    c = cfg.replace(inject_faults="engine.step:raise:0.5:5",
                    max_respawns=1, respawn_backoff_s=0.05)
    inj = faults_lib.injector_from(c)
    m = serve_split(model, params, dataset, c, arrival_times=times,
                    out_dir=os.path.join(work, "storm"), split="train",
                    clock="virtual", faults=inj)
    sv = m["serve"]
    got_lines = open(m["output_path"]).read().split("\n")
    ref_lines = ref_bytes.decode().split("\n")
    accounted = (sv["completed"] + sv["shed_queue_full"]
                 + sv["shed_deadline"] + sv["shed_error"])
    bad = _check_degraded_bytes(ref_lines, got_lines, m["request_records"])
    leg_ok = (sv["respawns"] >= 1 and sv["replica_retirements"] >= 2
              and accounted == n and sv["shed_error"] > 0 and not bad
              and len(got_lines) == len(ref_lines))
    ok = ok and leg_ok
    results.append({
        "leg": "respawn:storm", "ok": leg_ok,
        "retirements": sv["replica_retirements"],
        "respawns": sv["respawns"], "completed": sv["completed"],
        "shed_error": sv["shed_error"],
        **({"byte_violations": bad[:3]} if bad else {}),
    })

    # (d) SIGKILL mid-serve + journal resume: bytes identical to an
    # uninterrupted run — exactly-once output, machine-checked
    kr = kill_and_resume(dataset.data_dir,
                         os.path.join(work, "kill_resume"))
    leg_ok = kr["bytes_identical"] and kr["killed"]
    ok = ok and leg_ok
    results.append({"leg": "kill:resume", "ok": leg_ok, **kr})

    print(json.dumps({"recovery_smoke": "ok" if ok else "FAIL",
                      "n_requests": n, "legs": results}), flush=True)
    return 0 if ok else 1


def measure_recovery(out_path: str):
    """The committed recovery rows (docs/CHAOS_BENCH_r02.jsonl):
    capacity-restored-over-time under a seeded replica fault with
    respawn armed, and the write-ahead journal / resume overhead."""
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.robust import faults as faults_lib
    from fira_tpu.serve import poisson_times, serve_split

    n_commits = int(os.environ.get("FIRA_CHAOS_COMMITS", "240"))
    seed = int(os.environ.get("FIRA_CHAOS_SEED", "11"))
    offered = float(os.environ.get("FIRA_CHAOS_OFFERED_RPS", "150"))
    dataset, cfg, model, params = _setup(
        n_commits, batch=6, slots=8, replicas=2,
        dispatch_watchdog_s=0.0, robust_retries=1)
    n = len(dataset.splits["train"])
    work = tempfile.mkdtemp(prefix="fira_recovery_out_")
    times = poisson_times(n, offered, seed=seed)
    rows = []

    # warm pass (first-use costs off the timed rows)
    serve_split(model, params, dataset, cfg,
                arrival_times=poisson_times(min(n, 24), offered, seed=seed),
                out_dir=os.path.join(work, "warm"), split="train",
                clock="wall")

    # --- capacity restored over time: same seeded replica fault, PR-9
    # degrade vs respawn — the alive trace is the control signal
    for mode, respawns, spares in (("degrade", 0, 0), ("respawn", 3, 0),
                                   ("spare", 3, 1)):
        # rate/seed chosen so the fault FIRES early on this schedule
        # (engine.step keys by a per-site dispatch counter; 0.02:11
        # first fires at dispatches 3/74/102 — inside any serve run).
        # The spare policy is the wall-clock story: a mid-run rebuild
        # stalls the scheduler thread for the build+prewarm, a warm
        # spare attaches in O(1)
        c = cfg.replace(inject_faults=f"engine.step:raise:0.02:{seed}",
                        max_respawns=respawns, engine_spares=spares,
                        respawn_backoff_s=0.05)
        inj = faults_lib.injector_from(c)
        t0 = time.perf_counter()
        m = serve_split(model, params, dataset, c, arrival_times=times,
                        out_dir=os.path.join(work, f"cap_{mode}"),
                        split="train", clock="wall", faults=inj)
        wall = time.perf_counter() - t0
        sv = m["serve"]
        trace = sv["replicas_alive_over_time"]
        restore_rounds = []
        down_round = None
        for prev, e in zip(trace, trace[1:]):
            if e["alive"] < prev["alive"] and down_round is None:
                down_round = e["round"]
            elif e["alive"] > prev["alive"] and down_round is not None:
                restore_rounds.append(e["round"] - down_round)
                down_round = None
        rows.append({
            "mode": "recovery_capacity", "policy": mode,
            "offered_rps": offered, "n_requests": n,
            "wall_s": round(wall, 3),
            "throughput_rps": sv["throughput_rps"],
            "completed": sv["completed"], "shed_error": sv["shed_error"],
            "retirements": sv["replica_retirements"],
            "respawns": sv["respawns"],
            "spare_attaches": sv["spare_attaches"],
            "mean_restore_rounds": (round(sum(restore_rounds)
                                          / len(restore_rounds), 2)
                                    if restore_rounds else None),
            "replicas_alive_over_time": trace[:50],
            "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
            "host": "cpu-tiny: one physical core serves every replica, "
                    "so restored capacity adds scheduling contention, "
                    "not throughput — the capacity signal here is "
                    "mean_restore_rounds + the alive trace; restored "
                    "capacity = restored throughput needs per-replica "
                    "chips (real-accelerator geometry)",
        })

    # --- resume overhead: (a) the journal's fsync cost on an unfaulted
    # serve, (b) a real SIGKILL + resume (subprocess)
    t0 = time.perf_counter()
    serve_split(model, params, dataset, cfg, arrival_times=times,
                out_dir=os.path.join(work, "nojournal"), split="train",
                clock="wall")
    wall_off = time.perf_counter() - t0
    t0 = time.perf_counter()
    serve_split(model, params, dataset, cfg, arrival_times=times,
                out_dir=os.path.join(work, "journal"), split="train",
                clock="wall",
                journal_path=os.path.join(work, "journal",
                                          "output_fira.journal"))
    wall_on = time.perf_counter() - t0
    kill_dir = os.path.join(work, "kill")
    kdata = tempfile.mkdtemp(prefix="fira_resume_corpus_")
    write_corpus_dir(kdata, n_commits=40, seed=13)
    kr = kill_and_resume(kdata, kill_dir)
    rows.append({
        "mode": "resume_overhead", "n_requests": n,
        "offered_rps": offered,
        "journal_off_wall_s": round(wall_off, 3),
        "journal_on_wall_s": round(wall_on, 3),
        "journal_overhead_frac": round(wall_on / wall_off - 1.0, 4)
        if wall_off else None,
        "kill_resume": kr,
        "host": "cpu-tiny; fsync cost is rig-dependent — the FRACTION "
                "is the artifact",
    })

    stamp = {"generated_by": "scripts/chaos_bench.py (recovery rows)",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp) + "\n")
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return rows


def measure(out_path: str):
    """Throughput / shed-rate / retirement rows under injected fault
    rates: the committed chaos record (docs/CHAOS_BENCH_r01.jsonl)."""
    from fira_tpu.serve import poisson_times, serve_split

    n_commits = int(os.environ.get("FIRA_CHAOS_COMMITS", "240"))
    batch = int(os.environ.get("FIRA_CHAOS_BATCH", "6"))
    slots = int(os.environ.get("FIRA_CHAOS_SLOTS", "8"))
    seed = int(os.environ.get("FIRA_CHAOS_SEED", "11"))
    rates = [float(r) for r in os.environ.get(
        "FIRA_CHAOS_RATES", "0.0,0.05,0.2").split(",")]

    dataset, cfg, model, params = _setup(
        n_commits, batch=batch, slots=slots, replicas=2,
        dispatch_watchdog_s=0.0, robust_retries=1)
    n = len(dataset.splits["train"])
    work = tempfile.mkdtemp(prefix="fira_chaos_out_")
    # wall clock at a rate the serve path sustains; the interesting
    # numbers are the DELTAS across fault rates, not the absolutes
    offered = float(os.environ.get("FIRA_CHAOS_OFFERED_RPS", "150"))
    times = poisson_times(n, offered, seed=seed)

    # one untimed warm pass: first-use costs (text-cooking/BLEU imports,
    # the serve path's own first touches) off the timed rows — the 0.0
    # baseline row must not carry them or the fault-rate deltas invert
    serve_split(model, params, dataset, cfg,
                arrival_times=poisson_times(min(n, 24), offered, seed=seed),
                out_dir=os.path.join(work, "warm"), split="train",
                clock="wall")

    scenarios = [("feeder.assemble", "raise"), ("engine.step", "raise")]
    rows = []
    for site, kind in scenarios:
        for rate in rates:
            c = (cfg if rate == 0.0 else
                 cfg.replace(inject_faults=f"{site}:{kind}:{rate}:{seed}"))
            t0 = time.perf_counter()
            m = serve_split(model, params, dataset, c, arrival_times=times,
                            out_dir=os.path.join(
                                work, f"{site.replace('.', '_')}_{rate}"),
                            split="train", clock="wall")
            wall = time.perf_counter() - t0
            sv = m["serve"]
            rows.append({
                "mode": "chaos_rate", "site": site, "kind": kind,
                "rate": rate, "offered_rps": offered,
                "n_requests": n, "wall_s": round(wall, 3),
                "throughput_rps": sv["throughput_rps"],
                "completed": sv["completed"],
                "shed_error": sv["shed_error"],
                "shed_frac": round(sv["shed_error"] / n, 4),
                "retirements": sv["replica_retirements"],
                "requeued": sv["requeued_requests"],
                "retries": sv["request_retries"],
                "fired": sum(m.get("faults", {}).values()),
                "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
                "host": "cpu-tiny (fira_tiny geometry; deltas across "
                        "fault rates are the artifact, not absolutes)",
            })

    stamp = {"generated_by": "scripts/chaos_bench.py",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp) + "\n")
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seeded fault at each site, contract-checked "
                         "(scripts/check.sh tier-1 leg)")
    ap.add_argument("--recovery-smoke", action="store_true",
                    help="self-healing contracts: respawn byte-identity, "
                         "spare attach, respawn-storm exhaustion, "
                         "SIGKILL+resume (scripts/check.sh recovery leg)")
    ap.add_argument("--resume-child", action="store_true",
                    help="internal: the kill-mid-serve subprocess")
    ap.add_argument("--data-dir", default=None,
                    help="--resume-child: corpus dir shared with parent")
    ap.add_argument("--child-out", default=None,
                    help="--resume-child: serve output dir")
    ap.add_argument("--child-rate", type=float, default=8.0,
                    help="--resume-child: offered rate (rps)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help=f"JSONL record path (default {DEFAULT_OUT})")
    ap.add_argument("--out2", default=DEFAULT_OUT2,
                    help=f"recovery-rows JSONL record path "
                         f"(default {DEFAULT_OUT2})")
    args = ap.parse_args()

    from fira_tpu.utils.startup import force_cpu_backend

    force_cpu_backend()
    if args.resume_child:
        return resume_child(args.data_dir, args.child_out, args.child_rate)
    if args.smoke:
        return smoke()
    if args.recovery_smoke:
        return recovery_smoke()
    rows = measure(args.out)
    rows += measure_recovery(args.out2)
    print(json.dumps({"rows": rows, "out": [args.out, args.out2]}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
