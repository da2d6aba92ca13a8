"""Open-loop serving bench on the slot engine -> docs/SERVE_BENCH_r01.jsonl.

The drain benches (tpu_decode_bench.py, bench.py's decode-engine leg)
measure commits/s on a pre-packed stream the engine empties as fast as it
can — a throughput number with no latency story. This bench runs the
serving loop (fira_tpu/serve — docs/SERVING.md) the way serving systems
are actually evaluated (Orca OSDI'22 §6, vLLM SOSP'23 §6): an OPEN-loop
Poisson arrival schedule at a swept offered rate, wall-clock latency
per request, p50/p99 TTFT and end-to-end reported per rate. Because the
generator never waits for the server, rates past capacity make the
admission queue grow without bound and the tail latencies record it —
the SATURATION KNEE the drain bench cannot see.

Legs (every row is one JSON line in the record):

- ``rate_sweep`` — offered rates as fractions of the measured drain
  capacity (same engine, same stream, closed loop): below the knee
  throughput tracks offered rate and p99 e2e stays near service time;
  past it throughput pins at capacity and p99 grows with the run length.
- ``prefill_budget_ab`` — the latency-aware refill A/B, below and above
  the serve knee: ``serve_prefill_budget`` 1 (one prefill between step
  dispatches — seated requests pay at most one admission stall per
  step) vs a deep budget (admission throughput first). Below the knee
  the deep budget's per-admission stall shows up in the tail; at
  saturation its higher occupancy shows up as throughput — the two
  halves of the trade the knob exists for.

Absolute numbers are CPU proxies at the fira-tiny geometry (quiet-machine
caveats in docs/PERF.md apply); the SHAPE — knee location in units of
drain capacity, budget trade direction — is the artifact.

Modes:
  (default)   sweep + A/B, write --out (docs/SERVE_BENCH_r01.jsonl),
              echo a final JSON summary line.
  --smoke     fixed-trace virtual-clock replay under the armed compile
              guard for scripts/check.sh: serve-mode output bytes must
              equal drain mode's and the declared engine program family
              must show zero post-warmup compiles. Exit nonzero on any
              violation.
  --cache     the REPEATED-TRAFFIC leg (docs/CACHE_BENCH_r01.jsonl):
              seeded repeat-rate / Zipf request mixes over the trace
              generator, served at the knee rate with the prefix cache +
              in-flight dedup (docs/DECODE_ENGINE.md "Prefix cache &
              dedup") ON vs OFF at repeat rates {0, 0.3, 0.6} — hit
              rate, prefill-dispatches-saved, dedup fan-out, and
              throughput/p50/p99 per row, with on-vs-off output bytes
              asserted identical per mix.
  --cache-smoke
              fixed duplicate-heavy trace, virtual clock, armed compile
              guard: cache-on bytes == cache-off bytes with real hits +
              coalescing and zero post-warmup compiles (the check.sh
              leg). Exit nonzero on any violation.
  --ingest    the RAW-DIFF leg (docs/INGEST_BENCH_r02.jsonl): serve a
              trace of reconstructed unified diffs through the online
              ingest pipeline (fira_tpu/ingest — per-request diff parse
              + Java lexing + AST extraction + encode on the feeder
              workers) at swept offered rates, next to the corpus-graph
              path at the same rates: per-stage ingest latency, the
              ingest-stall fraction (the feed-stall twin), and the
              single-worker ingest rate vs the offline preprocessing
              baseline (docs/PERF.md § Preprocessing, 1,815
              commits/sec/core). Round-14 grew three fast-path legs
              (docs/INGEST.md "Fast path"): an ingest-WORKER-COUNT
              sweep (1/2/4, thread AND process parse-stage exec) on a
              cold repeat-0 trace — the machine-recorded scaling curve
              — and seeded repeat-rate mixes (PR-10's Zipf _repeat_mix
              on diff traces) served with the whole-diff result cache
              ON vs OFF (bytes asserted identical per mix) plus one
              composed row with the PR-10 prefill cache stacked on top.
  --ingest-smoke
              fixed reconstructed-diff trace, virtual clock, armed
              compile guard: ingest-path output bytes == corpus-path
              bytes with every request completed + stamped and zero
              post-warmup compiles (the check.sh leg). Exit nonzero on
              any violation.
  --ingest-cache-smoke
              duplicate-heavy reconstructed-diff trace, virtual clock,
              armed compile guard: ingest-cache-ON output bytes ==
              cache-OFF bytes with REAL whole-diff hits and hunk-memo
              partial hits recorded, and zero post-warmup retraces (the
              check.sh leg of the ingest fast-path bit-exactness
              contract). Exit nonzero on any violation.
  --spec-smoke
              speculative draft-and-verify leg (docs/DECODE_ENGINE.md
              "Speculative drafting"): a spec-armed serve (draft tier,
              k=4) under the armed compile guard must produce bytes
              identical to the plain spec-off drain with REAL
              acceptances metered and zero post-warmup compiles, and a
              seeded engine.step fault on a 2-replica spec-armed fleet
              must still retire/requeue byte-identically (the check.sh
              leg of the speculative-decode equivalence contract). Exit
              nonzero on any violation.
  --quant     the LOW-PRECISION-TIER leg (docs/QUANT_BENCH_r01.jsonl;
              docs/DECODE_ENGINE.md "Low-precision tiers"): the equal-
              HBM slot sweep (the f32 pool at full residency vs the bf16 pool at
              4x the slots against the same pool bytes — the
              paged_equal_hbm_slot_gain row records the machine-
              measured >= 4.0), per-tier serve rows (rps + p50/p99 e2e
              at the knee rate, stats-stamped kv_dtype /
              serve_precision), and the measured-quality rows on the
              frozen split (bleu_delta_vs_f32 +
              logprob_divergence_{mean,p99} per tier, |BLEU delta| <=
              0.5 asserted in-bench). Exit nonzero on any violation.
  --quant-smoke
              the same tiny stream served f32 / bf16-KV / int8w under
              the armed compile guard: per-tier byte-stability across
              repeat runs, f32 == plain drain bytes, stats tier stamps,
              measured BLEU-delta bound, bf16 halves kv_bytes_per_slot,
              zero post-warmup retraces (the check.sh leg). Exit
              nonzero on any violation.
  --disagg    the TIER-SPLIT leg (docs/DISAGG_BENCH_r01.jsonl;
              docs/SERVING.md "Disaggregated tiers"): in-process vs
              prefill-pool serving on the same prefill-heavy
              all-distinct trace at swept virtual-clock rates —
              per-mode throughput/latency rows with wall-clock
              prefill-tier utilization, a saturation A/B (disagg rps
              must beat in-process at the top rate), and per-tier knee
              rows machine-naming the first tier to saturate. Byte
              identity asserted per rate; exit nonzero on violation.
  --disagg-smoke
              prefill-pool serve bytes == plain drain bytes with every
              artifact delivered over the pipe/SHM transport, ZERO
              decode-tier prefill dispatches, no fallback, and zero
              post-warmup compiles on the decode tier (the check.sh
              leg). Exit nonzero on any violation.

Env knobs: FIRA_SERVE_COMMITS (synthetic corpus size, default 600),
FIRA_SERVE_RATE_FRACS (default "0.25,0.5,0.8,1.2,1.6" x drain capacity),
FIRA_SERVE_AB_FRACS (default "0.4,0.9" — below and above the serve
knee), FIRA_SERVE_SLOTS (default 16),
FIRA_SERVE_BATCH (default 8), FIRA_SERVE_EOS_DELTA (default 4.0 — the
mixed-settle bias of the engine benches), FIRA_SERVE_SEED (default 7).
Cache leg: FIRA_CACHE_REPEATS (default "0,0.3,0.6"),
FIRA_CACHE_REQUESTS (request count, default 400), FIRA_CACHE_RATE_FRACS
(offered rates as fractions of drain capacity, default "0.5,0.8" — the
measured SERVE_BENCH_r01 knee plus the off-arm saturation edge where
reuse pays), FIRA_CACHE_ENTRIES (LRU capacity, default 256).
Ingest leg: FIRA_INGEST_COMMITS (default 300), FIRA_INGEST_RATE_FRACS
(default "0.5,0.8"), FIRA_INGEST_WORKERS (worker-sweep counts, default
"1,2,4"), FIRA_INGEST_EXEC_MODES (parse-stage exec modes swept, default
"thread,process"), FIRA_INGEST_REPEATS (repeat-mix rates, default
"0.6").
Disagg leg: FIRA_DISAGG_COMMITS (default 48), FIRA_DISAGG_RATES
(virtual-clock offered rps swept, default "0.5,2.0,8.0"),
FIRA_DISAGG_WORKERS (default 2).
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

DEFAULT_OUT = os.path.join(REPO_ROOT, "docs", "SERVE_BENCH_r01.jsonl")
DEFAULT_CACHE_OUT = os.path.join(REPO_ROOT, "docs", "CACHE_BENCH_r01.jsonl")
DEFAULT_INGEST_OUT = os.path.join(REPO_ROOT, "docs",
                                  "INGEST_BENCH_r02.jsonl")
DEFAULT_QUANT_OUT = os.path.join(REPO_ROOT, "docs", "QUANT_BENCH_r01.jsonl")
DEFAULT_DISAGG_OUT = os.path.join(REPO_ROOT, "docs",
                                  "DISAGG_BENCH_r01.jsonl")

# the offline preprocessing baseline the online ingest rate is compared
# against (docs/PERF.md § Preprocessing: host-side shard workers over
# the full corpus, commits/sec/core)
OFFLINE_PREPROCESS_RPS_PER_CORE = 1815.0


def _repeat_mix(n: int, repeat: float, n_distinct: int, seed: int):
    """Seeded request mix: with probability ``repeat`` a request repeats
    an already-seen sample drawn Zipf-style (rank-1/r popularity over
    first-seen order — the monorepo-bot/CI-retry shape: a few hot diffs
    dominate the repeats), else it is the next fresh sample. repeat=0 is
    the identity-ish mix (all distinct while the corpus lasts)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    mix = np.empty(n, dtype=np.int64)
    seen = []
    fresh = 0
    for i in range(n):
        if seen and rng.random() < repeat:
            ranks = np.arange(1, len(seen) + 1, dtype=np.float64)
            w = 1.0 / ranks
            mix[i] = seen[int(rng.choice(len(seen), p=w / w.sum()))]
        else:
            mix[i] = fresh % n_distinct
            if fresh < n_distinct:
                seen.append(int(mix[i]))
            fresh += 1
    return mix


def _setup(n_commits: int, *, batch: int, slots: int, eos_delta: float,
           buckets=(), extracted: bool = False):
    """Synthetic corpus + tiny engine config + EOS-biased params (mixed
    settle depths — the schedule the refill loop exists for).
    ``extracted``: build the corpus with
    data.synthetic.write_extracted_corpus_dir (graph streams from the
    REAL FSM + astdiff extraction — the round-trip corpus the ingest
    legs need) instead of the random-graph writer."""
    import numpy as np

    from fira_tpu.config import fira_tiny
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import (write_corpus_dir,
                                         write_extracted_corpus_dir)
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import init_state

    data_dir = tempfile.mkdtemp(prefix="fira_serve_bench_")
    writer = write_extracted_corpus_dir if extracted else write_corpus_dir
    corpus = writer(data_dir, n_commits, seed=13)
    cfg = fira_tiny(batch_size=8, test_batch_size=batch,
                    decode_engine=True, engine_slots=slots,
                    buckets=buckets)
    dataset = FiraDataset(data_dir, cfg)
    cfg = dataset.cfg
    split = dataset.splits["train"]  # the big synthetic split
    sample = make_batch(split, np.arange(min(batch, len(split))), cfg,
                        batch_size=batch)
    model = FiraModel(cfg)
    params = eos_biased_params(init_state(model, cfg, sample).params,
                               delta=eos_delta)
    return dataset, corpus, cfg, model, params


def _serve_row(model, params, dataset, cfg, times, out_dir, **kw):
    from fira_tpu.serve import serve_split

    t0 = time.perf_counter()
    metrics = serve_split(model, params, dataset, cfg, arrival_times=times,
                          out_dir=out_dir, split="train", **kw)
    sv = metrics["serve"]
    sv["wall_s"] = round(time.perf_counter() - t0, 3)
    sv["slot_occupancy"] = metrics["engine"]["slot_occupancy"]
    sv["harvest_reads"] = metrics["engine"]["harvest_reads"]
    return sv, metrics


def measure(out_path: str) -> int:
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks
    from fira_tpu.serve import poisson_times

    n_commits = int(os.environ.get("FIRA_SERVE_COMMITS", "600"))
    batch = int(os.environ.get("FIRA_SERVE_BATCH", "8"))
    slots = int(os.environ.get("FIRA_SERVE_SLOTS", "16"))
    eos_delta = float(os.environ.get("FIRA_SERVE_EOS_DELTA", "4.0"))
    seed = int(os.environ.get("FIRA_SERVE_SEED", "7"))
    fracs = [float(f) for f in os.environ.get(
        "FIRA_SERVE_RATE_FRACS", "0.25,0.5,0.8,1.2,1.6").split(",")]
    ab_fracs = [float(f) for f in os.environ.get(
        "FIRA_SERVE_AB_FRACS", "0.4,0.9").split(",")]

    dataset, _corpus, cfg, model, params = _setup(
        n_commits, batch=batch, slots=slots, eos_delta=eos_delta)
    data = dataset.splits["train"]
    n = len(data)
    work = tempfile.mkdtemp(prefix="fira_serve_out_")

    # --- drain capacity: the closed-loop ceiling the sweep is scaled
    # by. Warm drain first (SlotEngine jits per INSTANCE, so the warm
    # pass must run on the SAME engine the timed pass uses — the
    # tpu_decode_bench warm-then-measure discipline), stats reset, then
    # a timed second drain of the same stream.
    from fira_tpu.decode import engine as engine_lib

    eng = engine_lib.SlotEngine(model, params, cfg)

    def drain_once():
        tasks, _ = _decode_tasks(data, cfg)
        with Feeder(tasks, num_workers=cfg.feeder_workers,
                    depth=cfg.feeder_depth) as feed:
            for _ in eng.run(feed):
                pass

    drain_once()                     # compiles prefill/step/insert/harvest
    eng.stats = engine_lib.EngineStats(slots=eng.slots)
    t0 = time.perf_counter()
    drain_once()
    drain_s = time.perf_counter() - t0
    drain_rps = eng.stats.commits / drain_s
    rows = [{
        "mode": "drain_capacity", "commits": eng.stats.commits,
        "wall_s": round(drain_s, 3), "drain_rps": round(drain_rps, 3),
        "slots": slots, "batch": batch, "n_requests": n,
        "eos_delta": eos_delta, "seed": seed,
        "host": "cpu-tiny (fira_tiny geometry; shapes are the artifact, "
                "not absolute numbers)",
    }]

    # One untimed serve warm pass (short stream at the drain rate):
    # first-use costs off the timed rows — text-cooking/BLEU imports and
    # the serve path's own first touches cost ~seconds on first use,
    # which would otherwise land entirely in the first swept rate's
    # latency percentiles (measured: a 2.5 s first-run stall regardless
    # of which rate runs first).
    _serve_row(model, params, dataset, cfg,
               poisson_times(min(n, 4 * batch), drain_rps, seed=seed),
               os.path.join(work, "warm"), engine=eng)

    # --- rate sweep: offered rate as a fraction of drain capacity. The
    # WARM engine is reused across runs (serve_split ``engine=``) with a
    # stats reset per run, so the latency rows measure serving — not the
    # per-run cold compiles a fresh engine would pay while the whole
    # arrival schedule piles into the queue.
    for frac in fracs:
        rate = frac * drain_rps
        times = poisson_times(n, rate, seed=seed)
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        sv, _ = _serve_row(model, params, dataset, cfg, times,
                           os.path.join(work, f"r{frac}"), engine=eng)
        rows.append({"mode": "rate_sweep", "rate_frac": round(frac, 3),
                     "offered_rps": round(rate, 3), **sv})

    # --- prefill-budget A/B, below AND above the serve knee: budget 1
    # (bounded per-step admission stall) vs a deep budget (admission
    # throughput). Below the knee the seated requests' per-admission
    # stall is the visible cost of a deep budget; at saturation the
    # deep budget's higher occupancy is the visible win — both halves
    # of the trade the knob exists for. The deep-budget run needs a
    # deeper staging policy (wants_input clips at engine_prefill_depth,
    # an engine-side knob), so it gets its own engine, warmed by one
    # untimed drain.
    deep = max(2, slots // batch * 2)
    engines = {1: eng}
    for budget in (deep,):
        c = cfg.replace(engine_prefill_depth=max(cfg.engine_prefill_depth,
                                                 budget))
        ab_eng = engine_lib.SlotEngine(model, params, c)
        tasks, _ = _decode_tasks(data, c)
        with Feeder(tasks, num_workers=c.feeder_workers,
                    depth=c.feeder_depth) as feed:
            for _ in ab_eng.run(feed):   # untimed warm drain
                pass
        engines[budget] = ab_eng
    for ab_frac in ab_fracs:
        ab_rate = ab_frac * drain_rps
        ab_times = poisson_times(n, ab_rate, seed=seed)
        for budget in (1, deep):
            c = cfg.replace(serve_prefill_budget=budget,
                            engine_prefill_depth=max(
                                cfg.engine_prefill_depth, budget))
            ab_eng = engines[budget]
            ab_eng.stats = engine_lib.EngineStats(slots=ab_eng.slots)
            sv, _ = _serve_row(model, params, dataset, c, ab_times,
                               os.path.join(work, f"ab{ab_frac}_{budget}"),
                               engine=ab_eng)
            rows.append({"mode": "prefill_budget_ab",
                         "serve_prefill_budget": budget,
                         "rate_frac": round(ab_frac, 3),
                         "offered_rps": round(ab_rate, 3), **sv})

    # --- knee: the largest offered rate the server still answers at ~the
    # offered rate (completed throughput >= 90% of offered). Past it the
    # open-loop queue grows without bound and p99 e2e scales with run
    # length instead of service time.
    sweep = [r for r in rows if r["mode"] == "rate_sweep"]
    under = [r for r in sweep
             if r["throughput_rps"] and r["offered_rps"]
             and r["throughput_rps"] >= 0.9 * r["offered_rps"]]
    knee = {
        "mode": "knee",
        "drain_rps": round(drain_rps, 3),
        "knee_offered_rps": max((r["offered_rps"] for r in under),
                                default=None),
        "knee_rate_frac": max((r["rate_frac"] for r in under),
                              default=None),
        "note": "largest swept offered rate with completed throughput >= "
                "0.9x offered; p99 e2e above the knee is run-length-bound "
                "(open-loop queue growth), not service-time-bound",
    }
    rows.append(knee)

    stamp = {"generated_by": "scripts/serve_bench.py",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp) + "\n")
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(json.dumps({"rows": rows, "out": out_path}), flush=True)
    return 0


def cache_measure(out_path: str) -> int:
    """The repeated-traffic leg: serve seeded repeat-rate/Zipf mixes at
    the knee rate, prefix cache + dedup ON vs OFF per repeat rate, and
    record hit rate / prefill-dispatches-saved / dedup fan-out /
    throughput / p50-p99 — asserting on-vs-off output bytes identical
    per mix (the bit-exactness contract, machine-checked in the bench
    itself). Commits docs/CACHE_BENCH_r01.jsonl."""
    import dataclasses

    import numpy as np

    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks
    from fira_tpu.serve import poisson_times

    n_commits = int(os.environ.get("FIRA_SERVE_COMMITS", "600"))
    batch = int(os.environ.get("FIRA_SERVE_BATCH", "8"))
    slots = int(os.environ.get("FIRA_SERVE_SLOTS", "16"))
    eos_delta = float(os.environ.get("FIRA_SERVE_EOS_DELTA", "4.0"))
    seed = int(os.environ.get("FIRA_SERVE_SEED", "7"))
    n_req = int(os.environ.get("FIRA_CACHE_REQUESTS", "400"))
    # two operating points per repeat rate: the measured serve knee
    # (0.5x drain — SERVE_BENCH_r01) where the CPU-tiny engine has idle
    # headroom and the cache's honest overhead shows, and the off-arm
    # saturation edge (0.8x) where reuse actually pays — fewer prefill
    # dispatches, higher completed throughput, lower tails
    rate_fracs = [float(f) for f in os.environ.get(
        "FIRA_CACHE_RATE_FRACS", "0.5,0.8").split(",")]
    entries = int(os.environ.get("FIRA_CACHE_ENTRIES", "256"))
    repeats = [float(r) for r in os.environ.get(
        "FIRA_CACHE_REPEATS", "0,0.3,0.6").split(",")]

    dataset, _corpus, cfg, model, params = _setup(
        n_commits, batch=batch, slots=slots, eos_delta=eos_delta)
    data = dataset.splits["train"]
    n_distinct = len(data)
    work = tempfile.mkdtemp(prefix="fira_cache_out_")

    # drain capacity anchor (warm-then-measure, the serve_bench recipe)
    eng = engine_lib.SlotEngine(model, params, cfg)

    def drain_once():
        tasks, _ = _decode_tasks(data, cfg)
        with Feeder(tasks, num_workers=cfg.feeder_workers,
                    depth=cfg.feeder_depth) as feed:
            for _ in eng.run(feed):
                pass

    drain_once()
    eng.stats = engine_lib.EngineStats(slots=eng.slots)
    t0 = time.perf_counter()
    drain_once()
    drain_rps = eng.stats.commits / (time.perf_counter() - t0)

    rows = [{"mode": "cache_anchor", "drain_rps": round(drain_rps, 3),
             "rate_fracs": rate_fracs,
             "n_requests": n_req, "n_distinct": n_distinct,
             "slots": slots, "batch": batch, "cache_entries": entries,
             "host": "cpu-tiny (fira_tiny geometry; the on-vs-off DELTAS "
                     "per repeat rate are the artifact, not absolutes)"}]
    for rate_frac in rate_fracs:
      rate = rate_frac * drain_rps
      times = poisson_times(n_req, rate, seed=seed)
      for repeat in repeats:
        mix = _repeat_mix(n_req, repeat, n_distinct, seed=seed + 1)
        out_bytes = {}
        per_mode = {}
        for cache_on in (False, True):
            c = dataclasses.replace(cfg, prefix_cache=cache_on,
                                    prefix_cache_entries=entries)
            # fresh engine per row: cache/dedup state must not leak
            # across rows, and both arms pay identical construction
            row_eng = engine_lib.SlotEngine(model, params, c)
            # untimed warm pass (compiles + first-touch costs), then the
            # cache is CLEARED so the timed row's hits are earned from
            # its own mix, not the warmup's
            _serve_row(model, params, dataset, c,
                       poisson_times(min(n_req, 4 * batch), rate,
                                     seed=seed),
                       os.path.join(work,
                                    f"warm{rate_frac}_{repeat}_{cache_on}"),
                       engine=row_eng)
            row_eng.cache_clear()
            row_eng.stats = engine_lib.EngineStats(slots=row_eng.slots)
            row_dir = os.path.join(work, f"r{rate_frac}_{repeat}_{cache_on}")
            sv, m = _serve_row(model, params, dataset, c, times, row_dir,
                               engine=row_eng, request_mix=mix,
                               # the acceptance-row artifact: hit rate /
                               # HBM saved land in serve_metrics.json
                               metrics_path=os.path.join(
                                   row_dir, "serve_metrics.json"))
            e = m["engine"]
            per_mode[cache_on] = (sv, e, m["output_path"])
            out_bytes[cache_on] = open(m["output_path"], "rb").read()
        bytes_equal = out_bytes[True] == out_bytes[False]
        off_sv, off_e, _p = per_mode[False]
        on_sv, on_e, _p = per_mode[True]
        saved_frac = (1.0 - on_e["prefills"] / off_e["prefills"]
                      if off_e["prefills"] else 0.0)
        for cache_on in (False, True):
            sv, e, _p = per_mode[cache_on]
            rows.append({
                "mode": "cache_repeat", "repeat_rate": repeat,
                "rate_frac": rate_frac,
                "prefix_cache": cache_on, "offered_rps": round(rate, 3),
                "bytes_equal_off": bytes_equal,
                "throughput_rps": sv["throughput_rps"],
                "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
                "p50_ttft_s": sv["p50_ttft_s"],
                "p99_ttft_s": sv["p99_ttft_s"],
                "completed": sv["completed"], "wall_s": sv["wall_s"],
                "prefills": e["prefills"],
                "prefills_saved": e["prefills_saved"],
                "cache_hit_rate": e["cache_hit_rate"],
                "cache_hits": e["cache_hits"],
                "cache_evictions": e["cache_evictions"],
                "cache_hbm_bytes_saved": e["cache_hbm_bytes_saved"],
                "dedup_coalesced": sv["dedup_coalesced"],
                "dedup_fanout_max": sv["dedup_fanout_max"],
                "shared_block_peak": e["shared_block_peak"],
                "prefill_dispatch_reduction_vs_off":
                    round(saved_frac, 4) if cache_on else 0.0,
            })
    stamp = {"generated_by": "scripts/serve_bench.py --cache",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp) + "\n")
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(json.dumps({"rows": rows, "out": out_path}), flush=True)
    ok = all(r.get("bytes_equal_off", True) for r in rows)
    return 0 if ok else 1


def cache_smoke() -> int:
    """Fixed duplicate-heavy trace, virtual clock, armed compile guard:
    cache-on output bytes == cache-off bytes with REAL reuse happening
    (hits + coalescing both > 0) and zero post-warmup compiles — the
    check.sh tier-1 leg of the prefix-cache equivalence contract."""
    import dataclasses

    import numpy as np

    from fira_tpu.analysis import sanitizer
    from fira_tpu.serve import poisson_times, serve_split

    dataset, _corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),))
    n_distinct = len(dataset.splits["train"])
    n = 48
    # duplicate-heavy fixed mix: bursts of repeats AND spaced repeats, so
    # both dedup (in-flight) and the prefill cache (completed) fire
    mix = _repeat_mix(n, 0.6, n_distinct, seed=5)
    # virtual-clock units, offered fast enough that repeats ARRIVE while
    # their original is still in flight (the dedup window) as well as
    # after it completed (the cache window) — both mechanisms must fire
    # for the smoke to prove anything
    times = poisson_times(n, rate=1.5, seed=3)
    work = tempfile.mkdtemp(prefix="fira_cache_smoke_")

    ref = serve_split(model, params, dataset,
                      dataclasses.replace(cfg, prefix_cache=False),
                      arrival_times=times, out_dir=os.path.join(work, "off"),
                      split="train", clock="virtual", request_mix=mix)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(model, params, dataset,
                        dataclasses.replace(cfg, prefix_cache=True),
                        arrival_times=times,
                        out_dir=os.path.join(work, "on"), split="train",
                        clock="virtual", guard=guard, request_mix=mix)
        extra = guard.compiles_after_warmup()
    got = open(m["output_path"], "rb").read()
    exp = open(ref["output_path"], "rb").read()
    e, sv = m["engine"], m["serve"]
    ok = (got == exp and extra == 0 and sv["completed"] == n
          and e["cache_hits"] > 0 and e["prefills_saved"] > 0
          and sv["dedup_coalesced"] > 0
          and e["prefills"] < ref["engine"]["prefills"])
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_cache_off": got == exp,
        "compiles_after_warmup": extra,
        "completed": sv["completed"], "offered": n,
        "cache_hits": e["cache_hits"],
        "prefills_on_vs_off": [e["prefills"], ref["engine"]["prefills"]],
        "prefills_saved": e["prefills_saved"],
        "dedup_coalesced": sv["dedup_coalesced"],
    }), flush=True)
    return 0 if ok else 1


def _split_requests(dataset, corpus, split: str):
    """The split's commits as reconstructed raw-diff request texts,
    split order (request i = split position i — the corpus-path
    alignment the byte-equality check depends on)."""
    from fira_tpu.ingest.difftext import reconstruct_request

    return [reconstruct_request(corpus.record(int(i)))
            for i in dataset.split_indices[split]]


def ingest_smoke() -> int:
    """Fixed reconstructed-diff trace, virtual clock, armed compile
    guard: the --input diffs path must serve BYTE-IDENTICAL output to
    the corpus-graph path, complete every request with ingest stamps
    recorded, and compile nothing after warmup. The check.sh tier-1
    leg of the ingest round-trip contract (docs/INGEST.md)."""
    import json as _json

    from fira_tpu.analysis import sanitizer
    from fira_tpu.ingest.service import serve_diffs
    from fira_tpu.serve import poisson_times, serve_split

    dataset, corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),),
        extracted=True)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_ingest_smoke_")
    var_path = os.path.join(dataset.data_dir, "variable.json")
    with open(var_path) as f:
        var_maps = _json.load(f)

    ref = serve_split(model, params, dataset, cfg, arrival_times=times,
                      out_dir=os.path.join(work, "graphs"), split="train",
                      clock="virtual", var_maps=var_maps)
    requests = _split_requests(dataset, corpus, "train")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_diffs(model, params, dataset.word_vocab,
                        dataset.ast_change_vocab, cfg, requests=requests,
                        arrival_times=times,
                        out_dir=os.path.join(work, "diffs"),
                        clock="virtual", guard=guard)
        extra = guard.compiles_after_warmup()
    got = open(m["output_path"], "rb").read()
    exp = open(ref["output_path"], "rb").read()
    sv = m["serve"]
    ing = sv.get("ingest", {})
    ok = (got == exp and extra == 0 and sv["completed"] == n
          and sv["shed_error"] == 0
          and ing.get("requests_ingested") == n
          and ing.get("degraded") == 0)
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_corpus_path": got == exp,
        "compiles_after_warmup": extra,
        "completed": sv["completed"], "offered": n,
        "requests_ingested": ing.get("requests_ingested"),
        "p50_ingest_total_s": ing.get("p50_total_s"),
        "ingest_stall_frac": ing.get("stall_frac"),
    }), flush=True)
    return 0 if ok else 1


def ingest_measure(out_path: str) -> int:
    """The raw-diff serving leg (docs/INGEST_BENCH_r02.jsonl): drain
    capacity anchor, then corpus-graph vs reconstructed-diff serving at
    the same swept offered rates — per-stage ingest latency, the
    ingest-stall fraction, and the single-worker ingest rate vs the
    offline preprocessing baseline — plus the Round-14 fast-path legs:
    the ingest-worker-count x parse-exec-mode sweep on a cold repeat-0
    trace (the machine-recorded scaling curve) and seeded repeat-rate
    mixes served with the whole-diff result cache ON vs OFF (bytes
    asserted identical per mix, the repeat-traffic speedup row)."""
    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode.runner import _decode_tasks
    from fira_tpu.ingest.service import serve_diffs
    from fira_tpu.serve import poisson_times

    # 600 commits -> a ~500-request trace: long enough that the Zipf
    # mix's forced-fresh head amortizes (realized distinct -> ~0.4n, so
    # the repeat legs measure the 0.6 repeat rate they claim) and
    # end-of-stream drain effects stop dominating the short legs
    n_commits = int(os.environ.get("FIRA_INGEST_COMMITS", "600"))
    batch = int(os.environ.get("FIRA_SERVE_BATCH", "8"))
    slots = int(os.environ.get("FIRA_SERVE_SLOTS", "16"))
    eos_delta = float(os.environ.get("FIRA_SERVE_EOS_DELTA", "4.0"))
    seed = int(os.environ.get("FIRA_SERVE_SEED", "7"))
    fracs = [float(f) for f in os.environ.get(
        "FIRA_INGEST_RATE_FRACS", "0.5,0.8").split(",")]
    worker_counts = [int(w) for w in os.environ.get(
        "FIRA_INGEST_WORKERS", "1,2,4").split(",")]
    exec_modes = [m.strip() for m in os.environ.get(
        "FIRA_INGEST_EXEC_MODES", "thread,process").split(",")]
    repeats = [float(r) for r in os.environ.get(
        "FIRA_INGEST_REPEATS", "0.6").split(",")]

    dataset, corpus, cfg, model, params = _setup(
        n_commits, batch=batch, slots=slots, eos_delta=eos_delta,
        extracted=True)
    data = dataset.splits["train"]
    n = len(data)
    requests = _split_requests(dataset, corpus, "train")
    work = tempfile.mkdtemp(prefix="fira_ingest_out_")
    # the graphs arm must de-anonymize with the corpus var maps exactly
    # like the diffs arm does with its '#! var:' metadata, or the
    # in-bench byte-equality gate fails on any decode that emits a
    # placeholder token
    with open(os.path.join(dataset.data_dir, "variable.json")) as f:
        var_maps = json.load(f)

    # drain capacity anchor (warm-then-measure, the serve_bench recipe)
    eng = engine_lib.SlotEngine(model, params, cfg)

    def drain_once():
        tasks, _ = _decode_tasks(data, cfg)
        with Feeder(tasks, num_workers=cfg.feeder_workers,
                    depth=cfg.feeder_depth) as feed:
            for _ in eng.run(feed):
                pass

    drain_once()
    eng.stats = engine_lib.EngineStats(slots=eng.slots)
    t0 = time.perf_counter()
    drain_once()
    drain_rps = eng.stats.commits / (time.perf_counter() - t0)
    rows = [{
        "mode": "ingest_anchor", "drain_rps": round(drain_rps, 3),
        "n_requests": n, "slots": slots, "batch": batch,
        "offline_preprocess_rps_per_core": OFFLINE_PREPROCESS_RPS_PER_CORE,
        "host": "cpu-tiny (fira_tiny geometry; the ingest-vs-graphs "
                "DELTAS and the stage split are the artifact, not "
                "absolute numbers)",
    }]

    # one untimed warm pass per path (first-use costs off the timed rows)
    warm_times = poisson_times(min(n, 4 * batch), drain_rps, seed=seed)
    _serve_row(model, params, dataset, cfg, warm_times,
               os.path.join(work, "warm_graphs"), engine=eng,
               var_maps=var_maps)
    serve_diffs(model, params, dataset.word_vocab,
                dataset.ast_change_vocab, cfg,
                requests=requests[: len(warm_times)],
                arrival_times=warm_times,
                out_dir=os.path.join(work, "warm_diffs"), engine=eng)

    for frac in fracs:
        rate = frac * drain_rps
        times = poisson_times(n, rate, seed=seed)
        # corpus-graph reference at the same rate (the decode-only arm)
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        sv_g, _m = _serve_row(model, params, dataset, cfg, times,
                              os.path.join(work, f"g{frac}"), engine=eng,
                              var_maps=var_maps)
        # raw-diff arm: same engine, payloads from the ingest pipeline
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        t0 = time.perf_counter()
        m = serve_diffs(model, params, dataset.word_vocab,
                        dataset.ast_change_vocab, cfg, requests=requests,
                        arrival_times=times,
                        out_dir=os.path.join(work, f"d{frac}"), engine=eng)
        wall = time.perf_counter() - t0
        sv = m["serve"]
        ing = sv["ingest"]
        total_ingest_s = sum(
            sum(r["ingest"].get(k, 0.0)
                for k in ("lex_s", "parse_s", "assemble_s"))
            for r in m["request_records"] if r.get("ingest"))
        ingest_rps_1w = n / total_ingest_s if total_ingest_s else None
        bytes_equal = (
            open(m["output_path"], "rb").read()
            == open(_m["output_path"], "rb").read())
        rows.append({
            "mode": "ingest_sweep", "rate_frac": round(frac, 3),
            "offered_rps": round(rate, 3), "wall_s": round(wall, 3),
            "bytes_equal_graphs_path": bytes_equal,
            "completed": sv["completed"],
            "throughput_rps": sv["throughput_rps"],
            "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
            "graphs_throughput_rps": sv_g["throughput_rps"],
            "graphs_p50_e2e_s": sv_g["p50_e2e_s"],
            "mean_lex_s": ing["mean_lex_s"],
            "mean_parse_s": ing["mean_parse_s"],
            "mean_assemble_s": ing["mean_assemble_s"],
            "p50_ingest_total_s": ing["p50_total_s"],
            "p99_ingest_total_s": ing["p99_total_s"],
            "ingest_stall_s": ing["stall_s"],
            "ingest_stall_frac": ing["stall_frac"],
            "ingest_rps_single_worker": (round(ingest_rps_1w, 1)
                                         if ingest_rps_1w else None),
            "vs_offline_preprocess": (
                round(ingest_rps_1w / OFFLINE_PREPROCESS_RPS_PER_CORE, 4)
                if ingest_rps_1w else None),
        })

    # --- worker-count x exec-mode sweep on a COLD (repeat-0) trace at
    # the 0.8x drain leg: the true-fan-out scaling curve, machine-
    # recorded. Every request is distinct, so the whole-diff cache
    # cannot fire — stall improvements here are pure worker/exec
    # scaling. Thread mode shares the GIL (the native astdiff calls
    # release it; the Python around them doesn't); process mode ships
    # WHOLE requests to a spawned pool (text out, assembled payload
    # back — near-zero parent GIL per request). Fast paths are built
    # once per config and WARMED by an untimed serve (the engine=
    # warm-then-measure discipline: a spawned pool costs seconds to
    # start, which is startup, not serving).
    from fira_tpu.ingest.service import build_fast_path

    sweep_frac = 0.8 if 0.8 in fracs else fracs[-1]
    sweep_rate = sweep_frac * drain_rps
    sweep_times = poisson_times(n, sweep_rate, seed=seed)
    for mode in exec_modes:
        for w in worker_counts:
            c = cfg.replace(ingest_workers=w, ingest_exec=mode)
            fp = build_fast_path(c, context=(
                dataset.word_vocab, dataset.ast_change_vocab, c, None))
            try:
                serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, c,
                            requests=requests[: 6 * batch],
                            arrival_times=sweep_times[: 6 * batch],
                            out_dir=os.path.join(work, f"wu{mode}{w}"),
                            engine=eng, fast_path=fp)
                if fp[0] is not None:
                    fp[0].clear()   # hits must be earned by the timed mix
                eng.stats = engine_lib.EngineStats(slots=eng.slots)
                t0 = time.perf_counter()
                m = serve_diffs(model, params, dataset.word_vocab,
                                dataset.ast_change_vocab, c,
                                requests=requests,
                                arrival_times=sweep_times,
                                out_dir=os.path.join(work, f"w{mode}{w}"),
                                engine=eng, fast_path=fp)
                # wall stops BEFORE the finally joins the process pool —
                # shutdown cost is startup bookkeeping, and folding it in
                # would bias exactly the thread-vs-process comparison
                wall = time.perf_counter() - t0
            finally:
                if fp[2] is not None:
                    fp[2].close()
            sv = m["serve"]
            ing = sv["ingest"]
            rows.append({
                "mode": "ingest_worker_sweep", "ingest_workers": w,
                "ingest_exec": mode, "rate_frac": round(sweep_frac, 3),
                "offered_rps": round(sweep_rate, 3),
                "repeat_rate": 0.0, "wall_s": round(wall, 3),
                "completed": sv["completed"],
                "throughput_rps": sv["throughput_rps"],
                "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
                "ingest_stall_s": ing["stall_s"],
                "ingest_stall_frac": ing["stall_frac"],
                "p50_ingest_total_s": ing["p50_total_s"],
                "memo_hits": ing["memo_hits"],
                "cache_hits": ing["cache_hits"],
            })

    # --- repeat-traffic legs (PR-10's Zipf _repeat_mix applied to DIFF
    # traces): the whole-diff result cache ON vs OFF on the same
    # repeated request stream, output bytes asserted identical per mix
    # — the acceptance speedup row — plus one COMPOSED row stacking the
    # PR-10 prefill cache on the same digests (two cache layers, one
    # repeat). Offered at SATURATION (1.5x drain): the cache's win is
    # ingest capacity, so it must be measured where ingest is the
    # binding constraint — at sub-knee rates fan-out alone hides the
    # pipeline and the A/B measures nothing (the CACHE_BENCH 0.8x-leg
    # logic, one level down).
    ok = True
    rep_rate = 1.5 * drain_rps
    # The cache's win is INGEST capacity, so the A/B must be read where
    # ingest is the binding constraint in BOTH legs — which on this
    # shared-core rig means giving the repeat legs a decode-rich serve
    # config so the decode side approximates the accelerator asymmetry
    # (on a real accelerator the decode path runs device-side and the
    # host ingest is the honest bottleneck; at the sweep geometry the
    # CPU decode ceiling caps the cache-on leg at ~1.7-1.9x and the A/B
    # under-reads the cache). Three levers, each recorded per row:
    # saturation-tuned serve_prefill_budget (PR-9's A/B: budget 1's
    # stall bound costs 27% at saturation), a wider slot arena + packed
    # admission batch (dedicated engines below), and the anchor-rate
    # offered load at 1.5x drain. The off leg is ingest-bound and
    # indifferent to all three.
    rep_budget = int(os.environ.get("FIRA_INGEST_REPEAT_BUDGET", "8"))
    rep_slots = int(os.environ.get("FIRA_INGEST_REPEAT_SLOTS", "32"))
    rep_batch = int(os.environ.get("FIRA_INGEST_REPEAT_BATCH", "16"))
    # ONE ingest worker in every leg: the A/B toggles exactly one
    # variable (the cache) at fixed worker resources — worker fan-out
    # is the OTHER lever and has its own sweep above; at the 2-worker
    # thread default the off leg rides the native parse's released GIL
    # to ~1.5x single-worker and the ratio conflates the two levers
    rep_workers = int(os.environ.get("FIRA_INGEST_REPEAT_WORKERS", "1"))
    rcfg = cfg.replace(engine_slots=rep_slots, test_batch_size=rep_batch,
                       ingest_workers=rep_workers,
                       serve_prefill_budget=min(rep_budget, rep_slots))
    # the composed row stacks the PR-10 prefill cache on the same
    # repeated payloads — the engine's prefill-artifact LRU only exists
    # when IT was built with prefix_cache on, so the composed leg gets
    # its own engine; both repeat engines are warmed by one untimed
    # drain (the serve_bench warm-then-measure discipline)
    ccfg = rcfg.replace(prefix_cache=True)
    rep_eng = engine_lib.SlotEngine(model, params, rcfg)
    ceng = engine_lib.SlotEngine(model, params, ccfg)
    for e, c in ((rep_eng, rcfg), (ceng, ccfg)):
        tasks, _ = _decode_tasks(data, c)
        with Feeder(tasks, num_workers=c.feeder_workers,
                    depth=c.feeder_depth) as feed:
            for _ in e.run(feed):
                pass
    for repeat in repeats:
        mix = _repeat_mix(n, repeat, n, seed=seed + 1)
        rep_reqs = [requests[int(j)] for j in mix]
        rep_times = poisson_times(n, rep_rate, seed=seed)
        out_bytes = {}
        for label, c, leg_eng in (
                ("off", rcfg.replace(ingest_cache=False), rep_eng),
                ("on", rcfg, rep_eng),
                ("on+prefix", ccfg, ceng)):
            fp = build_fast_path(c, context=(
                dataset.word_vocab, dataset.ast_change_vocab, c, None))
            try:
                serve_diffs(model, params, dataset.word_vocab,
                            dataset.ast_change_vocab, c,
                            requests=rep_reqs[: 6 * rep_batch],
                            arrival_times=rep_times[: 6 * rep_batch],
                            out_dir=os.path.join(
                                work, f"repw{repeat}_{label}"),
                            engine=leg_eng, fast_path=fp)
                if fp[0] is not None:
                    fp[0].clear()
                leg_eng.stats = engine_lib.EngineStats(
                    slots=leg_eng.slots)
                leg_eng.cache_clear()
                t0 = time.perf_counter()
                m = serve_diffs(model, params, dataset.word_vocab,
                                dataset.ast_change_vocab, c,
                                requests=rep_reqs,
                                arrival_times=rep_times,
                                out_dir=os.path.join(
                                    work, f"rep{repeat}_{label}"),
                                engine=leg_eng, fast_path=fp)
                wall = time.perf_counter() - t0   # before the pool join
            finally:
                if fp[2] is not None:
                    fp[2].close()
            sv = m["serve"]
            ing = sv["ingest"]
            out_bytes[label] = open(m["output_path"], "rb").read()
            rows.append({
                "mode": "ingest_repeat", "repeat_rate": repeat,
                "ingest_cache": label != "off",
                "prefix_cache": label == "on+prefix",
                "leg": label,
                "rate_frac": 1.5,
                "offered_rps": round(rep_rate, 3),
                "serve_prefill_budget": min(rep_budget, rep_slots),
                "engine_slots": rep_slots, "batch": rep_batch,
                "ingest_workers": rep_workers,
                "wall_s": round(wall, 3),
                "completed": sv["completed"],
                "throughput_rps": sv["throughput_rps"],
                "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
                "ingest_stall_frac": ing["stall_frac"],
                "cache_hits": ing["cache_hits"],
                "memo_hits": ing["memo_hits"],
                "memo_misses": ing["memo_misses"],
                "ingest_cache_meter": ing.get("cache"),
                "prefill_cache_hits": m["engine"].get("cache_hits", 0),
                "dedup_coalesced": sv["dedup_coalesced"],
            })
        same = (out_bytes["on"] == out_bytes["off"]
                == out_bytes["on+prefix"])
        ok = ok and same
        by_leg = {r["leg"]: r for r in rows
                  if r["mode"] == "ingest_repeat"
                  and r.get("repeat_rate") == repeat}
        tp = {leg: r["throughput_rps"] for leg, r in by_leg.items()}
        speedup = (round(tp["on"] / tp["off"], 3)
                   if tp.get("off") and tp.get("on") else None)
        composed = (round(tp["on+prefix"] / tp["off"], 3)
                    if tp.get("off") and tp.get("on+prefix") else None)
        # the cache's own capacity effect, host-noise-free: full ingests
        # the off leg pays per served request vs the on leg (1 /
        # (1 - realized hit rate)) — the served-throughput ratio above
        # under-reads it whenever the cache-on leg saturates the rig's
        # DECODE ceiling instead of ingest (the one-box caveat: decode
        # and ingest share these cores, so relieved ingest capacity
        # beyond the decode ceiling is invisible in served rps; on a
        # real accelerator the decode side runs device-side and the
        # ingest relief is the serving win)
        hits = by_leg.get("on", {}).get("cache_hits", 0)
        capacity = (round(n / (n - hits), 3) if hits and n > hits
                    else None)
        rows.append({
            "mode": "ingest_repeat_verdict", "repeat_rate": repeat,
            "bytes_equal_on_off_composed": same,
            "throughput_speedup_on_vs_off": speedup,
            "throughput_speedup_composed_vs_off": composed,
            "ingest_capacity_multiplier_on_vs_off": capacity,
            "p50_e2e_speedup_composed_vs_off": (
                round(by_leg["off"]["p50_e2e_s"]
                      / by_leg["on+prefix"]["p50_e2e_s"], 3)
                if by_leg.get("off", {}).get("p50_e2e_s")
                and by_leg.get("on+prefix", {}).get("p50_e2e_s")
                else None),
            "caveat": ("one-box CPU rig: decode + ingest share cores, so "
                       "the cache-on legs are bounded by the DECODE "
                       "ceiling (~the graphs-path serve knee), not "
                       "ingest; the served-throughput ratio under-reads "
                       "the cache whenever capacity_multiplier > "
                       "speedup. Accelerator rigs (decode device-side) "
                       "see the capacity multiplier."),
        })

    stamp = {"generated_by": "scripts/serve_bench.py --ingest",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp) + "\n")
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(json.dumps({"rows": rows, "out": out_path}), flush=True)
    ok = ok and all(r.get("bytes_equal_graphs_path", True) for r in rows)
    return 0 if ok else 1


def ingest_cache_smoke() -> int:
    """Duplicate-heavy reconstructed-diff trace, virtual clock, armed
    compile guard: ingest-cache-ON output bytes must equal cache-OFF
    bytes with REAL reuse happening — whole-diff hits (the `cached`
    replay) AND hunk-memo partial hits both > 0 — at zero post-warmup
    retraces and zero post-warmup re-ingests of a repeated diff (every
    repeat is a cache hit once warm). The check.sh tier-1 leg of the
    ingest fast-path bit-exactness contract (docs/INGEST.md)."""
    from fira_tpu.analysis import sanitizer
    from fira_tpu.ingest.service import serve_diffs
    from fira_tpu.serve import poisson_times

    dataset, corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),),
        extracted=True)
    base = _split_requests(dataset, corpus, "train")
    n = 48
    # duplicate-heavy fixed mix: bursts AND spaced repeats, so the
    # whole-diff cache serves both the still-queued and the
    # long-completed repeat shapes
    mix = _repeat_mix(n, 0.6, len(base), seed=5)
    requests = [base[int(j)] for j in mix]
    times = poisson_times(n, rate=1.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_ingest_cache_smoke_")

    ref = serve_diffs(model, params, dataset.word_vocab,
                      dataset.ast_change_vocab,
                      cfg.replace(ingest_cache=False),
                      requests=requests, arrival_times=times,
                      out_dir=os.path.join(work, "off"), clock="virtual")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_diffs(model, params, dataset.word_vocab,
                        dataset.ast_change_vocab, cfg,
                        requests=requests, arrival_times=times,
                        out_dir=os.path.join(work, "on"),
                        clock="virtual", guard=guard)
        extra = guard.compiles_after_warmup()
    got = open(m["output_path"], "rb").read()
    exp = open(ref["output_path"], "rb").read()
    sv = m["serve"]
    ing = sv.get("ingest", {})
    meter = ing.get("cache") or {}
    n_repeat = n - len(set(mix.tolist()))
    # zero post-warmup re-ingests: every repeated text must have hit
    # (misses == distinct texts ingested exactly once)
    ok = (got == exp and extra == 0 and sv["completed"] == n
          and sv["shed_error"] == 0
          and ing.get("cache_hits", 0) > 0
          and ing.get("memo_hits", 0) > 0
          and meter.get("hits", 0) == n_repeat
          and meter.get("misses", 0) == len(set(mix.tolist())))
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_cache_off": got == exp,
        "compiles_after_warmup": extra,
        "completed": sv["completed"], "offered": n,
        "whole_diff_hits": ing.get("cache_hits"),
        "expected_repeats": n_repeat,
        "memo_hits": ing.get("memo_hits"),
        "cache_meter": meter,
    }), flush=True)
    return 0 if ok else 1


def smoke() -> int:
    """Fixed-trace virtual-clock replay under the armed compile guard:
    serve bytes == drain bytes, zero post-warmup compiles, everything
    completed. The check.sh tier-1 leg."""
    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.serve import poisson_times

    dataset, _corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),))
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_serve_smoke_")

    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "drain"), split="train")
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        served, _ = _serve_row(model, params, dataset, cfg, times,
                               os.path.join(work, "serve"), guard=guard,
                               clock="virtual")
        extra = guard.compiles_after_warmup()
    ref = open(drain["output_path"], "rb").read()
    got = open(os.path.join(work, "serve", "output_fira"), "rb").read()
    ok = (got == ref and extra == 0
          and served["completed"] == n and served["shed_queue_full"] == 0
          and served["shed_deadline"] == 0)
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_drain": got == ref,
        "compiles_after_warmup": extra,
        "completed": served["completed"], "offered": n,
        "p50_e2e_virtual": served["p50_e2e_s"],
        "p99_e2e_virtual": served["p99_e2e_s"],
    }), flush=True)
    return 0 if ok else 1


def spec_smoke() -> int:
    """Speculative draft-and-verify equivalence leg (scripts/check.sh,
    docs/DECODE_ENGINE.md "Speculative drafting"): a spec-armed serve
    under the armed compile guard must produce BYTE-IDENTICAL output to
    the plain drain, with REAL acceptances recorded (accepted > 0,
    verify_dispatches > 0 — a run where speculation never engaged proves
    nothing) and zero post-warmup compiles. Then the chaos-compat leg:
    an engine.step fault on a 2-replica fleet with spec ARMED must still
    fire, retire the faulted replica, requeue its work onto the
    survivor, and serve the same bytes — speculation must not widen the
    fault blast radius or break the retire/requeue path."""
    import dataclasses

    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.robust import faults as faults_lib
    from fira_tpu.serve import poisson_times, serve_split

    dataset, _corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),))
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_spec_smoke_")

    scfg = dataclasses.replace(cfg, spec_decode="draft", engine_spec_k=4)

    # --- equivalence leg: spec-on serve vs spec-off drain, same stream
    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "plain"), split="train")
    ref = open(drain["output_path"], "rb").read()
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        m = serve_split(model, params, dataset, scfg, arrival_times=times,
                        out_dir=os.path.join(work, "spec"), split="train",
                        clock="virtual", guard=guard)
        extra = guard.compiles_after_warmup()
    got = open(m["output_path"], "rb").read()
    e, sv = m["engine"], m["serve"]

    # --- chaos-compat leg: same spec config, 2 replicas, a seeded
    # engine.step fault that FIRES on this schedule. Output must still
    # match the plain drain bytes exactly (per-row exactness holds
    # through retire + requeue) with the retirement recorded.
    ccfg = dataclasses.replace(scfg, engine_replicas=2, engine_slots=12,
                               inject_faults="engine.step:raise:0.02:18")
    inj = faults_lib.injector_from(ccfg)
    with sanitizer.sanitize(nans=False, infs=False) as guard2:
        m2 = serve_split(model, params, dataset, ccfg, arrival_times=times,
                         out_dir=os.path.join(work, "fleet_faulted"),
                         split="train", clock="virtual", guard=guard2,
                         faults=inj)
        extra2 = guard2.compiles_after_warmup()
    sv2 = m2["serve"]
    fleet_got = open(m2["output_path"], "rb").read()
    fired = sum(m2.get("faults", {}).values())

    ok = (got == ref and extra == 0 and sv["completed"] == n
          and e["accepted"] > 0 and e["verify_dispatches"] > 0
          and fleet_got == ref and fired > 0 and sv2["completed"] == n
          and sv2["replica_retirements"] >= 1 and extra2 == 0)
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_plain": got == ref,
        "compiles_after_warmup": extra,
        "completed": sv["completed"], "offered": n,
        "acceptance_rate": e["acceptance_rate"],
        "accepted": e["accepted"],
        "verify_dispatches": e["verify_dispatches"],
        "steps_saved": e["steps_saved"],
        "chaos_bytes_equal_plain": fleet_got == ref,
        "chaos_compiles_after_warmup": extra2,
        "chaos_faults_fired": fired,
        "chaos_completed": sv2["completed"],
        "chaos_replica_retirements": sv2["replica_retirements"],
    }), flush=True)
    return 0 if ok else 1


def quant_smoke() -> int:
    """Low-precision serving-tier leg (scripts/check.sh,
    docs/DECODE_ENGINE.md "Low-precision tiers"): the SAME tiny stream
    served under the f32 default, the bf16 KV arena, and the int8
    weight tier, each under the armed compile guard. Per tier: output
    bytes must be STABLE across repeat runs (within-tier determinism —
    bytes are a pure function of the stream), the f32 tier must match
    the plain drain byte-for-byte (the default-path byte-identity
    contract), stats must stamp the tier, zero post-warmup compiles
    must hold from the tier-suffixed program family, and the tier's
    BLEU delta vs f32 must stay inside the measured bound (quality
    measured, never assumed)."""
    import dataclasses

    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.serve import poisson_times, serve_split

    dataset, _corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_quant_smoke_")

    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "plain"), split="train")
    ref = open(drain["output_path"], "rb").read()
    f32_bleu = drain["sentence_bleu"]

    tiers = [("f32", "f32"), ("bf16", "f32"), ("f32", "int8w")]
    rows, ok = [], True
    for kv, sp in tiers:
        tcfg = dataclasses.replace(cfg, kv_dtype=kv, serve_precision=sp)
        runs = []
        for rep in range(2):
            with sanitizer.sanitize(nans=False, infs=False) as guard:
                m = serve_split(
                    model, params, dataset, tcfg, arrival_times=times,
                    out_dir=os.path.join(work, f"{kv}_{sp}_{rep}"),
                    split="train", clock="virtual", guard=guard)
                extra = guard.compiles_after_warmup()
            runs.append((open(m["output_path"], "rb").read(), m, extra))
        (b0, m0, x0), (b1, _m1, x1) = runs
        e, sv = m0["engine"], m0["serve"]
        bleu_delta = m0["sentence_bleu"] - f32_bleu
        row = {
            "kv_dtype": kv, "serve_precision": sp,
            "bytes_stable": b0 == b1,
            "bytes_equal_plain": b0 == ref,
            "compiles_after_warmup": x0 + x1,
            "completed": sv["completed"], "offered": n,
            "stats_kv_dtype": e["kv_dtype"],
            "stats_serve_precision": e["serve_precision"],
            "kv_bytes_per_slot": e["kv_bytes_per_slot"],
            "bleu_delta_vs_f32": round(bleu_delta, 4),
        }
        rows.append(row)
        ok = ok and (b0 == b1 and x0 + x1 == 0 and sv["completed"] == n
                     and e["kv_dtype"] == kv and e["serve_precision"] == sp
                     and abs(bleu_delta) <= 0.5)
        if (kv, sp) == ("f32", "f32"):
            ok = ok and b0 == ref
    # the bf16 arena's honest HBM accounting: half the f32 bytes/slot
    ok = ok and rows[1]["kv_bytes_per_slot"] * 2 == rows[0][
        "kv_bytes_per_slot"]
    print(json.dumps({"smoke": "ok" if ok else "FAIL", "tiers": rows},
                     sort_keys=True), flush=True)
    return 0 if ok else 1


def quant_measure(out_path: str) -> int:
    """The LOW-PRECISION-TIER record (docs/QUANT_BENCH_r01.jsonl;
    docs/DECODE_ENGINE.md "Low-precision tiers"). Three legs:

    - ``equal_hbm_sweep`` — the HBM claim, machine-recorded: the f32 pool
      at full residency at a long tar budget vs the bf16 pool serving 4x
      the slots against the SAME pool bytes (bf16 halves the per-
      position bytes, paging's own equal-HBM doubling stacks on top) —
      ``kv_bytes_per_slot`` quarters, the ``paged_equal_hbm_slot_gain``
      row records >= 4.0.
    - ``tier_serve`` — rps + p50/p99 e2e at the knee rate (0.8 x
      measured drain capacity) per tier, stats-stamped.
    - ``tier_quality`` — the measured-quality contract on the frozen
      split: ``bleu_delta_vs_f32`` and ``logprob_divergence_{mean,p99}``
      per tier, with |BLEU delta| <= 0.5 asserted IN-BENCH (exit
      nonzero on violation — a committed row is a machine-checked row).

    Env: FIRA_QUANT_COMMITS (default 120), FIRA_QUANT_SWEEP_COMMITS
    (default 64), FIRA_QUANT_KNEE_FRAC (default 0.8)."""
    import dataclasses

    import numpy as np

    from fira_tpu.data.feeder import Feeder
    from fira_tpu.decode import engine as engine_lib
    from fira_tpu.decode import paging
    from fira_tpu.decode.runner import _decode_tasks
    from fira_tpu.serve import poisson_times

    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row, sort_keys=True), flush=True)

    # --- leg 1: equal-HBM slot sweep at a long tar budget ------------------
    from fira_tpu.config import fira_tiny
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.dataset import FiraDataset
    from fira_tpu.data.synthetic import write_corpus_dir
    from fira_tpu.decode.beam import eos_biased_params
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import init_state

    sweep_n = int(os.environ.get("FIRA_QUANT_SWEEP_COMMITS", "64"))
    sbatch = 4
    sweep_dir = tempfile.mkdtemp(prefix="fira_quant_sweep_")
    write_corpus_dir(sweep_dir, sweep_n, seed=13)
    cfg_s = fira_tiny(batch_size=8, test_batch_size=sbatch,
                      decode_engine=True, tar_len=64,
                      decode_tar_buckets=True)
    cfg_s = cfg_s.replace(buckets=(
        (cfg_s.ast_change_len, cfg_s.max_edges, 32),))
    dataset_s = FiraDataset(sweep_dir, cfg_s)
    cfg_s = dataset_s.cfg
    split_s = dataset_s.splits["train"]
    sample = make_batch(split_s, np.arange(min(sbatch, len(split_s))),
                        cfg_s, batch_size=sbatch)
    model_s = FiraModel(cfg_s)
    params_s = eos_biased_params(init_state(model_s, cfg_s, sample).params,
                                 delta=4.0)
    bs_s = paging.resolve_block_size(cfg_s)
    w_long = paging.blocks_per_seq(cfg_s.tar_len, bs_s)

    def sweep_row(tag, cfg_row, *, slots=None, pool_blocks=None):
        eng = engine_lib.SlotEngine(model_s, params_s, cfg_row,
                                    slots=slots, pool_blocks=pool_blocks)

        def drive():
            tasks, _ = _decode_tasks(split_s, cfg_row)
            with Feeder(tasks, num_workers=2, depth=2) as feed:
                for _ in eng.run(feed):
                    pass

        drive()                          # warm: compiles off the clock
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        t0 = time.perf_counter()
        drive()
        dt = time.perf_counter() - t0
        st = eng.stats.summary()
        emit({"mode": "equal_hbm_sweep", "tag": tag,
              "commits_per_sec": round(st["commits"] / dt, 2),
              "slots": st["slots"], "tar_len": cfg_row.tar_len,
              "pool_blocks": st["pool_blocks"],
              "kv_block_size": st["kv_block_size"],
              "kv_bytes_per_slot": st["kv_bytes_per_slot"],
              "kv_dtype": st["kv_dtype"],
              "serve_precision": st["serve_precision"]})
        return st

    # full residency: a slot's share of the pool is what a whole-sequence
    # f32 stripe would cost
    st_full = sweep_row("paged_f32_tar64", cfg_s)
    st_bf4x = sweep_row(
        "paged_bf16kv_tar64_4xslots", cfg_s.replace(kv_dtype="bf16"),
        slots=4 * sbatch, pool_blocks=2 * sbatch * w_long)
    gain = st_bf4x["slots"] / st_full["slots"]
    emit({"mode": "equal_hbm_sweep", "tag": "paged_equal_hbm_slot_gain",
          "kv_dtype": "bf16",
          "slots": f"{st_full['slots']} -> {st_bf4x['slots']}",
          "kv_bytes_per_slot": f"{st_full['kv_bytes_per_slot']} -> "
                               f"{st_bf4x['kv_bytes_per_slot']}",
          "value": round(gain, 2)})
    ok = gain >= 4.0 and st_bf4x["kv_bytes_per_slot"] * 4 \
        == st_full["kv_bytes_per_slot"]

    # --- legs 2+3: per-tier serve at the knee + measured quality -----------
    n_commits = int(os.environ.get("FIRA_QUANT_COMMITS", "120"))
    knee_frac = float(os.environ.get("FIRA_QUANT_KNEE_FRAC", "0.8"))
    dataset, _corpus, cfg, model, params = _setup(
        n_commits, batch=6, slots=8, eos_delta=4.0)
    data = dataset.splits["train"]
    n = len(data)
    work = tempfile.mkdtemp(prefix="fira_quant_out_")

    def tier_outputs(tcfg):
        """One drain collecting (tokens, probs) per sample + the warm
        engine for the serve row (per-instance jit: the serve row must
        reuse the drained engine's programs)."""
        eng = engine_lib.SlotEngine(model, params, tcfg)
        out = {}

        def drive(collect):
            tasks, _ = _decode_tasks(data, tcfg)
            with Feeder(tasks, num_workers=2, depth=2) as feed:
                for it in eng.run(feed):
                    if collect:
                        out[it.position] = (np.asarray(it.tokens),
                                            np.asarray(it.probs))
            return out

        drive(True)
        return out, eng

    tiers = [("f32", "f32"), ("bf16", "f32"), ("f32", "int8w"),
             ("bf16", "int8w")]
    f32_out = None
    f32_bleu = None
    drain_rps = None
    for kv, sp in tiers:
        tcfg = dataclasses.replace(cfg, kv_dtype=kv, serve_precision=sp)
        out, eng = tier_outputs(tcfg)
        if drain_rps is None:
            # f32 drain capacity: one timed re-drain on the warm engine
            eng.stats = engine_lib.EngineStats(slots=eng.slots)
            t0 = time.perf_counter()
            tasks, _ = _decode_tasks(data, tcfg)
            with Feeder(tasks, num_workers=2, depth=2) as feed:
                for _ in eng.run(feed):
                    pass
            drain_rps = eng.stats.commits / (time.perf_counter() - t0)
            emit({"mode": "drain_capacity", "kv_dtype": kv,
                  "serve_precision": sp,
                  "drain_rps": round(drain_rps, 3), "n_requests": n})
            # one untimed serve warm pass (the measure() discipline):
            # text-cooking/BLEU first-use costs off the timed rows
            _serve_row(model, params, dataset, tcfg,
                       poisson_times(min(n, 24), drain_rps, seed=7),
                       os.path.join(work, "warm"), engine=eng)

        # quality vs f32 on the frozen split
        if f32_out is None:
            f32_out = out
            div_mean = div_p99 = 0.0
        else:
            diffs = np.concatenate([
                np.abs(out[p][1].ravel() - f32_out[p][1].ravel())
                for p in sorted(f32_out)])
            div_mean = float(np.mean(diffs))
            div_p99 = float(np.percentile(diffs, 99))

        # serve at the knee rate on the warm engine
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        times = poisson_times(n, knee_frac * drain_rps, seed=7)
        sv, m = _serve_row(model, params, dataset, tcfg, times,
                           os.path.join(work, f"{kv}_{sp}"), engine=eng)
        if f32_bleu is None:
            f32_bleu = m["sentence_bleu"]
        bleu_delta = m["sentence_bleu"] - f32_bleu
        e = m["engine"]
        emit({"mode": "tier_serve", "kv_dtype": kv, "serve_precision": sp,
              "rate_frac": knee_frac,
              "offered_rps": round(knee_frac * drain_rps, 3),
              "completed": sv["completed"],
              "throughput_rps": sv["throughput_rps"],
              "p50_e2e_s": sv["p50_e2e_s"], "p99_e2e_s": sv["p99_e2e_s"],
              "kv_bytes_per_slot": e["kv_bytes_per_slot"],
              "stats_kv_dtype": e["kv_dtype"],
              "stats_serve_precision": e["serve_precision"]})
        emit({"mode": "tier_quality", "kv_dtype": kv,
              "serve_precision": sp, "n": n,
              "sentence_bleu": round(m["sentence_bleu"], 4),
              "bleu_delta_vs_f32": round(bleu_delta, 4),
              "logprob_divergence_mean": round(div_mean, 6),
              "logprob_divergence_p99": round(div_p99, 6)})
        ok = ok and abs(bleu_delta) <= 0.5 and sv["completed"] == n

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    print(json.dumps({"quant_bench": "ok" if ok else "FAIL",
                      "rows": len(rows), "out": out_path}), flush=True)
    return 0 if ok else 1


def disagg_smoke() -> int:
    """Disaggregated-tier equivalence leg (scripts/check.sh,
    docs/SERVING.md "Disaggregated tiers"): a ``serve_tiers=
    prefill-pool`` serve under the armed compile guard must produce
    BYTE-IDENTICAL output to the plain drain, with every request
    actually delivered over the pipe/shared-memory transport
    (rows_delivered == n, zero decode-tier prefill dispatches — the
    decode replicas seat exclusively through the prefix cache's all-hit
    path), no recorded fallback, and zero post-warmup compiles on the
    decode tier. Exit nonzero on any violation."""
    import dataclasses

    from fira_tpu.analysis import sanitizer
    from fira_tpu.decode.runner import run_test
    from fira_tpu.serve import poisson_times

    dataset, _corpus, cfg, model, params = _setup(
        40, batch=6, slots=6, eos_delta=4.0, buckets=((16, 400, 12),))
    cfg = dataclasses.replace(cfg, prefix_cache=True)
    n = len(dataset.splits["train"])
    times = poisson_times(n, rate=0.5, seed=3)  # virtual-clock units
    work = tempfile.mkdtemp(prefix="fira_disagg_smoke_")

    drain = run_test(model, params, dataset, cfg,
                     out_dir=os.path.join(work, "drain"), split="train")
    ref = open(drain["output_path"], "rb").read()

    dcfg = dataclasses.replace(cfg, serve_tiers="prefill-pool",
                               prefill_workers=2)
    with sanitizer.sanitize(nans=False, infs=False) as guard:
        served, m = _serve_row(model, params, dataset, dcfg, times,
                               os.path.join(work, "disagg"), guard=guard,
                               clock="virtual")
        extra = guard.compiles_after_warmup()
    got = open(m["output_path"], "rb").read()
    tiers = served.get("tiers") or {}
    decode_prefills = m["engine"]["prefills"]
    ok = (got == ref and extra == 0 and served["completed"] == n
          and tiers.get("rows_delivered", 0) == n
          and not tiers.get("fallback", True)
          and tiers.get("rows_given_up", 1) == 0
          and decode_prefills == 0)
    print(json.dumps({
        "smoke": "ok" if ok else "FAIL",
        "bytes_equal_drain": got == ref,
        "compiles_after_warmup": extra,
        "completed": served["completed"], "offered": n,
        "rows_delivered": tiers.get("rows_delivered"),
        "decode_prefills": decode_prefills,
        "fallback": tiers.get("fallback"),
        "shm_segments": tiers.get("shm_segments"),
        "p50_e2e_virtual": served["p50_e2e_s"],
        "p99_e2e_virtual": served["p99_e2e_s"],
    }), flush=True)
    return 0 if ok else 1


def disagg_measure(out_path: str) -> int:
    """The TIER-SPLIT record (docs/DISAGG_BENCH_r01.jsonl; docs/
    SERVING.md "Disaggregated tiers"): in-process serving vs the
    prefill-pool split on the SAME prefill-heavy all-distinct trace
    (long prefixes, EOS-biased short settles — the shape where prefill
    dominates the decode replica's dispatch mix) at swept offered
    rates on the deterministic virtual clock.

    The clock model is the equal-total-cores accounting: the virtual
    clock charges the DECODE tier's dispatches only (prefills via
    ``on_prefill``, steps per dispatch), so the in-process rows pay
    every prefill on the serving clock while the disagg rows seat
    through the cache's all-hit path and pay none — the structural
    claim (DistServe OSDI'24 §3: prefill off the decode critical path)
    isolated from this box's 1-core contention, which a wall-clock A/B
    would re-introduce as the workers' compute stealing the decode
    tier's core. The prefill tier's own cost is NOT hidden: each disagg
    row records wall-clock ``prefill_util`` (worker busy seconds /
    workers x span) next to decode ``slot_occupancy``, and the knee
    rows machine-name the first tier to saturate from exactly those
    two utilizations. Byte identity in-process vs disagg is asserted
    per rate (exit nonzero on violation).

    Env: FIRA_DISAGG_COMMITS (default 48), FIRA_DISAGG_RATES (default
    "0.5,2.0,8.0" — virtual-clock offered rps), FIRA_DISAGG_WORKERS
    (default 2)."""
    import dataclasses

    from fira_tpu.serve import poisson_times

    n_commits = int(os.environ.get("FIRA_DISAGG_COMMITS", "48"))
    rates = [float(r) for r in os.environ.get(
        "FIRA_DISAGG_RATES", "0.5,2.0,8.0").split(",")]
    workers = int(os.environ.get("FIRA_DISAGG_WORKERS", "2"))

    dataset, _corpus, cfg, model, params = _setup(
        n_commits, batch=6, slots=6, eos_delta=4.0,
        buckets=((16, 400, 12),))
    base = dataclasses.replace(cfg, prefix_cache=True)
    dcfg = dataclasses.replace(base, serve_tiers="prefill-pool",
                               prefill_workers=workers)
    n = len(dataset.splits["train"])
    work = tempfile.mkdtemp(prefix="fira_disagg_bench_")

    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row, sort_keys=True), flush=True)

    ok = True
    sweep = {"in-process": [], "disagg": []}
    for rate in rates:
        times = poisson_times(n, rate=rate, seed=7)
        bytes_by_mode = {}
        for mode, c in (("in-process", base), ("disagg", dcfg)):
            sv, m = _serve_row(model, params, dataset, c, times,
                               os.path.join(work, f"{mode}_r{rate}"),
                               clock="virtual")
            bytes_by_mode[mode] = open(m["output_path"], "rb").read()
            tiers = sv.get("tiers") or {}
            busy = float(tiers.get("prefill_busy_s", 0.0))
            util = (busy / (workers * sv["wall_s"])
                    if mode == "disagg" and sv["wall_s"] else None)
            row = {
                "mode": "tier_split", "serve_mode": mode,
                "offered_rps_virtual": rate, "n_requests": n,
                "prefill_workers": workers if mode == "disagg" else 0,
                "throughput_rps_virtual": sv["throughput_rps"],
                "p50_e2e_virtual": sv["p50_e2e_s"],
                "p99_e2e_virtual": sv["p99_e2e_s"],
                "p50_ttft_virtual": sv["p50_ttft_s"],
                "p99_ttft_virtual": sv["p99_ttft_s"],
                "completed": sv["completed"],
                "decode_prefills": m["engine"]["prefills"],
                "decode_slot_occupancy": m["engine"]["slot_occupancy"],
                "prefill_util_wall": (round(util, 4)
                                      if util is not None else None),
                "rows_delivered": tiers.get("rows_delivered"),
                "artifact_bytes": tiers.get("artifact_bytes"),
                "peak_inflight_bytes": tiers.get("peak_inflight_bytes"),
                "host": "cpu-tiny (fira_tiny geometry; virtual clock "
                        "charges decode-tier dispatches only — shapes "
                        "are the artifact, not absolute numbers)",
            }
            emit(row)
            sweep[mode].append(row)
        if bytes_by_mode["in-process"] != bytes_by_mode["disagg"]:
            ok = False
            emit({"mode": "byte_identity_FAIL",
                  "offered_rps_virtual": rate})

    # --- saturation A/B: at the top swept rate (past both knees by
    # construction) the disagg decode tier, relieved of every prefill
    # dispatch, must answer at a strictly higher virtual rate.
    top = max(rates)
    inproc_top = [r for r in sweep["in-process"]
                  if r["offered_rps_virtual"] == top][0]
    disagg_top = [r for r in sweep["disagg"]
                  if r["offered_rps_virtual"] == top][0]
    beats = (disagg_top["throughput_rps_virtual"]
             > inproc_top["throughput_rps_virtual"])
    ok = ok and beats
    emit({"mode": "saturation_ab", "offered_rps_virtual": top,
          "inproc_rps_virtual": inproc_top["throughput_rps_virtual"],
          "disagg_rps_virtual": disagg_top["throughput_rps_virtual"],
          "disagg_beats_inproc": beats,
          "note": "equal total cores: virtual clock charges decode "
                  "dispatches; prefill-tier load reported as "
                  "prefill_util_wall on the sweep rows"})

    # --- per-tier knee rows: smallest swept rate each serve mode fails
    # to answer at >= 0.9x offered, with the saturating tier machine-
    # named from the measured utilizations at that rate (disagg: the
    # busier of prefill_util_wall vs decode slot occupancy; in-process:
    # the only tier there is).
    for mode in ("in-process", "disagg"):
        sat = [r for r in sweep[mode]
               if r["throughput_rps_virtual"]
               < 0.9 * r["offered_rps_virtual"]]
        under = [r for r in sweep[mode] if r not in sat]
        knee = {"mode": "knee", "serve_mode": mode,
                "knee_offered_rps_virtual": max(
                    (r["offered_rps_virtual"] for r in under),
                    default=None)}
        if mode == "disagg" and sat:
            first = sat[0]
            pu = first["prefill_util_wall"] or 0.0
            du = first["decode_slot_occupancy"] or 0.0
            knee["knee_tier"] = "prefill" if pu > du else "decode"
            knee["prefill_util_wall"] = pu
            knee["decode_slot_occupancy"] = du
        elif sat:
            knee["knee_tier"] = "decode"
        else:
            knee["knee_tier"] = None
        emit(knee)

    stamp = {"generated_by": "scripts/serve_bench.py --disagg",
             "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write(json.dumps(stamp, sort_keys=True) + "\n")
        for r in rows:
            f.write(json.dumps(r, sort_keys=True) + "\n")
    print(json.dumps({"disagg_bench": "ok" if ok else "FAIL",
                      "rows": len(rows), "out": out_path}), flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fixed-trace replay sanity leg (scripts/check.sh)")
    ap.add_argument("--cache", action="store_true",
                    help="repeated-traffic prefix-cache leg "
                         "(docs/CACHE_BENCH_r01.jsonl)")
    ap.add_argument("--cache-smoke", action="store_true",
                    help="duplicate-trace cache-on == cache-off bytes leg "
                         "(scripts/check.sh)")
    ap.add_argument("--ingest", action="store_true",
                    help="raw-diff serving leg "
                         "(docs/INGEST_BENCH_r02.jsonl)")
    ap.add_argument("--ingest-smoke", action="store_true",
                    help="reconstructed-diff trace == corpus-path bytes "
                         "leg (scripts/check.sh)")
    ap.add_argument("--ingest-cache-smoke", action="store_true",
                    help="duplicate diff trace, ingest-cache on == off "
                         "bytes with real hits leg (scripts/check.sh)")
    ap.add_argument("--spec-smoke", action="store_true",
                    help="speculative decode: spec-on serve bytes == "
                         "plain drain bytes with real acceptances, plus "
                         "the fault-under-spec fleet leg (scripts/check.sh)")
    ap.add_argument("--quant", action="store_true",
                    help="low-precision serving tiers leg "
                         "(docs/QUANT_BENCH_r01.jsonl)")
    ap.add_argument("--quant-smoke", action="store_true",
                    help="tiers: per-tier byte-stability + measured BLEU "
                         "bound + zero retraces (scripts/check.sh)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated-tier split leg "
                         "(docs/DISAGG_BENCH_r01.jsonl)")
    ap.add_argument("--disagg-smoke", action="store_true",
                    help="prefill-pool serve bytes == drain bytes with "
                         "every artifact transport-delivered + zero "
                         "decode prefills leg (scripts/check.sh)")
    ap.add_argument("--out", default=None,
                    help=f"JSONL record path (default {DEFAULT_OUT}; "
                         f"{DEFAULT_CACHE_OUT} with --cache; "
                         f"{DEFAULT_INGEST_OUT} with --ingest)")
    args = ap.parse_args()

    from fira_tpu.utils.startup import force_cpu_backend

    force_cpu_backend()
    if args.smoke:
        return smoke()
    if args.cache_smoke:
        return cache_smoke()
    if args.ingest_smoke:
        return ingest_smoke()
    if args.ingest_cache_smoke:
        return ingest_cache_smoke()
    if args.spec_smoke:
        return spec_smoke()
    if args.quant_smoke:
        return quant_smoke()
    if args.disagg_smoke:
        return disagg_smoke()
    if args.disagg:
        return disagg_measure(args.out or DEFAULT_DISAGG_OUT)
    if args.quant:
        return quant_measure(args.out or DEFAULT_QUANT_OUT)
    if args.cache:
        return cache_measure(args.out or DEFAULT_CACHE_OUT)
    if args.ingest:
        return ingest_measure(args.out or DEFAULT_INGEST_OUT)
    return measure(args.out or DEFAULT_OUT)


if __name__ == "__main__":
    raise SystemExit(main())
