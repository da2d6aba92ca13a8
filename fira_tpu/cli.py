"""Command-line driver.

Keeps the reference's surface — ``train`` / ``test`` positionals
(/root/reference/run_model.py:417-425) — and adds the real flag system the
reference lacks (SURVEY.md §5 "Config / flag system"): named configs
(fira-tiny / fira-full / fira-large), ablation switches matching the paper's
Table 3 rows, a --backend flag (jax is the only compiled-in backend; the
flag exists for CLI parity with torch-based stacks), mesh shape, data/output
directories, and resume control.

Examples:
    python -m fira_tpu.cli train --data-dir DataSet --config fira-full
    python -m fira_tpu.cli test  --data-dir DataSet --ablation no_edit
    python -m fira_tpu.cli train --config fira-tiny --synthetic 512
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="fira_tpu", description=__doc__)
    p.add_argument("command", choices=["train", "test", "serve",
                                       "message", "preprocess"],
                   help="train: fit + dev-gate; test: beam-decode the test "
                        "split; serve: a long-lived server under open-loop "
                        "arrival-timed load — corpus test split or, with "
                        "--input diffs, raw unified-diff requests "
                        "(docs/SERVING.md, docs/INGEST.md); message: "
                        "one-shot diff-in/message-out on a single diff "
                        "file; preprocess: raw diffs -> DataSet/ corpus")
    p.add_argument("target", nargs="?", default=None,
                   help="message: the unified-diff file to generate a "
                        "commit message for (unused by other commands)")
    p.add_argument("--backend", default="jax", choices=["jax"],
                   help="compute backend (this framework is TPU/JAX-native)")
    p.add_argument("--config", default="fira-full",
                   help="named config: fira-tiny | fira-full | fira-large")
    p.add_argument("--ablation", default=None,
                   choices=["no_edit", "no_subtoken", "nothing"],
                   help="paper Table 3 ablations")
    p.add_argument("--data-dir", default="DataSet",
                   help="corpus directory (reference DataSet/ layout)")
    p.add_argument("--out-dir", default="OUTPUT")
    p.add_argument("--ckpt-dir", default=None,
                   help="default: <out-dir>/ckpt[_<ablation>]")
    p.add_argument("--epochs", type=int, default=None,
                   help="override config epoch count")
    p.add_argument("--batch-size", type=int, default=None)
    def _positive(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return v

    p.add_argument("--test-batch-size", type=_positive, default=None,
                   metavar="N",
                   help="test: decode batch (default 20, the reference's "
                        "run_model.py:41). A pure throughput knob: "
                        "predictions are batch-invariant (tested), and the "
                        "decode step's per-sample matmuls under-fill the "
                        "MXU at small batches")
    p.add_argument("--no-resume", action="store_true",
                   help="ignore an existing latest checkpoint")
    p.add_argument("--synthetic", type=int, default=None, metavar="N",
                   help="generate an N-commit synthetic corpus into "
                        "--data-dir first (fixture / smoke runs)")
    p.add_argument("--mesh", default=None, metavar="DPxTP",
                   help="device mesh, e.g. 4x1 (data x model); default: all "
                        "devices on the data axis")
    p.add_argument("--dtype", default=None, choices=["float32", "bfloat16"],
                   help="compute dtype override (params stay f32)")
    p.add_argument("--beam-factored-topk", action="store_true",
                   help="test: beam candidates from per-side top-ks "
                        "(generation vocab + copy positions, gate-scaled) "
                        "instead of the assembled 25,020-way fused tensor "
                        "— token-exact (pinned by tests)")
    p.add_argument("--beam-early-exit", action="store_true",
                   help="test: stop the decode loop once every beam has "
                        "emitted EOS (+1 settling step) — bit-exact vs the "
                        "full tar_len scan, wall clock scales with the "
                        "batch's longest message")
    p.add_argument("--engine", action="store_true",
                   help="test: decode through the slot-refill continuous-"
                        "batching engine (decode/engine.py, docs/"
                        "DECODE_ENGINE.md): settled slots are harvested "
                        "and refilled mid-flight, so wall clock scales "
                        "with total tokens emitted instead of per-batch "
                        "max length. Bit-exact per sample vs the batched "
                        "beam (pinned by tests) in every kv-cache x "
                        "factored-topk mode")
    p.add_argument("--engine-slots", type=_positive, default=None,
                   metavar="S",
                   help="test: engine slot-arena size (default: "
                        "--test-batch-size — equal geometry with the "
                        "batched beam)")
    p.add_argument("--engine-prefill-depth", type=_positive, default=None,
                   metavar="D",
                   help="test: prefilled chunks staged ahead of the "
                        "engine's refill loop (default 2; 1 = prefill "
                        "strictly on demand)")
    p.add_argument("--engine-harvest-every", type=_positive, default=None,
                   metavar="R",
                   help="test: engine harvest cadence — beam positions "
                        "advanced per step dispatch before the host "
                        "harvests settled slots (default 4; output-"
                        "identical for any R, pinned by tests)")
    p.add_argument("--engine-replicas", type=_positive, default=None,
                   metavar="N",
                   help="test: replicated slot-engine decode fleet "
                        "(parallel/fleet.py, docs/MULTICHIP.md): N engine "
                        "replicas — one per device — pull chunks from one "
                        "shared admission queue with harvest/refill "
                        "interleaved across replicas. Output file bytes "
                        "are invariant to N (pinned by tests). A nonzero "
                        "--engine-slots is the fleet TOTAL and must divide "
                        "by N")
    p.add_argument("--spec-decode", default=None,
                   choices=["off", "copy", "draft"],
                   help="test/serve: speculative draft-and-verify decode "
                        "on the slot engine (decode/spec.py, docs/"
                        "DECODE_ENGINE.md 'Speculative drafting'): a "
                        "cheap drafter proposes --spec-k tokens per live "
                        "slot and ONE jitted verify program scores them "
                        "with the engine's own step body, accepting the "
                        "longest matching prefix. 'copy' drafts from the "
                        "copy-head distribution alone (no decoder "
                        "stack); 'draft' greedy-rolls the full step "
                        "program. Accepted output stays bit-exact vs "
                        "plain engine decode (pinned by tests); default "
                        "off. Requires --engine")
    p.add_argument("--spec-k", type=_positive, default=None, metavar="K",
                   help="test/serve: speculative draft length — tokens "
                        "proposed per slot per verify dispatch (default "
                        "4). Must leave room in the smallest declared "
                        "decode tar budget (validated at parse time, "
                        "exit 2). Output bytes are invariant to K "
                        "(pinned by tests)")
    p.add_argument("--kv-block-size", type=int, default=None, metavar="B",
                   help="test: paged-KV block size in cache positions; "
                        "must divide every declared decode tar budget "
                        "(validated at parse time, exit 2). 0/unset = "
                        "auto (largest common divisor <= 16)")
    p.add_argument("--kv-pool-blocks", type=int, default=None, metavar="P",
                   help="test: paged-KV pool size in blocks (the fleet "
                        "TOTAL, split across --engine-replicas like "
                        "--engine-slots). Must keep every slot servable: "
                        "per replica >= slots x ceil(tar/block) on the "
                        "smallest decode tar and >= one largest-budget "
                        "sample (validated at parse time, exit 2). "
                        "0/unset = auto: full residency, admission "
                        "never waits for blocks")
    p.add_argument("--kv-dtype", default=None, choices=["f32", "bf16"],
                   help="test/serve: engine KV arena storage dtype (docs/"
                        "DECODE_ENGINE.md 'Low-precision tiers'): 'bf16' "
                        "stores the slot arena's pool blocks in bfloat16 "
                        "— half the kv_bytes_per_slot, machine-recorded "
                        "in stats — "
                        "while every read upcasts so attention math stays "
                        "f32. Output bytes within a tier stay a pure "
                        "function of the stream (pinned by tests); quality "
                        "vs f32 is measured, never assumed (bench records "
                        "bleu_delta_vs_f32). Default 'f32' is byte-"
                        "identical to the pre-tier engine. Requires "
                        "--engine")
    p.add_argument("--serve-precision", default=None,
                   choices=["f32", "bf16", "int8w"],
                   help="test/serve: decode weight tier (docs/DECODE_"
                        "ENGINE.md 'Low-precision tiers'): the decode-only "
                        "program family (step/draft/verify) runs on a "
                        "quantized copy of the dominant matmul weights — "
                        "'int8w' per-channel symmetric int8 with f32 "
                        "accumulate and on-the-fly dequant, 'bf16' a "
                        "bfloat16 cast — quantized once at engine build "
                        "(and per respawn/spare prewarm). Prefill and the "
                        "f32 default stay full precision; static shapes "
                        "and the zero-post-warmup-retrace contract are "
                        "unchanged (labels carry the tier suffix). "
                        "Requires --engine")
    p.add_argument("--decode-tar-buckets", action="store_true",
                   help="test: let decode buckets keep their OWN tar "
                        "lengths instead of pinning tar full — each "
                        "sample packs into the smallest tar budget that "
                        "fits its reference message, and the slot engine "
                        "caps generation (and sizes its paged block "
                        "reservation) at that budget. The longer-target "
                        "door: raise the config tar_len and declare the "
                        "common case as a bucket")
    p.add_argument("--prefix-cache", default=None, choices=["on", "off"],
                   help="cross-request prefix cache + in-flight dedup "
                        "(decode/prefix_cache.py; docs/DECODE_ENGINE.md "
                        "'Prefix cache & dedup'): 'on' content-addresses "
                        "each request's prefill artifacts by a keyed "
                        "digest of its packed payload — a byte-identical "
                        "repeat seats from cache without dispatching "
                        "prefill, and an identical IN-FLIGHT request "
                        "coalesces onto the existing seat with fan-out "
                        "delivery (one decode, N output positions). "
                        "Bit-exact vs 'off' (tested); hits/misses/"
                        "evictions, dedup fan-out, prefill dispatches "
                        "saved, and HBM bytes saved are metered. Default: "
                        "ON for `serve`, off for `test` (engine path "
                        "required)")
    p.add_argument("--prefix-cache-entries", type=int, default=None,
                   metavar="N",
                   help="prefix-cache LRU capacity in cached request "
                        "entries, per engine replica (default 256; must "
                        "be >= 1 when the cache is on — validated at "
                        "parse time, exit 2)")
    p.add_argument("--prefix-cache-bytes", type=int, default=None,
                   metavar="B",
                   help="prefix-cache host-memory budget in bytes, per "
                        "engine replica: entries evict LRU-first until "
                        "payload bytes fit (artifact payloads are MBs "
                        "per entry at production geometry). 0/unset = "
                        "unbounded (the entry cap is the only bound); "
                        "must be >= 0 — validated at parse time, exit 2")
    p.add_argument("--input", default="graphs", choices=["graphs", "diffs"],
                   help="serve: request source (docs/INGEST.md): 'graphs' "
                        "(default) serves the corpus test split's "
                        "pre-assembled graph requests; 'diffs' serves RAW "
                        "unified git diffs from --diff-trace end to end — "
                        "per-request diff parse + Java lexing + hunk FSM + "
                        "AST extraction + frozen-vocab encoding run inside "
                        "the feeder worker pool, malformed diffs are "
                        "recorded-shed (never a crash), and a "
                        "reconstructed corpus diff serves byte-identical "
                        "output to the graphs path (the round-trip "
                        "contract, machine-checked in check.sh)")
    p.add_argument("--diff-trace", default=None, metavar="PATH",
                   help="serve --input diffs: the request source — a file "
                        "of '#! request'-separated unified diffs, or a "
                        "directory of .diff files served in sorted name "
                        "order (validated at parse time, exit 2). "
                        "Arrival TIMES still come from --serve-rate / "
                        "--serve-trace")
    p.add_argument("--ingest-workers", type=int, default=None, metavar="N",
                   help="serve --input diffs: feeder workers for the "
                        "per-request ingest tasks (parse + AST extraction "
                        "+ encode, worker-side). 0/unset = reuse "
                        "--feeder-workers' config default; must be >= 0 "
                        "(validated at parse time, exit 2)")
    p.add_argument("--ingest-truncate", default=None,
                   choices=["clip", "shed"],
                   help="serve --input diffs: over-budget diff policy "
                        "(docs/INGEST.md): 'clip' (default) "
                        "deterministically truncates to the config "
                        "geometry and records what was dropped in the "
                        "request's ingest stamps; 'shed' rejects the "
                        "request with a recorded error and an empty "
                        "output line")
    p.add_argument("--ingest-cache", default=None, choices=["on", "off"],
                   help="serve --input diffs: the ingest fast path "
                        "(docs/INGEST.md 'Fast path'): 'on' (default) "
                        "content-addresses each raw diff's BYTES at "
                        "intake — a byte-identical repeat skips the "
                        "whole lex/AST/assemble pipeline and seats from "
                        "an LRU of assembled payloads (its _ingest "
                        "stamps replayed with a `cached` flag), and the "
                        "AST stage is memoized per hunk so near-"
                        "identical diffs reuse parsed sub-results. "
                        "Bit-exact vs 'off' (tested + check.sh smoke); "
                        "hits/evictions/integrity drops are metered")
    p.add_argument("--ingest-cache-entries", type=int, default=None,
                   metavar="N",
                   help="whole-diff result-cache LRU capacity in cached "
                        "request payloads (default 512; 0 = unbounded; "
                        "must be >= 0 — validated at parse time, "
                        "exit 2)")
    p.add_argument("--ingest-cache-bytes", type=int, default=None,
                   metavar="B",
                   help="whole-diff result-cache host-memory budget in "
                        "bytes: entries evict LRU-first until payload "
                        "bytes fit. 0/unset = unbounded; must be >= 0 — "
                        "validated at parse time, exit 2")
    p.add_argument("--ingest-exec", default=None,
                   choices=["thread", "process"],
                   help="serve --input diffs: AST parse-stage execution "
                        "(docs/INGEST.md 'Fast path'): 'thread' "
                        "(default) runs it inline on the feeder "
                        "workers; 'process' ships it to a spawned "
                        "process pool sized by --ingest-workers — the "
                        "GIL-bound stage's true fan-out mode (output "
                        "bit-exact either way)")
    p.add_argument("--serve-rate", type=float, default=None, metavar="RPS",
                   help="serve: offered load in requests/second for the "
                        "open-loop Poisson arrival generator; required "
                        "(> 0) unless --serve-trace replays a recorded "
                        "schedule (validated at parse time, exit 2)")
    p.add_argument("--serve-trace", default=None, metavar="PATH",
                   help="serve: replay this arrival-trace file (one "
                        "non-decreasing arrival time per line, line i = "
                        "test-split position i — serve/arrivals.py) "
                        "instead of generating Poisson arrivals; replayed "
                        "traces make serving runs deterministic")
    p.add_argument("--serve-prefill-budget", type=int, default=None,
                   metavar="P",
                   help="serve: max prefill dispatches interleaved between "
                        "step dispatches per replica (default 1 — the "
                        "latency-lean setting; must be >= 1 and <= the "
                        "per-replica slot count, validated at parse time, "
                        "exit 2). Higher trades seated requests' tail "
                        "latency for admission throughput")
    p.add_argument("--serve-deadline-steps", type=int, default=None,
                   metavar="D",
                   help="serve: per-request deadline in step dispatches — "
                        "a request still queued after D steps is shed "
                        "(recorded, never a hang). 0 = none (default); "
                        "must be 0 or >= 1 (validated at parse time, "
                        "exit 2)")
    p.add_argument("--serve-queue-cap", type=int, default=None, metavar="Q",
                   help="serve: admission-queue bound — an arrival past Q "
                        "queued requests is rejected on the spot "
                        "(structured backpressure; recorded). 0 = "
                        "unbounded (default)")
    p.add_argument("--serve-tiers", default=None,
                   choices=["off", "prefill-pool"],
                   help="serve: tier topology (docs/SERVING.md "
                        "'Disaggregated tiers') — 'off' (default) is "
                        "in-process serve; 'prefill-pool' runs a pool of "
                        "prefill worker PROCESSES shipping seat-ready "
                        "artifacts so decode replicas never dispatch a "
                        "prefill program. Requires --prefix-cache on and "
                        "the decode engine; validated at parse time, "
                        "exit 2")
    p.add_argument("--prefill-workers", type=int, default=None,
                   metavar="W",
                   help="serve: prefill-pool width — worker processes "
                        "in the prefill tier (each owns a jax runtime; "
                        "output bytes invariant to W). Must be >= 1 "
                        "(validated at parse time, exit 2)")
    p.add_argument("--serve-artifact-budget-mb", type=int, default=None,
                   metavar="MB",
                   help="serve: prefill-tier backpressure — total "
                        "artifact bytes in flight stays under this "
                        "budget so a fast prefill tier cannot OOM the "
                        "host. 0 = unbounded; must be >= 0 (validated "
                        "at parse time, exit 2)")
    p.add_argument("--serve-clock", default="wall",
                   choices=["wall", "virtual"],
                   help="serve: 'wall' (default) paces arrivals in real "
                        "time — the latency-measurement mode; 'virtual' "
                        "advances a deterministic unit clock per dispatch "
                        "— the replayable-trace equivalence mode")
    p.add_argument("--inject-faults", default=None, metavar="SPEC",
                   help="seeded fault injection (docs/FAULTS.md): "
                        "'site:kind:rate:seed[,...]' arming named "
                        "injection points (sites: feeder.assemble, "
                        "feeder.device_put, ingest.parse, engine.prefill, "
                        "engine.step, "
                        "engine.harvest, fleet.replica, serve.admit, "
                        "cache.lookup, ingest.cache, disagg.transport, "
                        "disagg.worker; "
                        "kinds: raise | hang | corrupt). Deterministic "
                        "given the seed — chaos runs replay exactly; "
                        "validated at parse time, exit 2. Off by default "
                        "(zero hot-path overhead)")
    p.add_argument("--dispatch-watchdog-s", type=float, default=None,
                   metavar="S",
                   help="per-dispatch wall-clock watchdog (docs/FAULTS"
                        ".md): a fleet/serve replica dispatch exceeding "
                        "S seconds is abandoned and the replica RETIRED "
                        "(its requests requeued onto survivors); a dev "
                        "gate exceeding it is skipped with a recorded "
                        "warning. 0 = off (default); validated at parse "
                        "time, exit 2")
    p.add_argument("--robust-retries", type=int, default=None, metavar="N",
                   help="poison-request quarantine depth (docs/FAULTS"
                        ".md): retries (with backoff) a request gets "
                        "when its assembly/admission/prefill raises, "
                        "before it is shed with a recorded error and an "
                        "empty output line (default 1; >= 0, validated "
                        "at parse time, exit 2)")
    p.add_argument("--max-respawns", type=int, default=None, metavar="N",
                   help="self-healing fleet (docs/FAULTS.md 'Recovery "
                        "contracts'): replacement budget per replica "
                        "lineage — a retired replica is respawned (fresh "
                        "engine on its device, prewarmed through the "
                        "declared family, or a warm spare attached) up "
                        "to N times before the lineage degrades "
                        "permanently. 0 = off (default, the retire-and-"
                        "degrade behavior); >= 0, validated at parse "
                        "time, exit 2")
    p.add_argument("--engine-spares", type=int, default=None, metavar="N",
                   help="warm-spare pool: N pre-built prewarmed standby "
                        "engines a retirement attaches in O(1) instead "
                        "of paying a mid-run build + compile; counts "
                        "against --max-respawns on attach (requires "
                        "--max-respawns >= 1; >= 0, validated at parse "
                        "time, exit 2)")
    p.add_argument("--respawn-backoff-s", type=float, default=None,
                   metavar="S",
                   help="respawn backoff base in wall seconds: a crash-"
                        "looping lineage waits the shared backoff curve "
                        "(linear in the attempt, capped at 5x) rescaled "
                        "to S between replacements (default 0.25; > 0, "
                        "validated at parse time, exit 2)")
    p.add_argument("--resume", action="store_true",
                   help="serve: resume a killed run from its write-ahead "
                        "request journal (<out>/output_fira*.journal) + "
                        "the ordered writer's crash pair — only the "
                        "not-yet-done suffix is re-served and the final "
                        "output file is byte-identical to an "
                        "uninterrupted run (exactly-once output; "
                        "docs/FAULTS.md 'Recovery contracts'). Requires "
                        "an existing journal from a prior `serve` run "
                        "with the same trace/seed/rate (validated at "
                        "parse time, exit 2)")
    p.add_argument("--beam-log-space", action="store_true",
                   help="log-space beam accumulation instead of the "
                        "reference-compat probability space")
    p.add_argument("--shard-size", type=int, default=100,
                   help="preprocess: commits per worker shard (reference "
                        "each_num=100)")
    p.add_argument("--num-procs", type=int, default=None,
                   help="preprocess: worker processes (default: cpu count)")
    p.add_argument("--encoder-buffer", default=None,
                   choices=["single", "split"],
                   help="encoder node buffer: one 650-row tensor with "
                        "per-round update-slices (single, default) or two "
                        "persistent segments with column-slab A.x bmms "
                        "(split; dense adjacency only, equal up to matmul "
                        "reassociation)")
    p.add_argument("--adjacency", default=None,
                   choices=["dense", "segment"],
                   help="GCN message passing: dense bmm (default) or "
                        "O(edges) COO segment-sum for larger graphs")
    p.add_argument("--copy-head", default=None, choices=["xla", "pallas"],
                   help="pointer-score impl: XLA (materialized intermediate) "
                        "or the fused Pallas kernel")
    p.add_argument("--typed-edges", action="store_true",
                   help="learn one gain per edge family instead of the "
                        "reference's flattened untyped adjacency "
                        "(beyond-parity extension; identical at init)")
    p.add_argument("--seq-shards", type=int, default=None, metavar="N",
                   help="ring-attention sequence parallelism: shard decoder "
                        "cross-attention K/V over N devices (long-context "
                        "scaling; 0/1 = dense attention)")
    p.add_argument("--sort-edges", action="store_true",
                   help="pre-sort each sample's COO edges on the host so "
                        "the device scatter runs with sorted indices "
                        "(semantically identical)")
    p.add_argument("--rng-impl", default=None, choices=["threefry", "rbg"],
                   help="dropout PRNG: reproducible-everywhere threefry "
                        "(default) or TPU-fast hardware rbg")
    p.add_argument("--fused-steps", type=int, default=None, metavar="K",
                   help="train: run K steps per dispatch as one lax.scan "
                        "device loop (1 = per-step dispatch); dev-gate/log "
                        "cadence rounds to K-step group boundaries")
    p.add_argument("--accum-steps", type=int, default=None, metavar="A",
                   help="train: accumulate A micro-batches into one "
                        "optimizer step normalized over the global "
                        "(sum, count) — A=4 with batch 170 reproduces the "
                        "reference's 4-GPU batch-680 dynamics on one chip")
    p.add_argument("--profile-dir", default=None,
                   help="train: write a jax.profiler trace of a steady-state "
                        "step window here (TensorBoard-loadable)")
    p.add_argument("--buckets", default=None, metavar="SPEC",
                   help="padding-bucket family (docs/BUCKETING.md): 'off' "
                        "(default — no declared table: decode runs the "
                        "single full geometry, a train dispatch pads its "
                        "edge rows to the least rung of max_edges / 2^k "
                        "that holds its commits), 'auto' (choose 3 buckets "
                        "from the "
                        "split's length histograms), or an explicit table "
                        "'AST:EDGES:TAR[,AST:EDGES:TAR...]' of geometries "
                        "<= the config's full values. Each sample packs "
                        "into its smallest admissible bucket; one "
                        "pre-warmed program per bucket, zero post-warmup "
                        "retraces. Composes with --fused-steps/"
                        "--accum-steps: groups pack bucket-homogeneous "
                        "K-stacks (docs/BUCKETING.md Composition)")
    p.add_argument("--sanitize", action="store_true",
                   help="arm the runtime sanitizer (analysis.sanitizer): "
                        "jax_debug_nans/jax_debug_infs on every program, "
                        "plus a compile-count guard that raises if any "
                        "step after a program's warmup dispatch triggers "
                        "a new XLA compilation (catches silent per-step "
                        "retraces). Debugging mode: each dispatch syncs, "
                        "so throughput numbers are not meaningful")
    p.add_argument("--perf", default=None, choices=["parity", "production"],
                   help="knob preset: 'production' applies the measured "
                        "fastest TPU config (config.PRODUCTION_PERF_KNOBS: "
                        "rbg dropout PRNG, fused device loop, sorted "
                        "scatters, bf16 residual streams, no copy-head "
                        "remat — docs/PERF.md) plus the equivalence-pinned "
                        "decode set (config.DECODE_PERF_KNOBS: kv cache, "
                        "factored top-k, early exit, slot-refill engine "
                        "decode); 'parity' (default) "
                        "keeps the reference-parity knob defaults. "
                        "Individual flags override the preset either way")
    return p


def _resolve_cfg(args):
    from fira_tpu.config import (DECODE_PERF_KNOBS, PRODUCTION_PERF_KNOBS,
                                 apply_ablation, get_config)

    cfg = get_config(args.config.replace("_", "-"))
    cfg = apply_ablation(cfg, args.ablation)
    if args.perf == "production":
        # train-side stacked knobs + the decode-side beam set (the latter
        # only matters when beam decode runs; every member is
        # equivalence-pinned — config.DECODE_PERF_KNOBS)
        cfg = cfg.replace(**PRODUCTION_PERF_KNOBS, **DECODE_PERF_KNOBS)
    overrides = {}
    if args.batch_size:
        overrides["batch_size"] = args.batch_size
    if args.test_batch_size:
        overrides["test_batch_size"] = args.test_batch_size
    if args.epochs:
        overrides["epochs"] = args.epochs
    if args.dtype:
        overrides["compute_dtype"] = args.dtype
    if args.beam_log_space:
        overrides["beam_compat_prob_space"] = False
    if args.beam_factored_topk:
        overrides["beam_factored_topk"] = True
    if args.beam_early_exit:
        overrides["beam_early_exit"] = True
    if args.engine:
        overrides["decode_engine"] = True
    elif cfg.arch != "fira":
        # its presets keep the engine on (nothing else runs them); on the
        # CLI the path is still asked for by name, and refused without it
        overrides["decode_engine"] = False
    if args.engine_slots is not None:
        overrides["engine_slots"] = args.engine_slots
    if args.engine_prefill_depth is not None:
        overrides["engine_prefill_depth"] = args.engine_prefill_depth
    if args.engine_harvest_every is not None:
        overrides["engine_harvest_every"] = args.engine_harvest_every
    if args.engine_replicas is not None:
        overrides["engine_replicas"] = args.engine_replicas
    if args.spec_decode is not None:
        overrides["spec_decode"] = args.spec_decode
    if args.spec_k is not None:
        overrides["engine_spec_k"] = args.spec_k
    if args.kv_block_size is not None:
        overrides["kv_block_size"] = args.kv_block_size
    if args.kv_pool_blocks is not None:
        overrides["kv_pool_blocks"] = args.kv_pool_blocks
    if args.decode_tar_buckets:
        overrides["decode_tar_buckets"] = True
    if args.kv_dtype is not None:
        overrides["kv_dtype"] = args.kv_dtype
    if args.serve_precision is not None:
        overrides["serve_precision"] = args.serve_precision
    # serve runs ON the slot engine: the serving loop drives the engine's
    # steppable scheduler pieces, so the engine path (and its parse-time
    # fleet/paging validation) is implied by the command itself. The
    # prefix cache + in-flight dedup default ON for serve — repeated
    # traffic is the serving regime they exist for — with --prefix-cache
    # off as the byte-identical equivalence comparator.
    if args.command == "serve":
        overrides["decode_engine"] = True
        if args.prefix_cache is None:
            overrides["prefix_cache"] = True
    if args.ingest_workers is not None:
        overrides["ingest_workers"] = args.ingest_workers
    if args.ingest_truncate is not None:
        overrides["ingest_truncate"] = args.ingest_truncate
    if args.ingest_cache is not None:
        overrides["ingest_cache"] = args.ingest_cache == "on"
    if args.ingest_cache_entries is not None:
        overrides["ingest_cache_entries"] = args.ingest_cache_entries
    if args.ingest_cache_bytes is not None:
        overrides["ingest_cache_bytes"] = args.ingest_cache_bytes
    if args.ingest_exec is not None:
        overrides["ingest_exec"] = args.ingest_exec
    if args.prefix_cache is not None:
        overrides["prefix_cache"] = args.prefix_cache == "on"
    if args.prefix_cache_entries is not None:
        overrides["prefix_cache_entries"] = args.prefix_cache_entries
    if args.prefix_cache_bytes is not None:
        overrides["prefix_cache_bytes"] = args.prefix_cache_bytes
    if args.serve_rate is not None:
        overrides["serve_rate"] = args.serve_rate
    if args.serve_prefill_budget is not None:
        overrides["serve_prefill_budget"] = args.serve_prefill_budget
    if args.serve_deadline_steps is not None:
        overrides["serve_deadline_steps"] = args.serve_deadline_steps
    if args.serve_queue_cap is not None:
        overrides["serve_queue_cap"] = args.serve_queue_cap
    if args.serve_tiers is not None:
        overrides["serve_tiers"] = args.serve_tiers
    if args.prefill_workers is not None:
        overrides["prefill_workers"] = args.prefill_workers
    if args.serve_artifact_budget_mb is not None:
        overrides["serve_artifact_budget_mb"] = args.serve_artifact_budget_mb
    if args.inject_faults is not None:
        overrides["inject_faults"] = args.inject_faults
    if args.dispatch_watchdog_s is not None:
        overrides["dispatch_watchdog_s"] = args.dispatch_watchdog_s
    if args.robust_retries is not None:
        overrides["robust_retries"] = args.robust_retries
    if args.max_respawns is not None:
        overrides["max_respawns"] = args.max_respawns
    if args.engine_spares is not None:
        overrides["engine_spares"] = args.engine_spares
    if args.respawn_backoff_s is not None:
        overrides["respawn_backoff_s"] = args.respawn_backoff_s
    if args.adjacency:
        overrides["adjacency_impl"] = args.adjacency
    if args.encoder_buffer:
        overrides["encoder_buffer"] = args.encoder_buffer
    if args.copy_head:
        overrides["copy_head_impl"] = args.copy_head
    if args.seq_shards is not None:
        overrides["seq_shards"] = args.seq_shards
    if args.fused_steps is not None:
        overrides["fused_steps"] = args.fused_steps
    if args.accum_steps is not None:
        overrides["accum_steps"] = args.accum_steps
    if args.rng_impl is not None:
        overrides["rng_impl"] = args.rng_impl
    if args.sort_edges:
        overrides["sort_edges"] = True
    if args.typed_edges:
        overrides["typed_edges"] = True
    # --accum-steps conflicts with the production preset's fused device
    # loop (mutually exclusive by config contract); an accum request —
    # whether from the CLI or baked into the named config/preset — drops
    # fused_steps unless the user pinned it explicitly
    effective_accum = overrides.get("accum_steps", cfg.accum_steps)
    if (effective_accum > 1 and cfg.fused_steps > 1
            and "fused_steps" not in overrides):
        overrides["fused_steps"] = 1
    return cfg.replace(**overrides) if overrides else cfg


def _make_mesh(spec: Optional[str]):
    from fira_tpu.parallel import mesh as pmesh

    if spec is None:
        import jax

        n = len(jax.devices())
        return pmesh.make_mesh(n_data=n) if n > 1 else None
    dp, tp = (int(x) for x in spec.lower().split("x"))
    return pmesh.make_mesh(n_data=dp, n_model=tp)


def _load_var_maps(data_dir: str) -> Optional[List[dict]]:
    path = os.path.join(data_dir, "variable.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if args.synthetic:
        from fira_tpu.data.synthetic import write_corpus_dir

        os.makedirs(args.data_dir, exist_ok=True)
        write_corpus_dir(args.data_dir, n_commits=args.synthetic)
        print(f"synthetic corpus: {args.synthetic} commits -> {args.data_dir}")

    if args.command == "preprocess":
        try:
            from fira_tpu.preprocess.pipeline import main as preprocess_main
        except ImportError:
            print("the preprocessing pipeline is not available in this build",
                  file=sys.stderr)
            return 1
        return preprocess_main(args)

    # one compile-cache rule for every entry point (utils/startup.py), set
    # before anything can compile
    from fira_tpu.utils import startup

    cache_dir = startup.configure_compile_cache()
    cfg = _resolve_cfg(args)

    if cfg.arch != "fira":
        # a decoder-only architecture is admitted HERE, before anything
        # that belongs to FIRA's corpus is looked at: what it does not run
        # yet exits 2 with its name (config.arch_errors)
        from fira_tpu.config import arch_errors, config_errors
        from fira_tpu.decode.paging import paging_errors

        errs = list(dict.fromkeys(
            config_errors(cfg) + arch_errors(cfg, args.command)
            + paging_errors(cfg)))
        if errs:
            for e in errs:
                print(f"parse-time validation: {e}", file=sys.stderr)
            return 2

    # Raw-diff ingest admission (docs/INGEST.md) validates BEFORE the
    # dataset loads — a missing --diff-trace or a bad knob must exit 2
    # immediately, same named-knob contract as the blocks below.
    if args.command in ("serve", "message"):
        from fira_tpu.ingest.service import ingest_errors

        ingest_errs = ingest_errors(cfg, input_mode=args.input,
                                    diff_trace=args.diff_trace,
                                    command=args.command)
        if args.command == "serve" and args.input == "diffs" \
                and (cfg.max_respawns > 0 or cfg.engine_spares > 0):
            # the raw-diff serve path has no recovery wiring yet: knobs
            # that LOOK armed but silently do nothing are worse than a
            # named rejection
            ingest_errs.append(
                "max_respawns/engine_spares support --input graphs only "
                "(the raw-diff serve path has no respawn wiring yet)")
        if args.command == "serve" and args.resume:
            # --resume admission (docs/FAULTS.md "Recovery contracts"):
            # a resume without a prior run's journal is a named exit-2
            # error, never a mid-run crash; the raw-diff path keeps no
            # journal yet, so resuming it is rejected up front too
            from fira_tpu.decode.runner import output_name as _oname

            journal = os.path.join(args.out_dir,
                                   _oname(args.ablation) + ".journal")
            if args.input == "diffs":
                ingest_errs.append(
                    "--resume supports --input graphs only (the raw-diff "
                    "serve path keeps no request journal yet)")
            elif not os.path.exists(journal):
                from fira_tpu.robust.recovery import missing_journal_error

                ingest_errs.append(missing_journal_error(journal))
        if args.command == "message":
            if not args.target:
                ingest_errs.append(
                    "message needs a diff file: cli message <diff-file>")
            elif not os.path.isfile(args.target):
                ingest_errs.append(
                    f"message target {args.target}: not a readable file")
        if ingest_errs:
            for e in ingest_errs:
                print(f"parse-time validation: {e}", file=sys.stderr)
            return 2

    # Say what this run is on BEFORE any work (and leave the same facts in
    # <out-dir>/run_info.json once the knobs are admitted): jax falls back
    # to the CPU with a log line when JAX_PLATFORMS is unset and no
    # accelerator answers, and a run that lost its chip must not read like
    # one that had it. device_info initializes the backend, so a
    # JAX_PLATFORMS naming a missing one raises here.
    info = {**startup.device_info(), "compile_cache_dir": cache_dir}
    print(startup.device_line(info), flush=True)

    def finished() -> int:
        # a run that completed adds each device's peak HBM (None where
        # the backend keeps no memory stats — the CPU)
        startup.write_run_info(args.out_dir, {
            **info, "peak_bytes_in_use": startup.peak_bytes_per_device()})
        # ... and the recorder's ring (utils/profiling.py): every span and
        # compile of the run, beside its metrics — and beside the device
        # trace they share a clock with, where train wrote one
        from fira_tpu.utils import profiling

        profiling.dump(os.path.join(args.out_dir, "spans.jsonl"))
        if args.command == "train" and args.profile_dir \
                and os.path.isdir(args.profile_dir):
            profiling.dump(os.path.join(args.profile_dir, "spans.jsonl"))
        return 0

    if cfg.arch != "fira":
        # a decoder-only architecture: no corpus, no checkpoint (token-id
        # prompts and weights from the seed) — the same engine
        # (decode/runner.run_lm_test); admitted above
        startup.write_run_info(args.out_dir, info)
        from fira_tpu.analysis import sanitizer as sanitizer_lib
        from fira_tpu.decode.runner import run_lm_test

        metrics = run_lm_test(cfg, out_dir=args.out_dir,
                              guard=sanitizer_lib.arm(args.sanitize))
        eng = metrics["engine"]
        print(f"test: {int(metrics['n'])} requests, "
              f"{eng['prompt_tokens']} prompt tokens in "
              f"{eng['prefills']} prefill dispatches, "
              f"{eng['steps_run']} positions -> {metrics['output_path']}")
        return finished()

    from fira_tpu.data.dataset import FiraDataset

    dataset = FiraDataset(args.data_dir, cfg)
    cfg = dataset.cfg

    # --buckets needs the processed split (auto reads its length
    # histograms), so it resolves after the dataset, not in _resolve_cfg
    if args.buckets and args.buckets != "off":
        from fira_tpu.data import buckets as buckets_lib

        split = dataset.splits["train" if args.command == "train" else "test"]
        if args.buckets == "auto":
            table = buckets_lib.choose_buckets(split, cfg)
        else:
            entries = []
            for entry in args.buckets.split(","):
                fields = entry.split(":")
                if len(fields) != 3 or not all(
                        f.strip().isdigit() for f in fields):
                    print(f"--buckets entry {entry!r} is not "
                          f"AST:EDGES:TAR (three integers); see "
                          f"docs/BUCKETING.md", file=sys.stderr)
                    return 2
                entries.append(tuple(int(f) for f in fields))
            table = tuple(entries)
            # range-validate against the resolved config HERE (the same
            # friendly exit the format check gets) instead of letting
            # buckets._validated raise a deep traceback mid-run
            try:
                buckets_lib.bucket_table(cfg.replace(buckets=table))
            except ValueError as e:
                print(f"--buckets invalid: {e}; see docs/BUCKETING.md",
                      file=sys.stderr)
                return 2
        cfg = cfg.replace(buckets=table)
        print(f"buckets: {', '.join(f'{a}:{e}:{t}' for a, e, t in table)} "
              f"(+ full fallback)")

    # Mesh / fleet divisibility validates HERE, at parse time (exit 2,
    # named-bucket messages) — not as a mid-run XLA reshape error deep in
    # the first epoch (docs/MULTICHIP.md).
    from fira_tpu.parallel import mesh as pmesh

    mesh = _make_mesh(args.mesh) if args.command == "train" else None
    # core train-knob admission (epochs, fused/accum device-loop axes,
    # ring seq shards) — same exit-2 contract, config.config_errors
    from fira_tpu.config import config_errors

    errs = list(config_errors(cfg))
    errs += pmesh.divisibility_errors(
        cfg, mesh.shape[pmesh.DATA_AXIS] if mesh is not None else 1)
    if cfg.decode_engine:
        from fira_tpu.parallel.fleet import fleet_divisibility_errors

        errs += fleet_divisibility_errors(cfg)
        # paged-KV knob admission (block size tiles every decode tar
        # budget, pool floors per replica) — same exit-2 contract,
        # decode/paging.paging_errors
        from fira_tpu.decode.paging import paging_errors

        errs += paging_errors(cfg)
    # prefix-cache knob admission (engine path required, LRU capacity
    # >= 1) — same exit-2 contract, decode/paging.prefix_cache_errors;
    # runs UNGATED so `--prefix-cache on` without --engine gets the
    # named message instead of a silent no-op
    from fira_tpu.decode.paging import prefix_cache_errors

    errs += prefix_cache_errors(cfg)
    # speculative-decode knob admission (tier name, draft length vs the
    # smallest declared decode tar budget, engine path required) — same
    # exit-2 contract, decode/spec.spec_errors; UNGATED for the same
    # reason: `--spec-decode copy` without --engine names the missing
    # knob instead of silently decoding plain
    from fira_tpu.decode.spec import spec_errors

    errs += spec_errors(cfg)
    # low-precision serving-tier admission (kv_dtype / serve_precision
    # names, engine path required, training-path rejection) — same exit-2
    # contract, decode/quant.quant_errors; UNGATED so `--kv-dtype bf16`
    # without --engine (or on train) names the conflict instead of
    # silently serving full precision
    from fira_tpu.decode.quant import quant_errors

    errs += quant_errors(cfg, train=args.command == "train")
    if args.command == "serve":
        # serving knob admission (offered rate, prefill budget vs slots,
        # deadline floor, queue bound) — same exit-2 contract,
        # serve.server.serve_errors
        from fira_tpu.serve.server import serve_errors

        errs += serve_errors(cfg, trace=args.serve_trace is not None)
        # disaggregated-tier knob admission (topology name, pool width,
        # in-flight artifact budget, the prefix-cache/decode-engine
        # requirements) — same exit-2 contract,
        # serve.disagg.disagg_errors
        from fira_tpu.serve.disagg import disagg_errors

        errs += disagg_errors(cfg, platform=info["platform"])
    # robustness knob admission (fault-spec grammar, watchdog timeout,
    # quarantine retry count) — same exit-2 contract, every command
    # (the watchdog also guards train's dev gates) —
    # robust.faults.robust_errors
    from fira_tpu.robust.faults import robust_errors

    errs += robust_errors(cfg)
    # self-healing knob admission (spare count, respawn budget, backoff
    # base) — same exit-2 contract, robust.recovery.recovery_errors
    from fira_tpu.robust.recovery import recovery_errors

    errs += recovery_errors(cfg)
    if errs:
        for e in errs:
            print(f"parse-time validation: {e}", file=sys.stderr)
        return 2

    startup.write_run_info(args.out_dir, info)
    var_maps = _load_var_maps(args.data_dir)
    suffix = f"_{args.ablation}" if args.ablation else ""
    ckpt_dir = args.ckpt_dir or os.path.join(args.out_dir, f"ckpt{suffix}")

    # --sanitize: process-lifetime arming is correct here and ONLY here —
    # the CLI process dies with the run (library callers use the
    # sanitizer.sanitize() context manager instead, which restores config)
    from fira_tpu.analysis import sanitizer as sanitizer_lib

    guard = sanitizer_lib.arm(args.sanitize)

    if args.command == "train":
        from fira_tpu.train.loop import train

        result = train(
            dataset, cfg, mesh=mesh, out_dir=args.out_dir,
            ckpt_dir=ckpt_dir, epochs=args.epochs, var_maps=var_maps,
            resume=not args.no_resume, profile_dir=args.profile_dir,
            guard=guard,
        )
        print(f"best dev bleu: {result.best_bleu:.4f}  "
              f"throughput: {result.commits_per_sec_per_chip:.1f} "
              f"commits/sec/chip  "
              f"feed_stall_frac: {result.feed_stall_frac:.3f}")
        return finished()

    # test/serve: load best params, beam-decode, write OUTPUT file
    import jax

    from fira_tpu.decode.runner import output_name, run_test
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train.state import CheckpointManager, init_state
    from fira_tpu.data.batching import make_batch
    import numpy as np

    ckpt = CheckpointManager(ckpt_dir)
    use_best = ckpt.has(CheckpointManager.BEST)
    if not use_best and not ckpt.has(CheckpointManager.LATEST):
        print(f"no checkpoint under {ckpt_dir}; train first", file=sys.stderr)
        return 1
    import jax.numpy as jnp

    # honor --dtype for decode too, not just training (params stay f32)
    model = FiraModel(cfg, dtype=jnp.dtype(cfg.compute_dtype))
    split = dataset.splits["test"]
    sample = make_batch(split, np.arange(min(cfg.test_batch_size, len(split))),
                        cfg, batch_size=cfg.test_batch_size)
    template = init_state(model, cfg, sample)
    if use_best:
        params = ckpt.restore_best(template.params)
    else:
        # the dev gate saves best only on STRICT improvement (reference
        # run_model.py:94-96), so a short run whose dev BLEU never left 0.0
        # has no best yet — decode the latest state instead of refusing
        print("no best checkpoint (dev BLEU never improved); "
              "decoding the LATEST training state", file=sys.stderr)
        params = ckpt.restore_latest(template)[0].params

    if args.command == "message":
        # one-shot diff-in / message-out (docs/INGEST.md): ingest the
        # target diff, run the batched beam on its single-row payload,
        # print the cooked message — the smallest raw-diff path
        from fira_tpu.ingest.difftext import DiffParseError
        from fira_tpu.ingest.service import IngestError, one_shot_message

        try:
            with open(args.target) as f:
                text = f.read()
            print(one_shot_message(model, params, dataset.word_vocab,
                                   dataset.ast_change_vocab, cfg, text))
        except (DiffParseError, IngestError, UnicodeDecodeError,
                OSError) as e:
            # a request-content failure, named like every other rejected
            # input (the serve path records-and-sheds the same errors)
            print(f"message: {args.target} rejected: {e}", file=sys.stderr)
            return 1
        return finished()

    if args.command == "serve":
        from fira_tpu.serve import poisson_times, read_trace, serve_split

        if args.input == "diffs":
            from fira_tpu.ingest.difftext import read_diff_trace

            requests = read_diff_trace(args.diff_trace)
            n_req = len(requests)
        else:
            n_req = len(split)
        if args.serve_trace:
            times = read_trace(args.serve_trace)
            if len(times) > n_req:
                print(f"parse-time validation: --serve-trace has "
                      f"{len(times)} arrivals but the request source "
                      f"holds only {n_req} "
                      f"{'diffs' if args.input == 'diffs' else 'samples'}",
                      file=sys.stderr)
                return 2
        else:
            times = poisson_times(n_req, cfg.serve_rate, seed=cfg.seed)
        # serve_split maintains serve_metrics.json itself: a .partial
        # snapshot refreshes atomically through the run (a kill leaves a
        # recent valid-JSON artifact) and the final file is written
        # atomically at completion — the ordered writer's crash contract
        # applied to metrics (docs/FAULTS.md)
        metrics_path = os.path.join(args.out_dir, "serve_metrics.json")
        # write-ahead request journal (robust/recovery.py): every graphs
        # serve run keeps one next to its output, so ANY run is
        # resumable after a hard kill; --resume additionally validates
        # the journal pins the SAME request stream (count + arrival
        # digest) before anything heavy is built
        journal_path = os.path.join(args.out_dir,
                                    output_name(args.ablation) + ".journal")
        if args.input == "diffs":
            from fira_tpu.ingest.service import serve_diffs

            metrics = serve_diffs(model, params, dataset.word_vocab,
                                  dataset.ast_change_vocab, cfg,
                                  requests=requests[: len(times)],
                                  arrival_times=times,
                                  out_dir=args.out_dir,
                                  ablation=args.ablation, guard=guard,
                                  clock=args.serve_clock,
                                  metrics_path=metrics_path)
        else:
            from fira_tpu.robust.recovery import ResumeError

            try:
                metrics = serve_split(model, params, dataset, cfg,
                                      arrival_times=times,
                                      out_dir=args.out_dir,
                                      ablation=args.ablation,
                                      var_maps=var_maps,
                                      guard=guard, clock=args.serve_clock,
                                      metrics_path=metrics_path,
                                      journal_path=journal_path,
                                      resume=args.resume)
            except ResumeError as e:
                # resume admission (stream count / arrival digest /
                # request-mix digest — robust.recovery.resume_errors,
                # the ONE validation site) rejected the journal: the
                # named exit-2 contract, not a traceback. Any other
                # mid-run error propagates as the crash it is.
                print(f"parse-time validation: {e}", file=sys.stderr)
                return 2
        sv = metrics["serve"]
        resumed = (f", {sv['resumed']} resumed from journal"
                   if sv.get("resumed") else "")
        print(f"serve: {sv['completed']}/{sv['offered']} completed "
              f"(shed {sv['shed_queue_full']} queue-full, "
              f"{sv['shed_deadline']} deadline, "
              f"{sv['shed_error']} error; "
              f"{sv['replica_retirements']} replica retirements, "
              f"{sv['respawns']} respawns{resumed})  "
              f"p50/p99 ttft {sv['p50_ttft_s']}/{sv['p99_ttft_s']} s  "
              f"p50/p99 e2e {sv['p50_e2e_s']}/{sv['p99_e2e_s']} s  "
              f"-> {metrics_path}")
        if "ingest" in sv:
            ing = sv["ingest"]
            print(f"ingest: {ing['requests_ingested']} requests "
                  f"({ing['truncated']} truncated, {ing['degraded']} "
                  f"degraded)  p50 ingest {ing['p50_total_s']} s  "
                  f"ingest_stall_frac {ing['stall_frac']}")
        return finished()

    metrics = run_test(model, params, dataset, cfg, out_dir=args.out_dir,
                       ablation=args.ablation, var_maps=var_maps,
                       guard=guard)
    print(f"test sentence-bleu: {metrics['sentence_bleu']:.4f} "
          f"({int(metrics['n'])} commits) -> "
          f"{os.path.join(args.out_dir, output_name(args.ablation))}")
    return finished()


if __name__ == "__main__":
    raise SystemExit(main())
