"""Benchmark: training throughput in commits/sec/chip (the repo's metric of
record, BASELINE.md) on the flagship fira-full geometry.

Prints ONE JSON line to stdout in every outcome:
  success -> {"metric", "value", "unit", "vs_baseline", "mfu",
              "value_basis": "compute", ...}
  failure -> {"metric", "value": null, "unit", "vs_baseline": null, "error", ...}

Kill-contract: the driver that wraps this script parses the LAST JSON line
of stdout and may SIGKILL the process at any time. The orchestrator
therefore prints (and flushes) a structured status record — same shape as
the failure record, plus "in_progress": true — at startup and after the
probe, and the worker's stdout passes straight through so its final record
is driver-visible the moment it exists. Whenever the process dies, the
stdout tail is a parseable record (tests/test_bench_killcontract.py).

Three roles, so that the process that owns the driver-visible stdout never
imports jax (an accelerator belongs to one process at a time):

  orchestrator (default)  runs one bounded-timeout PROBE subprocess, then
                          one bounded-timeout WORKER subprocess; either
                          failing ends in the structured failure record and
                          a non-zero exit.
  --probe                 imports jax, forces device init (jax.devices()),
                          prints the platform/device_kind, exits.
  --worker                the actual measurement (below). It starts no
                          child process: it holds the chip.

What is measured: jitted train steps (forward + loss + backward + Adam) at
the reference's exact model geometry — d=256, 6 GCN rounds over 650-node
graphs, 6 decoder layers, dual copy head, 24,650-word fused output
(/root/reference/Model.py:81) — per-chip batch 170 (run_model.py:40).
Two timings are reported:
  value / compute_step_time_s    batches device-resident: the chip-side
                                 number and the METRIC OF RECORD
                                 (commits/sec/CHIP). MFU is computed
                                 against this timing.
  value_e2e_host_link / step_time_s
                                 end to end: batches ASSEMBLED (make_batch)
                                 and transferred through the async Feeder
                                 (data.feeder, the same pipeline
                                 train/loop.py uses — assembly + H2D on
                                 background workers, docs/PIPELINE.md), H2D
                                 included. feed_stall_frac rides along: the
                                 share of the e2e window the consumer spent
                                 blocked on the feed, with
                                 feed_stall_frac_sync_assembly as the
                                 synchronous-assembly (num_workers=0)
                                 control leg measured the same way. Not yet
                                 measured on this machine's host link
                                 (ROADMAP S5 decides which of the two is
                                 the number of record).
Each timed window ends by MATERIALIZING the last loss on the host
(``float(loss)``), which waits for every step the window dispatched;
``chip_smoke.py`` times the same dispatch ended both ways (PERF.md
"Bring-up").

vs_baseline: the reference publishes no throughput numbers (SURVEY.md §6).
The denominator is an estimate of the reference stack's training rate on its
own 4-GPU rig (2x RTX 3090 + 2x TITAN RTX, batch 170/GPU): per batch-680
step it must densify + ship 680 x 650^2 x 4 B ~= 1.15 GB of adjacency over
PCIe (~95 ms floor at 12 GB/s) plus the DataParallel scatter/gather and the
~20M-param fp32 forward/backward; a 0.5 s step (optimistic for that stack)
gives 680/0.5/4 = 340 commits/sec/chip. We use 340 — the optimistic end, so
vs_baseline understates rather than oversells the speedup.

mfu: analytic model FLOPs/step (MXU terms from the model geometry — the
numerator of record, see _analytic_flops; 2.03e12 at fira-full/170) /
compute-only step time / chip peak FLOPs for the benchmark dtype.  XLA's
compiled cost analysis rides along as flops_per_step_xla (it also counts
compiler-generated work, so it slightly overstates model FLOPs).  Peak is
looked up from device_kind in PEAK_BF16_FLOPS — exact match, an unknown
device is an error; flops_per_step and peak_flops are reported alongside so
the number is auditable.

Any leg that runs and raises ends the worker with a traceback and a
non-zero exit: a record with a value is a record in which everything that
was asked for ran.

Env knobs: FIRA_BENCH_DTYPE=float32|bfloat16 (default bfloat16, the TPU fast
path; quality parity is validated in f32 by the test suite),
FIRA_BENCH_STEPS, FIRA_BENCH_BATCH, FIRA_BENCH_WINDOWS,
FIRA_BENCH_PROBE_TIMEOUT (s, default 90), FIRA_BENCH_WORKER_TIMEOUT (s,
default 1500),
FIRA_BENCH_ALLOW_CPU=1 (let the worker run on CPU — for harness testing
only; the result is flagged "platform": "cpu" and carries no mfu),
FIRA_BENCH_PRODUCTION_KNOBS (JSON FiraConfig fields applied by default —
the stacked production config: rbg dropout PRNG, fused_steps=8 device loop,
sorted scatters, bf16 residual streams, no copy-head remat (docs/PERF.md
round-4 table); '{}' benches the parity-default knobs),
FIRA_BENCH_OVERRIDES (JSON FiraConfig fields, wins over both),
FIRA_BENCH_COMPOSED=0 (skip the composed leg), FIRA_BENCH_COMPOSED_DATA
(corpus size for the composed leg; default 3*K*batch so each auto bucket
can fill K-groups),
FIRA_BENCH_DECODE_ENGINE=1 (opt-in decode leg: slot-refill continuous-
batching engine vs the batched early-exit beam on the same 3-batch
eos-biased stream — decode/engine.py),
FIRA_BENCH_DECODE_EOS_DELTA (default 4.75 — the mixed-settle EOS bias of
that leg's paramset),
FIRA_BENCH_SPEC=1 (opt-in speculative-decode leg: draft-and-verify spec
decode vs the plain engine twin at EQUAL geometry on the same 3-batch
eos-biased stream — decode/spec.py, docs/DECODE_ENGINE.md "Speculative
drafting" — per-position tokens asserted identical inside the leg;
FIRA_BENCH_SPEC_TIER=draft|copy and FIRA_BENCH_SPEC_K pick the drafter;
the full CPU artifact lands in docs/SPEC_BENCH_r01.jsonl via
scripts/tpu_decode_bench.py),
FIRA_BENCH_QUANT=1 (opt-in low-precision-tier leg: the bf16 KV arena +
int8 weight tier vs the f32 engine twin at EQUAL geometry on the same
3-batch eos-biased stream — decode/quant.py, docs/DECODE_ENGINE.md
"Low-precision tiers" — token-match fraction RECORDED, not asserted:
cross-tier drift is the quantity under test, next to the machine-
recorded kv_bytes_per_slot halving; FIRA_BENCH_QUANT_KV=f32|bf16 and
FIRA_BENCH_QUANT_PRECISION=f32|bf16|int8w pick the tier; the full CPU
artifact lands in docs/QUANT_BENCH_r01.jsonl via
scripts/serve_bench.py --quant).

The CPU-only studies — scripts/multichip_bench.py, scripts/serve_bench.py
(rate sweep, --cache, --ingest, --disagg) and scripts/chaos_bench.py —
force the CPU backend and write their own artifacts under docs/; run them
directly. Their rows are not folded into this record: it is stamped with
the worker's device, and theirs is another.

Composed leg — the production path going forward (ISSUE 4): the stacked
knobs AND the auto bucket table together. One shuffled epoch plan of
bucket-HOMOGENEOUS K-groups (data/grouping.py) runs device-resident
through the per-(geometry, K) program family; the record carries
``value_composed`` plus dispatch-count and padding_frac accounting
(``composed.{dispatches,grouped_dispatches,per_step_dispatches,
steps_dispatched,commits,padding_frac_dispatched}``), so every bench
artifact prices what grouping + bucketing actually dispatched. ``value``
stays the single-geometry compute leg for cross-round ledger continuity.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

EST_BASELINE_COMMITS_PER_SEC_PER_CHIP = 340.0
METRIC = "train_commits_per_sec_per_chip"
UNIT = "commits/sec/chip"

# bf16 peak FLOP/s per chip, keyed by the exact ``device_kind`` jax reports.
# One row per device this benchmark has run on, with its source; a device
# that is not here is an error, not a default. fp32 peaks are ~= bf16/2 (no
# separate fp32 MXU path — XLA upcasts around the same systolic array), so
# float32 runs halve the figure.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def _load_torch_anchor() -> dict | None:
    """TORCH_ANCHOR.json (written by scripts/torch_anchor.py next to
    BASELINE.json): the MEASURED reference-stack denominator for this host.
    vs_baseline keeps the estimated 340 c/s/chip for cross-round
    comparability; when the measured anchor exists it rides along as
    vs_torch_anchor so perf claims stop resting on an estimate alone."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "TORCH_ANCHOR.json")
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    return rec if rec.get("commits_per_sec_per_chip") else None


def _peak_flops(device_kind: str, dtype: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
            f"it to bench.PEAK_BF16_FLOPS with its source (known: "
            f"{sorted(PEAK_BF16_FLOPS)})")
    return PEAK_BF16_FLOPS[device_kind] / (2.0 if dtype == "float32" else 1.0)


# --------------------------------------------------------------------------
# probe: force backend init, report what answered
# --------------------------------------------------------------------------

def _maybe_force_cpu() -> None:
    # harness-test mode: the whole orchestrator->probe->worker path on CPU
    if os.environ.get("FIRA_BENCH_ALLOW_CPU") == "1":
        from fira_tpu.utils.startup import force_cpu_backend

        force_cpu_backend()


def probe() -> None:
    # Test hook for the kill-contract suite (tests/test_bench_killcontract.py):
    # simulate a hung backend init without touching any backend.
    hang = os.environ.get("FIRA_BENCH_TEST_HANG_S")
    if hang:
        time.sleep(float(hang))
    _maybe_force_cpu()
    from fira_tpu.utils.startup import device_info

    # raises here if JAX_PLATFORMS names a backend the machine lacks
    print(json.dumps(device_info()))


# --------------------------------------------------------------------------
# worker: the measurement itself
# --------------------------------------------------------------------------

def _flops_per_step(compiled) -> float | None:
    """XLA's compiled cost analysis (a dict on jax 0.9); None when the
    backend reports no flops."""
    flops = float((compiled.cost_analysis() or {}).get("flops", 0.0))
    return flops if flops > 0 else None


def _analytic_flops(cfg, batch_size: int) -> float:
    """Model-FLOPs estimate for one fwd+bwd+opt step (bwd ~= 2x fwd for
    param matmuls). Counts only the MXU terms (dense projections + attention
    + fused output head); elementwise and normalization terms are noise next
    to them. This is the MFU numerator of record because it is auditable
    from the model geometry alone — MFU's definition wants the model's
    theoretical FLOPs, whereas XLA's cost_analysis() also counts
    compiler-generated work (scatters, remat recomputation), which inflates
    utilization. At fira-full/170 this count is 2.03e12 vs XLA's 2.15e12 —
    close, as they should be. (Round 3's 1.62e12 undercounted: it omitted
    the Combination projections and priced decoder cross K/V at t instead
    of s; the ~6% MFU it reported is really ~10% under the correct count.)
    The XLA figure is reported alongside as flops_per_step_xla. The A.x
    adjacency term is only MXU work on the dense path; the COO path does it
    with segment-sums (VPU), so it drops out of model FLOPs there.
    """
    d = cfg.embedding_dim
    g, s, t, v = (cfg.graph_len, cfg.sou_len + cfg.sub_token_len, cfg.tar_len,
                  cfg.output_vocab_size)
    adj = g * g * d * 2 if cfg.adjacency_impl == "dense" else 0
    enc = cfg.num_layers * (
        4 * cfg.sou_len * d * d * 2    # Combination q/k/v/out projections
        + 2 * g * d * d * 2            # GCN fc1/fc2
    )
    dec = cfg.num_layers * (
        # self-attn q/k/v/o over t, cross-attn q/o over t, cross k/v over
        # the s-long encoder states (NOT t — undercounting this term by s/t
        # was how round 3 reported MFU ~6% when the true figure is ~10%)
        (6 * t + 2 * s) * d * d * 2
        + 2 * (t * t + t * s) * d * 2   # score + mix matmuls
        + 2 * t * d * 4 * d * 2         # FFN in/out
    )
    head = (t * d * v * 2               # fused out_fc
            + s * d * d * 2 + t * d * d * 2   # copy src/tgt projections
            + t * s * d * 2)            # tanh-score contraction
    # A.x backward is dx = A^T.dout only — the adjacency is batch data with
    # no gradient — so that term runs at 2x fwd, not the 3x of param matmuls
    return (3.0 * batch_size * (enc + dec + head)
            + 2.0 * batch_size * cfg.num_layers * adj)


def _emit_worker(record: dict) -> None:
    """Print the worker's one JSON line AND mirror it to the side file the
    orchestrator reads (FIRA_BENCH_RESULT_FILE). The orchestrator runs the
    worker with stdout passed straight through to its own stdout, so this
    line lands on the driver-visible stream the instant it is produced —
    killing the orchestrator after this point can no longer lose the
    result."""
    line = json.dumps(record)
    print(line, flush=True)
    rf = os.environ.get("FIRA_BENCH_RESULT_FILE")
    if rf:
        try:
            with open(rf, "w") as f:
                f.write(line + "\n")
        except OSError as e:  # pragma: no cover - side channel only
            print(f"result file write failed: {e}", file=sys.stderr)


def worker() -> None:
    _maybe_force_cpu()
    import jax
    import numpy as np

    from fira_tpu.config import PRODUCTION_PERF_KNOBS, get_config
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.synthetic import make_memory_split
    from fira_tpu.model.model import FiraModel
    from fira_tpu.train import step as step_lib
    from fira_tpu.train.state import init_state

    from fira_tpu.utils.startup import configure_compile_cache

    configure_compile_cache()

    # Trigger device init FIRST: fail fast, before any batch building, and
    # record what we're running on.
    devs = jax.devices()
    platform = devs[0].platform
    device_kind = devs[0].device_kind
    if platform != "tpu" and os.environ.get("FIRA_BENCH_ALLOW_CPU") != "1":
        _emit_worker({
            "metric": METRIC, "value": None, "unit": UNIT,
            "vs_baseline": None,
            "error": f"no TPU backend (got platform={platform!r}); "
                     "set FIRA_BENCH_ALLOW_CPU=1 to bench anyway",
        })
        sys.exit(1)

    dtype = os.environ.get("FIRA_BENCH_DTYPE", "bfloat16")
    n_steps = int(os.environ.get("FIRA_BENCH_STEPS", "20"))
    # FIRA_BENCH_CONFIG: the official number is fira-full; fira-tiny exists
    # for the CPU harness test (tests/test_bench_harness.py) which drives
    # the whole orchestrator->probe->worker->JSON path in seconds.
    cfg_name = os.environ.get("FIRA_BENCH_CONFIG", "fira-full")
    cfg0 = get_config(cfg_name)
    batch_size = int(os.environ.get("FIRA_BENCH_BATCH",
                                    str(cfg0.batch_size)))

    cfg = cfg0.replace(batch_size=batch_size, compute_dtype=dtype)
    # Production performance knobs, ON by default: the stacked config from
    # the round-4 ablation (docs/PERF.md, the builders' 2026-07-31 window).
    # Every knob is equivalence-tested; the fira-full preset itself keeps
    # parity defaults for training runs. FIRA_BENCH_PRODUCTION_KNOBS replaces the set
    # ('{}' benches the parity defaults); FIRA_BENCH_OVERRIDES wins over
    # both.
    knobs_env = os.environ.get("FIRA_BENCH_PRODUCTION_KNOBS")
    production_knobs = (json.loads(knobs_env) if knobs_env is not None
                        else dict(PRODUCTION_PERF_KNOBS))
    if production_knobs:
        cfg = cfg.replace(**production_knobs)
    # FIRA_BENCH_OVERRIDES: JSON dict of FiraConfig fields, e.g.
    # '{"rng_impl": "rbg", "sort_edges": true}' — for measuring the
    # optimization knobs without editing presets; echoed in the result.
    overrides = json.loads(os.environ.get("FIRA_BENCH_OVERRIDES", "{}"))
    if overrides:
        cfg = cfg.replace(**overrides)

    # synthetic corpus; at the flagship geometry vocabs pad to the
    # reference's 24,650 words / 71 labels so the fused 25,020-way output
    # costs what the real run costs. The composed leg needs enough samples
    # for each auto bucket to fill K-groups of full batches, so the corpus
    # grows to 3*K*batch (three auto buckets) when that leg is on — ONE
    # corpus serves every leg (a second one could drift the synthetic
    # vocab away from the params' embedding tables).
    n_data = int(os.environ.get("FIRA_BENCH_DATA", "512"))
    run_composed = os.environ.get("FIRA_BENCH_COMPOSED", "1") != "0"
    if run_composed:
        n_data = max(n_data, int(os.environ.get(
            "FIRA_BENCH_COMPOSED_DATA",
            str(3 * max(1, cfg.fused_steps) * batch_size))))
    pad_vocab = 24650 if cfg_name == "fira-full" else 0
    cfg, split, _ = make_memory_split(
        cfg, n_data, seed=0, pad_vocab_to=pad_vocab,
        pad_ast_vocab_to=71 if pad_vocab else 0)

    # Padded-FLOP accounting rides along with every bench record (the
    # bucket subsystem's motivating metric, docs/BUCKETING.md): how much of
    # the single-geometry cost is pad multiplication on this corpus, and
    # what the auto-chosen bucket table would leave. Measurement of the
    # bucketed assembly/step path itself lives in scripts/bucket_bench.py.
    from fira_tpu.data import buckets as buckets_lib

    pad_report = buckets_lib.padding_report(
        split, cfg, buckets_lib.bucket_table(
            cfg.replace(buckets=buckets_lib.choose_buckets(split, cfg))))

    rng = np.random.RandomState(0)
    # K>1 = the production device loop (one dispatch runs K steps via
    # lax.scan). The timed feeds rotate two K-stacked groups, so build 2*K
    # distinct base batches — otherwise the groups would alias the same
    # data. Index sets are kept so the e2e feeder leg re-assembles the
    # byte-identical batches from scratch on its workers.
    K = max(1, cfg.fused_steps)
    n_base = max(4, 2 * K)
    base_indices = [rng.choice(n_data, batch_size, replace=True)
                    for _ in range(n_base)]
    host_batches = [make_batch(split, ix, cfg) for ix in base_indices]

    import jax.numpy as jnp

    model = FiraModel(cfg, dtype=jnp.dtype(dtype))
    state = init_state(model, cfg, host_batches[0])
    # Stack host batches in groups of K on a leading axis for
    # make_multi_step (step-identical to K single dispatches, pinned by
    # tests); every timing below is divided by real steps run.
    if K > 1:
        host_groups = [
            step_lib.stack_batches(
                [host_batches[(g * K + i) % len(host_batches)]
                 for i in range(K)])
            for g in range(2)
        ]
    else:
        host_groups = host_batches
    # AOT-compile once and reuse the executable for the timed loop: going
    # through jit dispatch after lower().compile() would trace+compile the
    # whole program a second time (the AOT result does not populate the jit
    # cache), doubling startup inside the worker timeout.
    step_maker = step_lib.make_multi_step if K > 1 else step_lib.make_train_step
    train_step = jax.jit(step_maker(model, cfg),
                         donate_argnums=(0,)
                         ).lower(state, host_groups[0]).compile()

    # Analytic MXU count is the MFU numerator of record (see _analytic_flops
    # docstring: XLA's cost_analysis overcounts); XLA's figure rides along
    # for the audit trail (normalized to one step when K>1).
    flops = _analytic_flops(cfg, batch_size)
    flops_source = "analytic_mxu"
    flops_xla = _flops_per_step(train_step)
    if flops_xla and K > 1:
        flops_xla = flops_xla / K

    # warmup (transfers + executable load)
    state, metrics = train_step(state, host_groups[0])
    jax.block_until_ready(metrics["loss"])

    # Median of steady-state windows, each ended by MATERIALIZING its last
    # loss (float() is a D2H copy of computed data, so it waits for every
    # step the window dispatched). The first window is a throwaway: it
    # absorbs executable load and pipeline fill.
    n_windows = max(1, int(os.environ.get("FIRA_BENCH_WINDOWS", "5")))

    state_box = [state]

    from fira_tpu.data.feeder import Feeder

    def timed_windows(feed) -> float:
        """Median steady-state seconds per window; `feed(w)` yields the w-th
        window's batch iterator."""
        times = []
        for w in range(n_windows + 1):
            batches = feed(w)
            t0 = time.perf_counter()
            for b in batches:
                state_box[0], m = train_step(state_box[0], b)
            # K>1 returns per-step losses; sync on (and check) the last one
            loss = float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])
            times.append(time.perf_counter() - t0)
            if not math.isfinite(loss):  # a broken step must not bench
                raise RuntimeError(f"non-finite loss {loss} in window {w}")
        steady = sorted(times[1:])  # drop the queue-fill window
        return steady[len(steady) // 2]

    # n_steps is the per-window step target; with K>1 each call runs K
    # steps, so a window runs n_calls dispatches = n_calls*K real steps —
    # i.e. FIRA_BENCH_STEPS is rounded down to a multiple of K with a floor
    # of one dispatch: a window always runs at least K real steps. Size
    # FIRA_BENCH_WORKER_TIMEOUT for K steps/window minimum (or drop
    # fused_steps via FIRA_BENCH_PRODUCTION_KNOBS/OVERRIDES).
    n_calls = max(1, n_steps // K) if K > 1 else n_steps
    steps_per_window = n_calls * K

    # (a) compute-only: batches device-resident — the chip-side number,
    # independent of the host link.
    dev_batches = jax.device_put(host_groups)
    jax.block_until_ready(dev_batches)
    dt_compute = timed_windows(
        lambda _w: (dev_batches[i % len(dev_batches)] for i in range(n_calls)))

    # (b) end-to-end: the framework's real input pipeline (train/loop.py
    # rides the same Feeder) — each dispatch group is ASSEMBLED from
    # scratch (make_batch (+ stack)) on the feeder's workers and its
    # device_put overlaps the previous group's compute. ONE feeder persists
    # across all windows, exactly like one feeder persists across an epoch:
    # the throwaway window absorbs the pipeline fill, the steady windows
    # measure the warm pipeline. feed_stall_frac = share of steady wall
    # clock the consumer spent blocked on the feed.
    def assemble_group(g: int):
        if K > 1:
            return step_lib.stack_batches([
                make_batch(split,
                           base_indices[(g * K + i) % len(base_indices)],
                           cfg)
                for i in range(K)])
        return make_batch(split, base_indices[g % len(base_indices)], cfg)

    def timed_feeder_windows(num_workers: int):
        """(median steady window seconds, {feed_stall_frac,
        queue_depth_mean}) with stall/depth accounted over the steady
        windows only (stats deltas around the throwaway window)."""
        total_calls = (n_windows + 1) * n_calls
        tasks = ((lambda i=i: assemble_group(i % 2))
                 for i in range(total_calls))
        times = []
        with Feeder(tasks, num_workers=num_workers,
                    depth=cfg.feeder_depth) as feeder:
            stall0 = depth0 = fed0 = 0.0
            stall_s = depth_sum = fed_n = 0.0
            for w in range(n_windows + 1):
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    item = next(feeder)
                    state_box[0], m = train_step(state_box[0], item.device)
                loss = float(np.asarray(
                    jax.device_get(m["loss"])).ravel()[-1])
                times.append(time.perf_counter() - t0)
                st = feeder.stats()
                if w == 0:  # snapshot after the fill window
                    stall0 = st["feed_stall_s"]
                    depth0 = st["queue_depth_sum"]
                    fed0 = st["batches"]
                else:
                    stall_s = st["feed_stall_s"] - stall0
                    depth_sum = st["queue_depth_sum"] - depth0
                    fed_n = st["batches"] - fed0
                if not math.isfinite(loss):
                    raise RuntimeError(f"non-finite loss {loss} in window {w}")
        steady = sorted(times[1:])
        total_t = sum(times[1:])
        info = {
            "feed_stall_frac": round(min(1.0, stall_s / total_t), 4),
            "queue_depth_mean": (round(depth_sum / fed_n, 2)
                                 if fed_n else 0.0),
        }
        return steady[len(steady) // 2], info

    dt_e2e, e2e_info = timed_feeder_windows(cfg.feeder_workers)

    # (c) control leg: synchronous assembly on the consumer thread
    # (num_workers=0, the pre-feeder world) — the stall fraction the async
    # feeder must beat, measured by the same accounting.
    dt_sync, sync_info = timed_feeder_windows(0)

    # the step above is jitted without a mesh: it runs on exactly one chip
    # regardless of how many are visible
    n_chips = 1

    # (d) COMPOSED leg — stacked knobs x auto buckets, the production path
    # (ISSUE 4): one shuffled epoch of bucket-homogeneous K-groups
    # (data/grouping.py) runs device-resident through the per-(geometry, K)
    # program family — fused tails per-step, exactly what train/loop.py
    # dispatches — with dispatch-count + padded-FLOP accounting on the
    # record.
    composed = None
    if run_composed:
        from fira_tpu.data import grouping

        cfg_comp = cfg.replace(
            buckets=buckets_lib.choose_buckets(split, cfg))
        table = buckets_lib.bucket_table(cfg_comp)
        ext = buckets_lib.sample_extents(split, cfg_comp)
        plan = grouping.grouped_plan(
            split, cfg_comp, batch_size=batch_size, group_size=K,
            accum=False, shuffle=True, seed=0, epoch=0, table=table,
            assignment=buckets_lib.assign_buckets(ext, table))
        acct = grouping.plan_report(split, cfg_comp, plan,
                                    batch_size=batch_size, extents=ext)
        items = []
        for task in grouping.grouped_assembly_tasks(
                split, plan, cfg_comp, batch_size=batch_size):
            host = task()
            wire = {kk: vv for kk, vv in host.items()
                    if not kk.startswith("_")}
            items.append((jax.device_put(wire),
                          wire["valid"].ndim == 2))
        jax.block_until_ready([d for d, _ in items])
        step_comp = jax.jit(step_lib.make_train_step(model, cfg_comp),
                            donate_argnums=(0,))
        multi_comp = (jax.jit(step_lib.make_multi_step(model, cfg_comp),
                              donate_argnums=(0,))
                      if K > 1 else None)

        def composed_pass():
            m = None
            for dev_b, stacked in items:
                state_box[0], m = (multi_comp if stacked
                                   else step_comp)(state_box[0], dev_b)
            return m

        # pass 0 compiles the whole (geometry x entrypoint x K) family
        # — jit's shape cache specializes per member, like the train
        # loop's pre-warm — then steady passes are compile-free
        m = composed_pass()
        float(np.asarray(jax.device_get(m["loss"])).ravel()[-1])
        ctimes = []
        for _w in range(n_windows):
            t0 = time.perf_counter()
            m = composed_pass()
            loss = float(np.asarray(
                jax.device_get(m["loss"])).ravel()[-1])
            ctimes.append(time.perf_counter() - t0)
            if not math.isfinite(loss):
                raise RuntimeError(
                    f"non-finite loss {loss} in composed pass {_w}")
        dt_comp = sorted(ctimes)[len(ctimes) // 2]
        composed = {
            "value": round(acct["commits"] / dt_comp / n_chips, 2),
            "unit": UNIT,
            "value_basis": "compute",
            "step_time_s": round(dt_comp / acct["steps_dispatched"], 5),
            "group_size": K,
            "buckets": [buckets_lib.geom_tag(g) for g in table],
            **acct,
        }
    # (e) DECODE-ENGINE leg (opt-in: FIRA_BENCH_DECODE_ENGINE=1):
    # slot-refill continuous-batching decode (decode/engine.py) vs the
    # batched early-exit beam on the SAME
    # 3-batch stream and eos-biased paramset (mixed settle depths — the
    # realistic regime where the batch path pays the per-batch max and the
    # engine pays the mean). Reported next to the train legs so one bench
    # record carries both sides.
    # Measurement protocol (warm + stats reset + timed drive, batched twin
    # with per-batch np.asarray harvest sync) must stay in lockstep with
    # scripts/tpu_decode_bench.py's engine_row/batch_early_exit_row.
    decode_engine = None
    if os.environ.get("FIRA_BENCH_DECODE_ENGINE", "0") == "1":
        from fira_tpu.data.feeder import Feeder
        from fira_tpu.decode import engine as engine_lib
        from fira_tpu.decode.beam import (eos_biased_params,
                                          make_beam_search)

        eos_delta = float(os.environ.get(
            "FIRA_BENCH_DECODE_EOS_DELTA", "4.75"))
        cfg_dec = cfg.replace(test_batch_size=batch_size,
                              beam_kv_cache=True,
                              beam_factored_topk=False)
        params_dec = eos_biased_params(state_box[0].params,
                                       delta=eos_delta)
        dec_chunks = [rng.choice(n_data, batch_size, replace=True)
                      for _ in range(3)]
        n_dec = batch_size * len(dec_chunks)

        # both sides pay the SAME input pipeline (assembly + H2D via
        # the async Feeder, inside the timed window) — the speedup
        # compares decode strategies, not batch pre-staging
        def dec_tasks():
            for ix in dec_chunks:
                yield (lambda ix=ix: make_batch(split, ix, cfg_dec))

        cfgb = cfg_dec.replace(beam_early_exit=True)
        beam_b = make_beam_search(
            FiraModel(cfgb, dtype=jnp.dtype(dtype)), cfgb,
            with_steps=True)
        warm_b = jax.device_put(make_batch(split, dec_chunks[0], cfgb))
        jax.block_until_ready(warm_b)
        out = beam_b(params_dec, warm_b)
        _ = np.asarray(out[0])          # compile + honest sync
        t0 = time.perf_counter()
        batch_steps = 0
        with Feeder(dec_tasks(), num_workers=cfg.feeder_workers,
                    depth=cfg.feeder_depth) as dec_feed:
            for dec_item in dec_feed:
                out = beam_b(params_dec, dec_item.device)
                batch_steps += int(out[2])  # per-batch harvest sync
                _ = np.asarray(out[0])
        dt_batch = time.perf_counter() - t0

        model_dec = FiraModel(cfg_dec, dtype=jnp.dtype(dtype))
        eng = engine_lib.SlotEngine(model_dec, params_dec, cfg_dec)

        def drive():
            tasks = ((lambda ix=ix: make_batch(split, ix, cfg_dec))
                     for ix in dec_chunks)
            with Feeder(tasks, num_workers=cfg.feeder_workers,
                        depth=cfg.feeder_depth) as feed:
                for _item in eng.run(feed):
                    pass

        drive()                          # compiles prefill/step/insert
        eng.stats = engine_lib.EngineStats(slots=eng.slots)
        t0 = time.perf_counter()
        drive()
        dt_eng = time.perf_counter() - t0
        st = eng.stats.summary()
        decode_engine = {
            "value_engine": round(st["commits"] / dt_eng / n_chips, 2),
            "value_early_exit": round(n_dec / dt_batch / n_chips, 2),
            "speedup": round((st["commits"] / dt_eng)
                             / (n_dec / dt_batch), 3),
            "unit": UNIT,
            "eos_delta": eos_delta,
            "early_exit_steps_run": batch_steps,
            **{k: st[k] for k in ("slots", "slot_occupancy",
                                  "steps_run", "refills",
                                  "steps_per_commit", "dispatches",
                                  # paged-KV HBM accounting
                                  # (decode/paging.py): the machine-
                                  # recorded side of any equal-
                                  # memory claim
                                  "pool_blocks", "kv_block_size",
                                  "kv_bytes_per_slot", "peak_blocks",
                                  "pool_utilization")},
        }

    # (e2) SPECULATIVE-DECODE leg (opt-in: FIRA_BENCH_SPEC=1): the
    # draft-and-verify spec path (decode/spec.py) vs the plain engine
    # twin at EQUAL geometry on the same 3-batch eos-biased stream,
    # harvest cadence 1 on both sides so the comparison isolates
    # speculation from cadence batching. Per-position tokens are asserted
    # identical inside the leg — a speedup that costs output bytes is a
    # bug, not a result. Protocol stays in lockstep with
    # scripts/tpu_decode_bench.py's spec rows (docs/SPEC_BENCH_r01.jsonl).
    spec = None
    if os.environ.get("FIRA_BENCH_SPEC", "0") == "1":
        from fira_tpu.data.feeder import Feeder
        from fira_tpu.decode import engine as engine_lib
        from fira_tpu.decode.beam import eos_biased_params

        eos_delta = float(os.environ.get(
            "FIRA_BENCH_DECODE_EOS_DELTA", "4.75"))
        spec_tier = os.environ.get("FIRA_BENCH_SPEC_TIER", "draft")
        spec_k = int(os.environ.get("FIRA_BENCH_SPEC_K", "4"))
        cfg_spec0 = cfg.replace(test_batch_size=batch_size,
                                beam_kv_cache=True,
                                beam_factored_topk=False,
                                decode_engine=True,
                                engine_harvest_every=1)
        params_spec = eos_biased_params(state_box[0].params,
                                        delta=eos_delta)
        spec_chunks = [rng.choice(n_data, batch_size, replace=True)
                       for _ in range(3)]

        def spec_leg(cfg_leg):
            model_leg = FiraModel(cfg_leg, dtype=jnp.dtype(dtype))
            eng = engine_lib.SlotEngine(model_leg, params_spec, cfg_leg)

            def drive(collect):
                tasks = ((lambda ix=ix: make_batch(split, ix, cfg_leg))
                         for ix in spec_chunks)
                toks = {}
                with Feeder(tasks, num_workers=cfg.feeder_workers,
                            depth=cfg.feeder_depth) as feed:
                    for it in eng.run(feed):
                        if collect:
                            toks[it.position] = np.asarray(it.tokens)
                return toks

            toks = drive(True)       # warm pass; tokens for the check
            eng.stats = engine_lib.EngineStats(slots=eng.slots)
            t0 = time.perf_counter()
            drive(False)
            dt = time.perf_counter() - t0
            return toks, eng.stats.summary(), dt

        toks_off, st_off, dt_off = spec_leg(cfg_spec0)
        toks_on, st_on, dt_on = spec_leg(cfg_spec0.replace(
            spec_decode=spec_tier, engine_spec_k=spec_k))
        assert set(toks_on) == set(toks_off)
        for p in toks_off:
            np.testing.assert_array_equal(toks_on[p], toks_off[p])
        spec = {
            "tier": spec_tier,
            "k": spec_k,
            "eos_delta": eos_delta,
            "tokens_identical": True,
            "value_spec": round(st_on["commits"] / dt_on / n_chips, 2),
            "value_plain": round(st_off["commits"] / dt_off / n_chips,
                                 2),
            "speedup": round((st_on["commits"] / dt_on)
                             / (st_off["commits"] / dt_off), 3),
            "acceptance_rate": st_on["acceptance_rate"],
            "drafted": st_on["drafted"],
            "accepted": st_on["accepted"],
            "verify_dispatches": st_on["verify_dispatches"],
            "steps_saved": st_on["steps_saved"],
            "spec_frames": st_on["spec_frames"],
            "steps_per_commit_spec": st_on["steps_per_commit"],
            "steps_per_commit_plain": st_off["steps_per_commit"],
        }

    # (e3) LOW-PRECISION-TIER leg (opt-in: FIRA_BENCH_QUANT=1): the bf16
    # KV arena + int8 weight tier (decode/quant.py) vs the plain f32
    # engine twin at EQUAL geometry on the same 3-batch eos-biased
    # stream. Quality is MEASURED, never assumed: the leg records the
    # token-match fraction vs f32 instead of asserting identity (cross-
    # tier drift is the quantity under test), next to the machine-
    # recorded kv_bytes_per_slot halving. The full CPU artifact is
    # docs/QUANT_BENCH_r01.jsonl via scripts/serve_bench.py --quant.
    quant_leg = None
    if os.environ.get("FIRA_BENCH_QUANT", "0") == "1":
        from fira_tpu.data.feeder import Feeder
        from fira_tpu.decode import engine as engine_lib
        from fira_tpu.decode.beam import eos_biased_params

        eos_delta = float(os.environ.get(
            "FIRA_BENCH_DECODE_EOS_DELTA", "4.75"))
        q_kv = os.environ.get("FIRA_BENCH_QUANT_KV", "bf16")
        q_sp = os.environ.get("FIRA_BENCH_QUANT_PRECISION", "int8w")
        cfg_q0 = cfg.replace(test_batch_size=batch_size,
                             beam_kv_cache=True,
                             beam_factored_topk=False,
                             decode_engine=True,
                             engine_harvest_every=1)
        params_q = eos_biased_params(state_box[0].params,
                                     delta=eos_delta)
        q_chunks = [rng.choice(n_data, batch_size, replace=True)
                    for _ in range(3)]

        def quant_run(cfg_leg):
            model_leg = FiraModel(cfg_leg, dtype=jnp.dtype(dtype))
            eng = engine_lib.SlotEngine(model_leg, params_q, cfg_leg)

            def drive(collect):
                tasks = ((lambda ix=ix: make_batch(split, ix, cfg_leg))
                         for ix in q_chunks)
                toks = {}
                with Feeder(tasks, num_workers=cfg.feeder_workers,
                            depth=cfg.feeder_depth) as feed:
                    for it in eng.run(feed):
                        if collect:
                            toks[it.position] = np.asarray(it.tokens)
                return toks

            toks = drive(True)       # warm pass; tokens for the match
            eng.stats = engine_lib.EngineStats(slots=eng.slots)
            t0 = time.perf_counter()
            drive(False)
            dt = time.perf_counter() - t0
            return toks, eng.stats.summary(), dt

        toks_f32, st_f32, dt_f32 = quant_run(cfg_q0)
        toks_q, st_q, dt_q = quant_run(cfg_q0.replace(
            kv_dtype=q_kv, serve_precision=q_sp))
        match = sum(bool(np.array_equal(toks_q[p], toks_f32[p]))
                    for p in toks_f32)
        quant_leg = {
            "kv_dtype": st_q["kv_dtype"],
            "serve_precision": st_q["serve_precision"],
            "eos_delta": eos_delta,
            "value_quant": round(st_q["commits"] / dt_q / n_chips, 2),
            "value_f32": round(st_f32["commits"] / dt_f32 / n_chips, 2),
            "speedup": round((st_q["commits"] / dt_q)
                             / (st_f32["commits"] / dt_f32), 3),
            "kv_bytes_per_slot_f32": st_f32["kv_bytes_per_slot"],
            "kv_bytes_per_slot_tier": st_q["kv_bytes_per_slot"],
            "token_match_frac": round(match / max(1, len(toks_f32)), 3),
        }

    step_time = dt_e2e / steps_per_window
    compute_step_time = dt_compute / steps_per_window
    # metric of record: chip-side throughput (module docstring)
    value = batch_size / compute_step_time / n_chips
    value_e2e = batch_size / step_time / n_chips

    # MFU against the compute-only step: the model-FLOPs utilization of the
    # chip (the end-to-end number additionally carries the host link). A
    # device metric: the FIRA_BENCH_ALLOW_CPU harness run carries none.
    peak = _peak_flops(device_kind, dtype) if platform == "tpu" else None
    mfu = round(flops / compute_step_time / peak, 4) if peak else None

    anchor = _load_torch_anchor()
    _emit_worker({
        "metric": METRIC,
        "value": round(value, 2),
        "unit": UNIT,
        # the metric's basis, named in the record itself so ledgers can't
        # silently compare across definitions
        "value_basis": "compute",
        "vs_baseline": round(value / EST_BASELINE_COMMITS_PER_SEC_PER_CHIP, 3),
        "mfu": mfu,
        "flops_per_step": flops,
        "flops_source": flops_source,
        "flops_per_step_xla": flops_xla,
        "step_time_s": round(step_time, 5),
        "compute_step_time_s": round(compute_step_time, 5),
        "value_e2e_host_link": round(value_e2e, 2),
        # input-pipeline observability (docs/PIPELINE.md): stall fraction
        # with the async feeder vs the synchronous-assembly control leg,
        # measured by the same per-item accounting
        "feed_stall_frac": e2e_info["feed_stall_frac"],
        "feeder_queue_depth_mean": e2e_info["queue_depth_mean"],
        "feeder_workers": cfg.feeder_workers,
        # padded-FLOP share of the single-geometry path on this corpus vs
        # what the auto bucket table leaves (data/buckets.padding_report)
        "padding_frac_single": pad_report["padding_frac_single"],
        "padding_frac_bucketed": pad_report["padding_frac_bucketed"],
        "bucket_report": pad_report["buckets"],
        # composed production path (stacked knobs x buckets): throughput
        # plus dispatch-count + dispatched-padding accounting
        **({"value_composed": composed["value"],
            "composed": composed} if composed else {}),
        # slot-refill engine decode vs batched early exit on the same
        # stream (FIRA_BENCH_DECODE_ENGINE=1; decode/engine.py)
        **({"decode_engine": decode_engine} if decode_engine else {}),
        # speculative draft-and-verify vs the plain engine twin at equal
        # geometry (FIRA_BENCH_SPEC=1; decode/spec.py — the CPU artifact
        # is docs/SPEC_BENCH_r01.jsonl via scripts/tpu_decode_bench.py)
        **({"spec_decode": spec} if spec else {}),
        # low-precision serving tiers vs the f32 engine twin at equal
        # geometry (FIRA_BENCH_QUANT=1; decode/quant.py — the CPU
        # artifact is docs/QUANT_BENCH_r01.jsonl via
        # scripts/serve_bench.py --quant)
        **({"quant_tiers": quant_leg} if quant_leg else {}),
        "feed_stall_frac_sync_assembly": sync_info["feed_stall_frac"],
        "value_e2e_sync_assembly": round(
            batch_size / (dt_sync / steps_per_window) / n_chips, 2),
        "peak_flops": peak,
        "platform": platform,
        "device_kind": device_kind,
        "dtype": dtype,
        "batch_size": batch_size,
        "fused_steps": K,
        **({"vs_torch_anchor": round(
                value / anchor["commits_per_sec_per_chip"], 3),
            "torch_anchor": {
                "commits_per_sec_per_chip":
                    anchor["commits_per_sec_per_chip"],
                "device": anchor.get("device"),
                "batch_size": anchor.get("batch_size"),
            }} if anchor else {}),
        **({"production_knobs": production_knobs} if production_knobs else {}),
        **({"overrides": overrides} if overrides else {}),
    })


# --------------------------------------------------------------------------
# orchestrator: one bounded probe, then one bounded worker
# --------------------------------------------------------------------------

def _run_sub(mode: str, timeout_s: float,
             passthrough_file: str | None = None,
             ) -> tuple[int | None, str, str]:
    """Run `python bench.py --<mode>`; rc None means timed out (killed).

    With passthrough_file set (worker runs), the child's stdout is NOT
    captured — it flows straight to this process's stdout, so the worker's
    final JSON line is on the driver-visible stream the moment it exists —
    and the child mirrors its JSON record into passthrough_file, which is
    returned as the `out` leg for parsing."""
    cmd = [sys.executable, os.path.abspath(__file__), f"--{mode}"]
    env = os.environ.copy()
    if passthrough_file is not None:
        env["FIRA_BENCH_RESULT_FILE"] = passthrough_file

    def _die_with_parent():  # runs in the forked child before exec
        # The driver may SIGKILL the orchestrator at any time; without this
        # the probe/worker child would survive as an orphan, holding the
        # driver-visible stdout pipe open and contending with the driver's
        # own next TPU client. PR_SET_PDEATHSIG (Linux) kills the child the
        # instant its parent dies. "libc.so.6" is glibc's soname; musl and
        # other libcs name it differently, so fall back to the loader's own
        # lookup — and when neither installs the signal, say so on stderr
        # (it lands in the attempt tail) so the orphan risk is visible
        # instead of silent.
        try:
            import ctypes
            import ctypes.util
            import signal as _sig
            try:
                libc = ctypes.CDLL("libc.so.6", use_errno=True)
            except OSError:
                name = ctypes.util.find_library("c")
                if name is None:
                    raise OSError("no libc found via ctypes.util")
                libc = ctypes.CDLL(name, use_errno=True)
            libc.prctl(1, _sig.SIGKILL)  # PR_SET_PDEATHSIG
        except Exception as e:  # never block the launch over this
            print(f"PDEATHSIG not installed ({e!r}): child may orphan if "
                  f"the orchestrator is killed", file=sys.stderr)

    try:
        p = subprocess.run(
            cmd, text=True, timeout=timeout_s, env=env,
            stdout=(None if passthrough_file else subprocess.PIPE),
            stderr=subprocess.PIPE, preexec_fn=_die_with_parent,
        )
        rc, out, err = p.returncode, p.stdout or "", p.stderr or ""
    except subprocess.TimeoutExpired as e:
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        rc = None
    if passthrough_file is not None:
        try:
            with open(passthrough_file) as f:
                out = f.read()
        except OSError:
            out = ""
    return rc, out, err


def _last_json_line(out: str) -> dict | None:
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def orchestrate() -> None:
    probe_timeout = float(os.environ.get("FIRA_BENCH_PROBE_TIMEOUT", "90"))
    worker_timeout = float(os.environ.get("FIRA_BENCH_WORKER_TIMEOUT", "1500"))
    attempts: list[dict] = []

    def emit_status(error: str, in_progress: bool) -> None:
        print(json.dumps({
            "metric": METRIC, "value": None, "unit": UNIT,
            "vs_baseline": None, "mfu": None,
            "error": error, "attempts": attempts,
            **({"in_progress": True} if in_progress else {}),
        }), flush=True)

    def fail(error: str) -> None:
        emit_status(error, in_progress=False)
        sys.exit(1)

    # A parseable line must exist from the first instant: if the driver
    # kills us before the probe finishes, this is the record it parses.
    emit_status("in progress: starting (no probe attempted yet)",
                in_progress=True)

    # Phase 1: one probe. A backend that is absent raises in seconds; one
    # that hangs in init is killed at the timeout. Neither is retried: the
    # chip is local, and a failure that would repeat verbatim is an answer.
    t0 = time.time()
    rc, out, err = _run_sub("probe", probe_timeout)
    rec = {"phase": "probe", "rc": rc, "secs": round(time.time() - t0, 1)}
    probed = _last_json_line(out) if rc == 0 else None
    if probed is None:
        rec["tail"] = (err or out).strip()[-300:]
        attempts.append(rec)
        fail("backend init "
             + (f"hung past the {probe_timeout:.0f}s probe timeout"
                if rc is None else f"failed (probe rc={rc})"))
    rec["result"] = probed
    attempts.append(rec)
    if probed.get("platform") != "tpu" \
            and os.environ.get("FIRA_BENCH_ALLOW_CPU") != "1":
        fail(f"backend answered but is not TPU: {probed}")

    # Phase 2: the measurement. The worker's stdout passes straight through
    # to ours (its JSON line is driver-visible the moment it prints — a kill
    # after that point cannot lose it); the side file is how we parse it.
    import tempfile

    emit_status(f"in progress: worker running on "
                f"{probed.get('device_kind')}", in_progress=True)
    t0 = time.time()
    with tempfile.NamedTemporaryFile(mode="r", suffix=".json") as rf:
        rc, out, err = _run_sub("worker", worker_timeout,
                                passthrough_file=rf.name)
    rec = {"phase": "worker", "rc": rc, "secs": round(time.time() - t0, 1)}
    result = _last_json_line(out)
    if rc == 0 and result and result.get("value") is not None:
        # the worker already printed the record to our stdout; print it
        # again so the tail is the success record even if the worker also
        # wrote post-JSON noise
        print(json.dumps(result), flush=True)
        return
    if rc == 0 and result is None:
        # The worker exits 0 only after printing its success record to our
        # (inherited) stdout — an unreadable side-file mirror must not
        # invert a successful measurement into a final null record
        # overwriting it as the stdout tail.
        print("worker rc=0 but side file unreadable; trusting the "
              "worker's own stdout record", file=sys.stderr)
        return
    if result and result.get("error"):
        # the worker's own structured error is the real cause — keep it
        worker_error = result["error"]
        rec["error"] = worker_error
    else:
        worker_error = ("worker timed out" if rc is None
                        else f"worker failed (rc={rc})")
        rec["tail"] = (err or out).strip()[-500:]
    attempts.append(rec)
    fail(worker_error)


if __name__ == "__main__":
    if "--probe" in sys.argv:
        probe()
    elif "--worker" in sys.argv:
        worker()
    else:
        orchestrate()
