"""Training driver: epochs, dev gating, checkpointing, throughput metering.

Rebuilds the reference's train/dev orchestration
(/root/reference/run_model.py:83-184) TPU-first: a SMALL FIXED FAMILY of
compiled programs runs for the whole session — per-step/grouped train
steps x bucket geometries x dev (data/grouping.py, data/buckets.py; by
default the populated rungs of the edge ladder), the train programs
pre-warmed at startup; batches stream through fixed shapes;
throughput is reported as commits/sec/chip (the repo's metric of record,
BASELINE.md).

Reference semantics kept:
- dev-gate cadence ``epoch >= dev_start_epoch and batch_idx % dev_every == 0``
  (run_model.py:89);
- gating metric is NLTK method2 sentence BLEU on teacher-forced greedy
  output (run_model.py:171), NOT the reported B-Norm number;
- best checkpoint saved on strict improvement (run_model.py:94-96), plus an
  append-only train_process log line per gate decision (run_model.py:92).

Added beyond the reference: full train-state checkpointing with resume
(optimizer moments + PRNG + gating bookkeeping survive preemption).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Dict, List, Optional

import jax
import numpy as np

from fira_tpu.analysis.sanitizer import program_label as sanitizer_label
from fira_tpu.config import FiraConfig
from fira_tpu.data import buckets as buckets_lib
from fira_tpu.data import grouping
from fira_tpu.data.batching import epoch_index_chunks, make_batch
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder, assembly_tasks
from fira_tpu.decode.text import cook_prediction, deanonymize, reference_words
from fira_tpu.eval.dev_bleu import nltk_sentence_bleu
from fira_tpu.model.model import FiraModel
from fira_tpu.parallel import mesh as pmesh
from fira_tpu.robust.watchdog import WatchdogTimeout, run_with_watchdog
from fira_tpu.train import step as step_lib
from fira_tpu.train.state import CheckpointManager, TrainState, init_state
from fira_tpu.utils import profiling


@dataclasses.dataclass
class TrainLog:
    """Per-gate and per-interval console/file logging (run_model.py:92,114)."""

    out_dir: str

    def __post_init__(self):
        os.makedirs(self.out_dir, exist_ok=True)

    def gate(self, epoch: int, batch: int, bleu: float, better: bool) -> None:
        line = (f"epoch: {epoch} batch: {batch} dev bleu: {bleu} "
                f"is better: {better}\n")
        with open(os.path.join(self.out_dir, "train_process"), "a") as f:
            f.write(line)

    def dev_output(self, text: str) -> None:
        with open(os.path.join(self.out_dir, "dev_output"), "w") as f:
            f.write(text)

    def console(self, msg: str) -> None:
        print(msg, flush=True)


def _eval_tasks(data, cfg: FiraConfig, plan=None):
    """Assembly tasks for the dev pass: the single-geometry sequential
    chunks when buckets are off (the byte-identical legacy stream), the
    bucketed sort-by-length plan when on. Dev packs with the DECODE bucket
    table — tar_len pinned full, admissibility on (nodes, edges) only:
    the reference's gating metric scores teacher-forced predictions at
    EVERY tar position (even pad-conditioned ones, run_model.py:118-184),
    so truncating tar would change the metric; with tar full the per-line
    dev output is bit-identical to the unbucketed pass (pinned by
    tests/test_buckets.py). ``plan``: a precomputed packed plan for the
    split — the shuffle=False plan never changes, so train() computes it
    once instead of re-deriving extents/assignment at every dev gate."""
    if cfg.buckets:
        if plan is None:
            # tar stays PINNED FULL here even under cfg.decode_tar_buckets
            # (an engine-only generation knob): the teacher-forced gating
            # metric scores every tar position, and use_msg=False packing
            # would otherwise seat long-message samples in short-tar
            # buckets and trip make_batch's admissibility backstop mid-run
            dev_cfg = cfg.replace(decode_tar_buckets=False)
            plan = buckets_lib.packed_plan(data, cfg,
                                           batch_size=cfg.test_batch_size,
                                           table=buckets_lib.decode_table(
                                               dev_cfg),
                                           use_msg=False)
        return buckets_lib.bucketed_assembly_tasks(
            data, plan, cfg, batch_size=cfg.test_batch_size)
    chunks = epoch_index_chunks(len(data), cfg, batch_size=cfg.test_batch_size)
    return assembly_tasks(data, chunks, cfg, batch_size=cfg.test_batch_size)


def run_dev(dev_step, params, dataset: FiraDataset, cfg: FiraConfig,
            var_maps: Optional[List[Dict[str, str]]] = None,
            split: str = "valid", guard=None,
            eval_plan=None, cancel=None) -> tuple[float, str]:
    """Greedy teacher-forced validation (run_model.py:118-184). Returns
    (mean sentence BLEU over the split, dev_output text — always in split
    order, even when the bucket packer reordered the batch stream).

    ``cancel``: zero-arg callable polled per eval batch — the dispatch
    watchdog's cooperative kill switch (docs/FAULTS.md): a gate the
    watchdog abandoned must STOP dispatching eval programs and stepping
    the shared compile guard instead of racing the resumed training
    loop; raising here closes the eval feeder via the context manager."""
    data = dataset.splits[split]
    vocab = dataset.word_vocab
    indices = dataset.split_indices[split]
    total_bleu = 0.0
    out_lines: List[tuple] = []  # (split position, line)
    cursor = 0
    with Feeder(_eval_tasks(data, cfg, plan=eval_plan),
                num_workers=cfg.feeder_workers,
                depth=cfg.feeder_depth) as feed:
        for item in feed:
            if cancel is not None and cancel():
                raise WatchdogTimeout(
                    "dev gate abandoned by the dispatch watchdog")
            batch = item.host  # numpy fields for host-side text cooking
            # firacheck: allow[HOST-SYNC] dev gate IS a designated sync boundary: teacher-forced ids must reach the host for BLEU scoring (README Design notes)
            ids = np.asarray(jax.device_get(dev_step(params, item.device)))
            valid = batch["valid"]  # host-side numpy batch field, no device trip
            positions = batch.get("_positions")  # bucketed stream only
            if guard is not None:
                guard.step(sanitizer_label("dev_step", batch.get("_tag")))
            for i in range(ids.shape[0]):
                if not valid[i]:
                    continue
                pos = cursor if positions is None else int(positions[i])  # firacheck: allow[HOST-SYNC] _positions is a host-only numpy field (feeder strips it from the wire); no device value exists here
                hyp = cook_prediction(
                    ids[i].tolist(), batch["diff"][i], batch["sub_token"][i],
                    vocab, cfg,
                )
                ref = reference_words(batch["msg"][i], vocab)
                b = nltk_sentence_bleu([ref], hyp)
                total_bleu += b
                var_map = (var_maps[indices[pos]]
                           if var_maps is not None else None)
                out_lines.append(
                    (pos, " ".join(deanonymize(hyp, var_map)) + f",{b}"))
                cursor += 1
    out_lines.sort(key=lambda r: r[0])
    return (total_bleu / max(len(data), 1),
            "\n".join(line for _, line in out_lines) + "\n")


def _materialize(x) -> None:
    """Device sync by copying computed data to the host. On the local chip
    ``jax.block_until_ready`` waits just as long (chip_smoke.py times a
    train dispatch ended both ways; PERF.md "Bring-up"); this form also
    leaves the value on the host, where the callers want it next."""
    # firacheck: allow[HOST-SYNC] THE designated sync helper: every hot-loop sync funnels through here so the boundaries stay enumerable (called only at meter/log/epoch edges)
    np.asarray(jax.device_get(x))


@dataclasses.dataclass
class TrainResult:
    state: TrainState
    best_bleu: float
    epochs_run: int
    commits_per_sec_per_chip: float
    # share of measured train wall clock the host spent blocked on the
    # input feed (profiling.Meter; docs/PIPELINE.md) — the denominator the
    # next perf round divides host-pipeline work against
    feed_stall_frac: float = 0.0
    # aggregated data/feeder.Feeder stats over the run: batches,
    # feed_stall_s, queue_depth_mean/min, num_workers, depth
    feeder: Dict[str, float] = dataclasses.field(default_factory=dict)
    # loud-but-nonfatal run conditions (also printed to the console):
    # fused_steps not dividing dev_every_batches (gate-staleness footgun,
    # config.py), profiling annotations spanning K-step grouped dispatches —
    # anything a reader of this run's numbers must know to read them right
    warnings: List[str] = dataclasses.field(default_factory=list)


def train(dataset: FiraDataset, cfg: Optional[FiraConfig] = None, *,
          mesh=None,
          out_dir: str = "OUTPUT",
          ckpt_dir: Optional[str] = None,
          epochs: Optional[int] = None,
          var_maps: Optional[List[Dict[str, str]]] = None,
          resume: bool = True,
          profile_dir: Optional[str] = None,
          profile_steps: int = 10,
          guard=None,
          dtype=None) -> TrainResult:
    """Full training run. ``mesh=None`` => single-chip jit; otherwise the
    (data, model) mesh from parallel.mesh with XLA-inserted collectives.

    ``guard``: an armed analysis.sanitizer.CompileGuard — each dispatch
    site labels its program and a post-warmup step that triggers a new XLA
    compilation raises RetraceError. The CLI arms process-wide via
    ``--sanitize`` (sanitizer.arm); library callers wrap the call in
    ``with sanitizer.sanitize() as guard:`` so global config is restored.
    """
    import jax.numpy as jnp

    cfg = cfg or dataset.cfg  # dataset.cfg has vocab sizes filled in
    if mesh is not None:
        # fail BEFORE any compile: a batch axis that doesn't divide the
        # data mesh axis otherwise dies mid-epoch in an XLA sharding error
        # (the CLI runs the same check at parse time and exits 2)
        errs = pmesh.divisibility_errors(cfg,
                                         mesh.shape[pmesh.DATA_AXIS])
        if errs:
            raise ValueError("mesh divisibility: " + "; ".join(errs))
    log = TrainLog(out_dir)
    model = FiraModel(cfg, dtype=dtype or jnp.dtype(cfg.compute_dtype))

    train_split = dataset.splits["train"]
    sample = make_batch(train_split, np.arange(min(cfg.batch_size,
                                                   len(train_split))),
                        cfg, batch_size=cfg.batch_size)
    with profiling.span("train.init_state"):
        state = init_state(model, cfg, sample)
    train_step = step_lib.jit_train_step(model, cfg, mesh, state, sample)
    dev_step = jax.jit(step_lib.make_dev_step(model))

    ckpt = CheckpointManager(ckpt_dir or os.path.join(out_dir, "ckpt"))
    best_bleu, start_epoch = 0.0, 0
    if resume and ckpt.has(CheckpointManager.LATEST):
        state, meta = ckpt.restore_latest(state, expect_rng_impl=cfg.rng_impl)
        best_bleu, start_epoch = meta["best_bleu"], meta["epoch"]
        log.console(f"resumed at epoch {start_epoch}, best dev bleu {best_bleu:.4f}")
    if mesh is not None:
        # the WHOLE state on the mesh — fresh or restored — placed as every
        # dispatch hands it back and as the warm-up's throwaway copy is:
        # the first real dispatch then runs the program the warm-up
        # compiled (a state with only its params sharded is another
        # signature, and compiled the step a second time)
        state = jax.device_put(state, step_lib.state_shardings(state, mesh))

    n_epochs = epochs if epochs is not None else cfg.epochs
    n_chips = 1 if mesh is None else mesh.devices.size
    # The host only syncs with the device at logging/dev boundaries — steps
    # stay asynchronously dispatched in between (the per-step .item() sync is
    # one of the reference's throughput sins to avoid). Meter(warmup=1) drops
    # the interval containing the compile step.
    meter = profiling.Meter(warmup=1)
    pending_commits = 0
    pending_stall = 0.0
    meter.start()

    def sync_tick():
        """Record the interval since the last sync, attributing the commits
        dispatched in it and the feed-stall time they carried; an empty
        interval just restarts the clock."""
        nonlocal pending_commits, pending_stall
        if pending_commits:
            meter.tick(pending_commits, stall_s=pending_stall)
        else:
            # an empty interval is discarded wholesale — drop its stall too
            # (e.g. the epoch's pipeline-fill stall at a start-of-epoch dev
            # gate), or it would be mis-attributed to the NEXT interval and
            # overstate feed_stall_frac
            meter.start()
        pending_commits = 0
        pending_stall = 0.0

    # jax.profiler trace of a steady-state step window (skips the compile
    # step); viewable in TensorBoard / xprof.
    profile_window = (range(2, 2 + profile_steps) if profile_dir else range(0))
    profiler = contextlib.ExitStack()   # holds profiling.trace while open
    profiling_active = False
    profile_done = False
    global_step = 0

    # Double-buffered device feed: batch i+1 transfers while step i runs.
    # With a mesh, batches land pre-sharded along the data axis — the
    # shared shape-dispatched callable (parallel.mesh.feed_shardings)
    # picks stacked vs per-batch shardings per item, so mixed-geometry
    # bucketed streams and K-groups both ship correctly sharded from the
    # feeder's workers.
    batch_sharding = pmesh.feed_shardings(mesh)

    # Grouped device programs — mutually exclusive:
    #   fused_steps K   > 1: K-groups run as K steps in ONE lax.scan dispatch
    #   accum_steps A   > 1: A-groups accumulate into ONE optimizer step
    #                        normalized over the global (sum, count) — the
    #                        reference's DataParallel batch-680 dynamics
    # The epoch tail (< group size) uses the per-step program under fused
    # and pads to the stacked shape with all-invalid micro-batches under
    # accum. Both COMPOSE with cfg.buckets: the grouped scheduler
    # (data/grouping.py) packs bucket-homogeneous groups over the same
    # epoch permutation, so each dispatch is one member of the
    # (geometry x entrypoint x group-size) program family.
    fused = max(1, int(cfg.fused_steps))
    accum = max(1, int(cfg.accum_steps))
    if fused > 1 and accum > 1:
        raise ValueError("fused_steps and accum_steps are mutually "
                         "exclusive (one scans steps, one accumulates "
                         "gradients); set at most one > 1")
    warnings: List[str] = []
    if fused > 1 and cfg.dev_every_batches % fused:
        # the gate-staleness footgun documented at cfg.fused_steps: gates
        # due inside a K-group collapse to one, fired BEFORE the group with
        # up-to-K-1-steps-stale params — loud here, recorded in the result
        w = (f"fused_steps={fused} does not divide dev_every_batches="
             f"{cfg.dev_every_batches}: dev gates due inside a fused group "
             f"collapse to one gate fired before the group (params up to "
             f"{fused - 1} steps stale); pick K dividing the cadence "
             f"(config.py fused_steps note)")
        log.console(f"WARNING: {w}")
        warnings.append(w)
    if (fused > 1 or accum > 1) and profile_dir:
        # the REAL grouped program is profiled (not a per-step downgrade —
        # profiled numbers must be production-path numbers); each trace
        # annotation then spans one whole K-step dispatch
        w = (f"profiling the grouped program: each step annotation spans "
             f"one {'fused' if fused > 1 else 'accum'} dispatch of "
             f"{fused if fused > 1 else accum} stacked batches")
        log.console(w)
        warnings.append(w)
    group_size = fused if fused > 1 else accum
    grouped_step = None
    if group_size > 1:
        stacked_sample = step_lib.stack_batches([sample] * group_size)
        maker = (step_lib.jit_multi_step if fused > 1
                 else step_lib.jit_accum_step)
        grouped_step = maker(model, cfg, mesh, state, stacked_sample)

    # --- the program family (data/buckets.py; docs/BUCKETING.md) ---
    # Table + per-sample assignment computed ONCE for the train split. The
    # table is the user's declared cfg.buckets, or — the default — the edge
    # ladder: the COO pad of a dispatch is the least rung of max_edges / 2^k
    # that holds its commits (buckets.train_table). The family's programs
    # are pre-warmed here — each compiles against a throwaway state copy
    # and an all-pad batch (zero training effect), so the epoch loop never
    # compiles again. The guard then learns the closed family: every label
    # gets its one warmup dispatch, and any label outside the declared set
    # raises.
    train_table = buckets_lib.train_table(cfg)
    bucket_assignment = buckets_lib.assign_buckets(
        buckets_lib.sample_extents(train_split, cfg), train_table)

    def epoch_plan(epoch: int):
        """ONE scheduler for every mode (data/grouping.py): per-step mode
        is the greedy packer's walk, grouped mode packs bucket-homogeneous
        K-stacks over the SAME permutation (fused tails per-step, accum
        tails padded with all-invalid micro-batches)."""
        return grouping.grouped_plan(
            train_split, cfg, batch_size=cfg.batch_size,
            group_size=group_size, accum=accum > 1, shuffle=True,
            seed=cfg.seed, epoch=epoch, table=train_table,
            assignment=bucket_assignment)

    dev_geoms, dev_plan, dev_labels = (), None, ["dev_step"]
    if cfg.buckets:
        # a declared table: every member x every entry point an epoch can
        # dispatch. Under fused the per-step program is warmed too (epoch
        # tails dispatch it); under accum it never runs (tails pad to the
        # stacked shape), so only the grouped member is warmed per geometry.
        sizes = ([1] if group_size == 1 or fused > 1 else []) \
            + ([group_size] if group_size > 1 else [])
        programs = [(g, k) for g in train_table for k in sizes]
        # dev packs with the decode table (tar pinned full — the gating
        # metric scores every tar position, see _eval_tasks, so the
        # engine-only cfg.decode_tar_buckets knob is forced off here);
        # the dev plan is shuffle=False and never changes, so compute it
        # ONCE here instead of re-deriving extents/assignment at every
        # dev gate
        dev_geoms = buckets_lib.decode_table(
            cfg.replace(decode_tar_buckets=False))
        dev_plan = buckets_lib.packed_plan(
            dataset.splits["valid"], cfg, batch_size=cfg.test_batch_size,
            table=dev_geoms, use_msg=False)
        dev_labels = [sanitizer_label("dev_step", buckets_lib.geom_tag(g))
                      for g in dev_geoms]
    else:
        # the edge ladder: ONLY the rungs this split populates, with the
        # entry points its plan dispatches (the same set every epoch) — a
        # corpus of small commits compiles one rung, not four. The dev
        # gate keeps its one full-geometry program (decode_table), warmed
        # by its first dispatch as before.
        programs = grouping.plan_programs(epoch_plan(start_epoch))

    def program_label(geom, k: int) -> str:
        return sanitizer_label("grouped_step" if k > 1 else "train_step",
                               buckets_lib.geom_tag(geom), k)

    if guard is not None:
        guard.declare(dev_labels + [program_label(g, k) for g, k in programs])
    if programs and start_epoch < n_epochs:
        # donation-safe throwaway copy: the real state (and its PRNG) is
        # untouched by warmup; host round-trip avoids compiling a copy op
        host_state = jax.device_get(state)
        warm_state = (jax.device_put(host_state,
                                     step_lib.state_shardings(state, mesh))
                      if mesh is not None else jax.device_put(host_state))
        for g, k in programs:
            wb = buckets_lib.warmup_batch(train_split, cfg, g,
                                          cfg.batch_size)
            if k > 1:
                wb = grouping.stack_group([wb] * k)
            if batch_sharding is not None:
                # placed as the feeder's workers ship the real ones: a
                # host batch is another signature of the same program
                wb = jax.device_put(wb, batch_sharding(wb))
            warm_state, wm = (grouped_step if k > 1 else train_step)(
                warm_state, wb)
            if guard is not None:
                guard.step(program_label(g, k))
        for g in dev_geoms:
            wb = buckets_lib.warmup_batch(train_split, cfg, g,
                                          cfg.test_batch_size)
            dev_step(state.params, wb)
            if guard is not None:
                guard.step(sanitizer_label("dev_step",
                                           buckets_lib.geom_tag(g)))
        _materialize(wm["loss"])  # startup warmup boundary, pre-metering
        del warm_state, host_state
        log.console(
            f"buckets: pre-warmed "
            f"{sum(k == 1 for _, k in programs)} train + "
            f"{sum(k > 1 for _, k in programs)} grouped"
            f"{f'(g{group_size})' if group_size > 1 else ''} + "
            f"{len(dev_geoms)} dev programs "
            f"({', '.join(sorted({buckets_lib.geom_tag(g) for g, _ in programs}))}"
            f"{'' if cfg.buckets else ': the populated rungs of the edge ladder'})")
        meter.start()  # warmup/compile time is not train time

    def epoch_tasks(epoch: int):
        """Zero-arg assembly tasks in the exact deterministic (seed, epoch)
        batch order. Each task builds ONE dispatch item, so independent
        items assemble in parallel on the feeder's workers."""
        return grouping.grouped_assembly_tasks(
            train_split, epoch_plan(epoch), cfg, batch_size=cfg.batch_size)

    # Aggregated feeder stats across epochs (each epoch gets a fresh
    # pipeline; sums/mins fold here for TrainResult)
    feed_totals = {"batches": 0.0, "feed_stall_s": 0.0,
                   "queue_depth_sum": 0.0, "queue_depth_min": float("inf"),
                   "edge_slots": 0.0, "edges": 0.0}

    for epoch in range(start_epoch, n_epochs):
        last_metrics = None
        idx = 0  # batch index of the current item's first step
        epoch_feed = Feeder(epoch_tasks(epoch),
                            num_workers=cfg.feeder_workers,
                            depth=cfg.feeder_depth, sharding=batch_sharding)
        try:
            for item in epoch_feed:
                batch, n_valid = item.device, item.n_valid
                if meter.paused:
                    # the epoch's first batch: the checkpoint save and this
                    # feeder's pipeline fill lie behind it and are not train
                    # time (nor is that fill a steady-state feed stall)
                    meter.start()
                else:
                    pending_stall += item.stall_s
                stacked = item.host["valid"].ndim == 2
                # cadence counts REAL batches: the accum tail is padded with
                # all-zero micro-batches, so the stacked leading dim overstates
                # it — n_valid (host-side, no sync) recovers the real count
                # exactly because only a group's last real batch can be partial
                k = -(-n_valid // cfg.batch_size) if stacked else 1
                # does [idx, idx+k) contain a multiple of the cadence?
                gate_due = (-idx) % cfg.dev_every_batches < k
                log_due = (-idx) % 10 < k
                if epoch >= cfg.dev_start_epoch and gate_due:
                    if last_metrics is not None:
                        _materialize(last_metrics["loss"])
                    sync_tick()
                    meter.pause()  # dev time is not train time
                    # dispatch watchdog (docs/FAULTS.md): a dev gate that
                    # wedges (hung eval dispatch, stuck eval feeder) is
                    # ABANDONED after cfg.dispatch_watchdog_s and skipped
                    # with a recorded warning — training continues
                    # degraded instead of the whole run hanging on its
                    # own evaluation. 0 (default) = off, call inline.
                    gate_cancel = threading.Event()
                    try:
                        cur_bleu, dev_text = run_with_watchdog(
                            lambda: run_dev(dev_step, state.params,
                                            dataset, cfg, var_maps,
                                            guard=guard,
                                            eval_plan=dev_plan,
                                            cancel=gate_cancel.is_set),
                            float(cfg.dispatch_watchdog_s),  # firacheck: allow[HOST-SYNC] config scalar, not a device value; the gate is already a designated sync boundary
                            label=f"dev_gate[e{epoch}b{idx}]",
                            cancel_event=gate_cancel)
                    except WatchdogTimeout as e:
                        w = (f"dev gate at epoch {epoch} batch {idx} "
                             f"skipped: {e}; training continues without "
                             f"this gate's checkpoint decision")
                        log.console(f"WARNING: {w}")
                        warnings.append(w)
                    else:
                        better = cur_bleu > best_bleu
                        log.gate(epoch, idx, cur_bleu, better)
                        if better:
                            best_bleu = cur_bleu
                            ckpt.save_best(state.params)
                            log.dev_output(dev_text)
                    meter.start()

                if (profile_window and not profiling_active
                        and not profile_done
                        and global_step >= profile_window[0]):
                    # the REAL program is profiled — grouped dispatches and
                    # all — so profiled numbers are production-path numbers;
                    # a K-group's annotation spans its whole scan dispatch
                    profiler.enter_context(profiling.trace(profile_dir))
                    profiling_active = True
                dispatch = grouped_step if stacked else train_step
                if profiling_active:
                    with profiling.step_annotation(global_step):
                        state, metrics = dispatch(state, batch)
                else:
                    state, metrics = dispatch(state, batch)
                if guard is not None:
                    # compile-once contract: a post-warmup dispatch of any
                    # program that recompiles raises RetraceError here; a
                    # bucketed item carries its geometry tag and a stacked
                    # item its group size, giving each (geom, K) member of
                    # the pre-warmed family its own label
                    tag = item.host.get("_tag")
                    guard.step(sanitizer_label(
                        "grouped_step" if stacked else "train_step", tag,
                        group_size if stacked else 1))
                # a fused group is k steps; an accumulation group is ONE step
                global_step += 1 if (stacked and accum > 1) else k
                if profiling_active and global_step > profile_window[-1]:
                    _materialize(metrics["loss"])
                    profiler.close()
                    profiling_active = False
                    profile_done = True
                    log.console(f"profile trace written to {profile_dir}")
                last_metrics = metrics
                pending_commits += n_valid
                if log_due:
                    # blocks; a stacked dispatch reports its last step's loss
                    # firacheck: allow[HOST-SYNC] the 10-batch console-log cadence is a designated sync boundary (README Design notes); steps in between stay async-dispatched
                    loss = float(np.asarray(
                        jax.device_get(metrics["loss"])).ravel()[-1])  # firacheck: allow[HOST-SYNC] same log boundary — the expression's device_get continues onto this line
                    sync_tick()
                    log.console(f"epoch: {epoch} batch: {idx} loss: {loss:.4f}")
                idx += k
        finally:
            # clean pipeline shutdown on ANY exit (error, interrupt, normal
            # exhaustion): no worker threads survive the epoch
            s = epoch_feed.stats()
            for key in ("batches", "feed_stall_s", "queue_depth_sum",
                        "edge_slots", "edges"):
                feed_totals[key] += s[key]
            feed_totals["queue_depth_min"] = min(
                feed_totals["queue_depth_min"], s["queue_depth_min"])
            epoch_feed.close()
        if last_metrics is not None:
            _materialize(last_metrics["loss"])
        sync_tick()
        meter.pause()  # until the next epoch's first batch arrives
        ckpt.save_latest(state, best_bleu=best_bleu, epoch=epoch + 1,
                         rng_impl=cfg.rng_impl)

    if profiling_active:  # run ended inside the profile window
        profiler.close()
        log.console(f"profile trace written to {profile_dir}")
    elif profile_dir and not profile_window:
        log.console("profile trace NOT written: profile_steps=0")
    elif profile_dir and not profile_done:
        log.console(f"profile trace NOT written: run ended after "
                    f"{global_step} steps, before the profile window "
                    f"(starts at step {profile_window[0]})")

    msum = meter.summary()
    cps = msum["items_per_sec"] / n_chips
    n_fed = feed_totals["batches"]
    feeder_stats = {
        "batches": n_fed,
        "feed_stall_s": round(feed_totals["feed_stall_s"], 4),
        "queue_depth_mean": round(
            feed_totals["queue_depth_sum"] / n_fed, 2) if n_fed else 0.0,
        "queue_depth_min": (feed_totals["queue_depth_min"]
                            if n_fed else 0.0),
        "num_workers": float(cfg.feeder_workers),
        "depth": float(cfg.feeder_depth),
        # COO slots the dispatches shipped and the real edges among them:
        # edges / edge_slots is the wire's fill share (the rest is pad the
        # adjacency scatter adds as zeros)
        "edge_slots": feed_totals["edge_slots"],
        "edges": feed_totals["edges"],
    }
    if n_fed:
        log.console(
            f"throughput: {cps:.2f} commits/sec/chip | feed_stall_frac "
            f"{msum['feed_stall_frac']:.3f} "
            f"({msum['feed_stall_ms_per_step']:.1f} ms/step) | feeder "
            f"queue depth mean {feeder_stats['queue_depth_mean']:.1f} "
            f"min {feeder_stats['queue_depth_min']:.0f} "
            f"(workers {cfg.feeder_workers}, depth {cfg.feeder_depth}) | "
            f"edge fill "
            f"{feed_totals['edges'] / max(feed_totals['edge_slots'], 1):.3f}")
    # epochs ACTUALLY executed this call (a resumed run skips start_epoch of
    # them; a checkpoint already past the target runs zero) — callers
    # validating resume legs depend on the distinction
    return TrainResult(state=state, best_bleu=best_bleu,
                       epochs_run=max(0, n_epochs - start_epoch),
                       commits_per_sec_per_chip=cps,
                       feed_stall_frac=msum["feed_stall_frac"],
                       feeder=feeder_stats, warnings=warnings)
