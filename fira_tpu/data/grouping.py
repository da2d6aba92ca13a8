"""Bucket-homogeneous grouped dispatch: the one epoch scheduler.

The two biggest shipped throughput wins used to be mutually exclusive: the
production knob set runs a ``fused_steps=8`` device loop (docs/PERF.md) and
the reference-dynamics config accumulates ``accum_steps=4`` micro-batches
(config.py), but both stack K batches on a leading axis — and the bucket
packer (data/buckets.py) emits batches of MIXED geometry, which cannot
stack. This module closes the gap: after bucket assignment over the same
``epoch_order`` permutation, runs of K (fused) or A (accum) SAME-geometry
batches pack into one dispatch group, so the padding win (padding_frac
0.264 -> 0.086, docs/BUCKET_BENCH.jsonl) and the dispatch-amortization win
(68.75 ms/step stacked row, docs/PERF.md) compose instead of competing —
the standard NMT/Transformer recipe (length-bucketed batching + multi-step
device loops; PAPERS.md).

One plan shape subsumes every train epoch:

- ``group_size == 1``: per-step dispatch — EXACTLY
  ``buckets.packed_plan(shuffle=True)`` over the same table (same greedy
  walk, same tail flush); over a one-geometry table it degenerates to the
  sequential ``epoch_index_chunks`` slicing (pinned by
  tests/test_grouping.py).
- ``group_size > 1``, fused: each bucket's chunks collect until K are
  ready, then emit as ONE :class:`GroupEntry` the moment the K-th fills
  (deterministic in the walk); leftovers smaller than K fall back to
  per-step dispatch — the fused-tail rule, now per bucket.
- ``group_size > 1``, accum: tails pad to A with all-invalid micro-batches
  (zero rows contribute nothing to the global (sum, count) — the same
  machinery as the pre-bucket accum tail), so accumulation is always ONE
  A-stacked dispatch and the per-step program is never needed.

The table (``buckets.train_table``)
-----------------------------------
The user's declared ``cfg.buckets`` where there is one. With
``cfg.buckets = ()`` — every configuration's default — the plan takes the
EDGE LADDER (``buckets.edge_ladder``): a commit goes to the least rung of
``max_edges / 2^k`` that holds its edges, K same-rung batches make a
dispatch, ``make_batch(geom=)`` shortens the COO rows, and the AST tail and
the target length stay full. So a dispatch carries the edge slots its
commits fill, not the admission bound: on a corpus of small commits the
adjacency scatter stops adding millions of zeros a step, and sample ORDER
differs from plain chunking only where the split populates more than one
rung.

Determinism contract (extends the buckets/feeder contracts)
-----------------------------------------------------------
The plan is a pure function of ``(seed, epoch, bucket table, group size,
accum)``. Chunk FORMATION depends only on the permutation walk — the
sample->chunk assignment is identical for every group size; grouping only
packages chunks into dispatches. The feeder preserves task order for any
worker count, so the delivered sample stream is identical across worker
counts too (all pinned by tests/test_grouping.py).

Correctness bar: a grouped dispatch is the same ``train_step`` body run K
times by ``lax.scan`` (train/step.py), and each member batch is assembled
by the same ``make_batch(geom=...)`` the per-step bucketed path uses — so
grouped-bucketed training reproduces per-step bucketed dispatch of the
same chunk stream (params + per-step losses), which is already bit-exact
against full pad (tests/test_buckets.py).

Sanitizer interplay: each grouped program is one member of the (geometry x
entrypoint x group-size) family — labels via
``analysis.sanitizer.program_label`` (``grouped_step[a16.e256.t8.g8]``),
pre-warmed and declared by train/loop.py, so an undeclared (geom, K)
program still raises at the dispatch that produced it.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence

import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.data.buckets import (BucketGeom, assign_buckets, geom_cost,
                                   geom_tag, sample_extents, train_table)
from fira_tpu.data.dataset import ProcessedSplit


class GroupEntry(NamedTuple):
    """One dispatch of an epoch plan.

    ``pad_to == 1``: a per-step dispatch of ``chunks[0]`` (exactly one
    chunk). ``pad_to > 1``: a stacked dispatch — ``chunks`` (all the same
    geometry, each a full or tail index chunk) stack on a leading axis;
    when ``len(chunks) < pad_to`` (accum tails) the assembly pads with
    all-invalid micro-batches up to ``pad_to``.
    """

    chunks: tuple          # of np.ndarray index chunks, len >= 1
    geom: BucketGeom
    pad_to: int


Plan = List[GroupEntry]


def grouped_plan(split: ProcessedSplit, cfg: FiraConfig, *,
                 batch_size: Optional[int] = None,
                 group_size: int = 1,
                 accum: bool = False,
                 shuffle: bool = False,
                 seed: int = 0,
                 epoch: int = 0,
                 table: Optional[Sequence[BucketGeom]] = None,
                 extents=None,
                 assignment: Optional[np.ndarray] = None,
                 use_msg: bool = True) -> Plan:
    """The deterministic grouped batch order of one train epoch.

    Walks the exact ``epoch_order(seed, epoch)`` permutation (the single
    order source every packing strategy chunks from), appending each sample
    to its bucket's open chunk; a chunk joins its bucket's pending group
    when it fills, and a group dispatches the moment its ``group_size``-th
    chunk lands. Tails flush in table order: fused leftovers (< group_size
    chunks, plus each bucket's partial chunk) emit per-step; with
    ``accum=True`` they emit as one short group the assembly pads to
    ``group_size`` with all-invalid micro-batches.

    ``table``: default ``buckets.train_table(cfg)`` — the declared table,
    or the edge ladder under ``cfg.buckets = ()``. The split's extents are
    memoised (``buckets.sample_extents``), so a caller that plans every
    epoch without passing ``extents`` / ``assignment`` measures them once.
    """
    from fira_tpu.data.batching import epoch_order

    if group_size < 1:
        raise ValueError(f"group_size must be >= 1, got {group_size}")
    bs = batch_size or cfg.batch_size
    table = tuple(table) if table is not None else train_table(cfg)
    if assignment is None:
        if len(table) == 1:  # single geometry: everything is the fallback
            assignment = np.zeros(len(split), dtype=np.int64)
        else:
            extents = extents or sample_extents(split, cfg)
            assignment = assign_buckets(extents, table, use_msg=use_msg)
    order = epoch_order(len(split), shuffle=shuffle, seed=seed, epoch=epoch)

    plan: Plan = []
    open_rows: List[List[int]] = [[] for _ in table]
    pending: List[List[np.ndarray]] = [[] for _ in table]
    for i in order:
        b = int(assignment[i])  # firacheck: allow[HOST-SYNC] host numpy assignment array — the scheduler runs on host index data only, never device values
        open_rows[b].append(int(i))  # firacheck: allow[HOST-SYNC] host numpy permutation entry, same scheduler-side data
        if len(open_rows[b]) < bs:
            continue
        pending[b].append(np.asarray(open_rows[b]))  # firacheck: allow[HOST-SYNC] list-of-host-ints to numpy chunk; no device round-trip
        open_rows[b] = []
        if group_size == 1:
            plan.append(GroupEntry((pending[b].pop(),), table[b], 1))
        elif len(pending[b]) == group_size:
            plan.append(GroupEntry(tuple(pending[b]), table[b], group_size))
            pending[b] = []
    for b, geom in enumerate(table):
        if open_rows[b]:
            pending[b].append(np.asarray(open_rows[b]))  # firacheck: allow[HOST-SYNC] same host-side tail flush as above
        if not pending[b]:
            continue
        if group_size > 1 and accum:
            # accum tail: ONE short group, padded to the stacked shape with
            # all-invalid micro-batches at assembly time
            plan.append(GroupEntry(tuple(pending[b]), geom, group_size))
        else:
            # fused tail (or per-step mode): leftover chunks run per-step
            plan.extend(GroupEntry((c,), geom, 1) for c in pending[b])
        pending[b] = []
    return plan


def stack_group(batches: Sequence[Dict[str, np.ndarray]], *,
                pad_to: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Stack same-geometry host batches on a new leading axis; with
    ``pad_to`` larger than the group, pad with all-zero micro-batches
    (every row invalid, label 0 everywhere — they contribute nothing to the
    accumulated (sum, count), the same mechanism that makes make_batch's
    pad rows free). Identical layout to ``train.step.stack_batches``."""
    group = list(batches)
    if pad_to is not None and len(group) < pad_to:
        pad = {k: np.zeros_like(v) for k, v in group[0].items()}
        group.extend([pad] * (pad_to - len(group)))
    return {k: np.stack([b[k] for b in group]) for k in group[0]}


def plan_programs(plan: Plan) -> List[tuple]:
    """The distinct ``(geometry, pad_to)`` of a plan's dispatches, sorted:
    the programs an epoch runs. The same for every epoch of a split —
    chunk formation counts a bucket's members, whatever their order — so
    the train loop warms and declares this set and no more."""
    return sorted({(e.geom, e.pad_to) for e in plan})


def grouped_assembly_tasks(split: ProcessedSplit, plan: Plan,
                           cfg: FiraConfig, *,
                           batch_size: Optional[int] = None) -> Iterator:
    """One zero-arg assembly task per plan entry for the async Feeder
    (data/feeder.py): a per-step entry builds one ``make_batch`` batch; a
    stacked entry builds its member batches AND stacks them, so the worker
    ``device_put``s the whole K-group as ONE transfer.

    Each batch builds at ITS ENTRY'S geometry (at the full geometry that
    is ``make_batch`` without ``geom``, byte for byte) and carries the
    host-only ``_tag`` (geometry tag, for per-bucket guard labels;
    per-step entries also carry ``_positions`` like
    ``buckets.bucketed_assembly_tasks``)."""
    from fira_tpu.data.batching import make_batch

    bs = batch_size or cfg.batch_size

    def task(entry: GroupEntry):
        def build():
            group = [make_batch(split, c, cfg, batch_size=bs,
                                geom=entry.geom)
                     for c in entry.chunks]
            if entry.pad_to == 1:
                batch = group[0]
                chunk = entry.chunks[0]
                positions = np.full(bs, -1, dtype=np.int64)
                positions[: len(chunk)] = chunk
                batch["_positions"] = positions
            else:
                batch = stack_group(group, pad_to=entry.pad_to)
            batch["_tag"] = geom_tag(entry.geom)
            return batch
        return build

    for entry in plan:
        yield task(entry)


def plan_report(split: ProcessedSplit, cfg: FiraConfig, plan: Plan, *,
                batch_size: Optional[int] = None,
                extents=None) -> Dict:
    """Dispatch-count + padded-FLOP accounting for one epoch plan — the
    numbers bench.py's composed leg reports on every record.

    ``padding_frac_dispatched`` extends ``buckets.padding_report`` to the
    ACTUAL dispatched stream: the denominator prices every dispatched row —
    bucket pad inside chunks, invalid pad rows of partial chunks, and the
    all-invalid accum pad micro-batches — at its dispatch geometry."""
    bs = batch_size or cfg.batch_size
    ext = extents or sample_extents(split, cfg)
    ideal = 0.0
    dispatched = 0.0
    n_commits = 0
    n_grouped = n_per_step = steps = real_batches = 0
    for entry in plan:
        cost = geom_cost(cfg, entry.geom)
        k = max(1, entry.pad_to)
        dispatched += k * bs * cost
        steps += k
        real_batches += len(entry.chunks)
        if entry.pad_to > 1:
            n_grouped += 1
        else:
            n_per_step += 1
        for chunk in entry.chunks:
            n_commits += len(chunk)
            for i in chunk:
                i = int(i)  # firacheck: allow[HOST-SYNC] host numpy index chunk; the accounting never holds device values
                ideal += geom_cost(cfg, BucketGeom(
                    int(ext.ast[i]),  # firacheck: allow[HOST-SYNC] SampleExtents are host numpy arrays (data/buckets.sample_extents); no device value exists in the accounting
                    int(ext.edges[i]) - (ext.ast_change_len - int(ext.ast[i])),  # firacheck: allow[HOST-SYNC] same host-side extents arithmetic
                    max(2, int(ext.msg[i]))))  # firacheck: allow[HOST-SYNC] same host-side extents arithmetic
    return {
        "dispatches": len(plan),
        "grouped_dispatches": n_grouped,
        "per_step_dispatches": n_per_step,
        "steps_dispatched": steps,
        "real_batches": real_batches,
        "commits": n_commits,
        "padding_frac_dispatched": round(
            1.0 - ideal / dispatched, 4) if dispatched else 0.0,
    }
