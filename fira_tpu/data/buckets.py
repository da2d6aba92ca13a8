"""Bucketed-geometry compilation + length-aware batch packing.

Every batch used to be padded to the worst-case geometry — the full
``ast_change_len`` node tail, ``max_edges`` COO slots, ``tar_len`` message
positions — yet the corpus is dominated by small commits, so most device
FLOPs multiplied pad zeros (the reference pays the same tax with dense
per-sample adjacencies, Dataset.py:336-343). This module declares a SMALL
FIXED FAMILY of padding geometries ("buckets"), assigns each sample to the
smallest admissible bucket, and packs same-bucket samples into batches, so
XLA compiles one program per bucket (N programs total, pre-warmed once at
startup — still ZERO post-warmup retraces, the PR-1 invariant).

Which axes are bucketable
-------------------------
A bucket is ``(ast_len, max_edges, tar_len)``:

- ``ast_len``  truncates the AST+change node region — the only node region
  that CAN shrink: ``sou_len`` and ``sub_token_len`` are baked into the
  copy-label id space (``vocab + diff_pos`` / ``vocab + sou_len + sub_pos``,
  graph_build.copy_labels) and into the fused output width, so shrinking
  them would re-key the supervision. Truncating the ast tail is exact for
  every real node: pad ast nodes only ever connect to themselves (the
  reference's unconditional self-loops, Dataset.py:271-275), so dropping
  them removes zero-contribution rows/columns of the adjacency.
- ``max_edges``  shrinks the COO pad; pad edges scatter exact zeros, so
  fewer of them change nothing.
- ``tar_len``  truncates decoder positions past the sample's message; the
  loss masks them to exactly zero and causal attention keeps real-position
  outputs bit-identical. Decode does NOT bucket this axis (the model
  decides the output length, which must not be clipped): decode buckets
  are ``(ast_len, max_edges, full tar_len)``.

The edge/node coupling: ``build_adjacency`` appends one self-loop per node
of the FULL geometry, ascending, AFTER all family edges — so the edges of
the truncated node tail are exactly the LAST ``graph_len - bucket_graph_len``
entries of each sample's ragged edge slice, and ``make_batch`` drops them
by shortening the slice (data/batching.py, ``geom=``). Bit-exactness of
loss and decoded tokens at bucket geometry vs full pad is pinned by
tests/test_buckets.py.

Determinism contract (extends the PR-2 feeder contract)
-------------------------------------------------------
The packed batch order is a pure function of ``(seed, epoch, bucket
table)``: the packer starts from the SAME permutation
``data.batching.epoch_order`` draws, walks it greedily appending each
sample to its bucket's open chunk, and emits a chunk the moment it fills
(tails flush in table order). With ``shuffle=False`` (dev/decode) packing
is a stable partition by bucket — sort-by-length packing that preserves
in-bucket corpus order; drivers restore output order from the
``_positions`` host-only field each batch carries.

What ``cfg.buckets = ()`` means (the default of every configuration)
--------------------------------------------------------------------
NOT "pad every row to ``max_edges``". ``max_edges`` is the admission bound
of ``make_batch`` (no commit of the 90,661-commit corpus has more edges),
not the width of the wire. The two tables part at :func:`train_table` /
:func:`decode_table`:

- TRAINING takes the **edge ladder** (:func:`edge_ladder`): the COO pad of
  a dispatch is ``max_edges / 2^k``, the least rung that holds its widest
  commit; the AST tail and the target length stay FULL (they change the
  products the step computes — those remain the user's table to cut).
  The rung is read off the input: no field, flag or variable selects it,
  and the train loop compiles only the rungs its split populates.
- DECODE (and the dev gate) stays the full geometry alone: the engine
  prewarms one prefill and one insert program a geometry at set-up, so a
  rung there costs every start a compile; it waits on a table of prefill
  batch sizes an edge rung can ride on (ROADMAP S11).

Sanitizer / firacheck interplay: see docs/BUCKETING.md. Each bucket's
programs get their own compile-guard label (``train_step[a16.e256.t8]``),
drivers pre-warm and then ``CompileGuard.declare`` the family, and a
dispatch outside the declared family raises — geometry drift is a
machine-enforced non-event, not a recompile storm.

Composition with the grouped device programs (fused_steps / accum_steps)
lives in data/grouping.py: its scheduler walks the same permutation,
reuses this module's table/assignment/extents machinery, and packs
bucket-HOMOGENEOUS K-groups so the padding win and the dispatch-
amortization win stack. ``packed_plan`` below stays the dev/decode packer
(stable partition, sort-by-length) and the ``group_size == 1`` reference
the grouped plan is pinned equal to.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.data.dataset import ProcessedSplit


class BucketGeom(NamedTuple):
    """One padding geometry: the bucketable axes of a batch."""

    ast_len: int     # AST+change node region length (<= cfg.ast_change_len)
    max_edges: int   # per-sample COO pad length (<= cfg.max_edges)
    tar_len: int     # message positions (<= cfg.tar_len)


def geom_tag(geom: BucketGeom) -> str:
    """Stable label fragment for guard labels / reports: 'a16.e256.t8'."""
    return f"a{geom.ast_len}.e{geom.max_edges}.t{geom.tar_len}"


def full_geom(cfg: FiraConfig) -> BucketGeom:
    return BucketGeom(cfg.ast_change_len, cfg.max_edges, cfg.tar_len)


def geom_cost(cfg: FiraConfig, geom: BucketGeom) -> float:
    """Per-sample FLOP proxy at a geometry — the packer's and the padding
    metric's unit of account. Mirrors the geometry-dependent MXU terms of
    bench._analytic_flops (GCN fc + dense A.x, decoder attention/FFN,
    fused head) plus a small per-edge scatter term; constant terms
    (Combination, source-side projections) are included so padding
    fractions are not overstated."""
    d, L = cfg.embedding_dim, cfg.num_layers
    s = cfg.sou_len + cfg.sub_token_len          # copy span: not bucketable
    g = s + geom.ast_len                          # bucketed node count
    t = geom.tar_len
    v = cfg.vocab_size + s
    enc = L * (2 * g * g * d                      # dense A.x bmm
               + 2 * g * d * d * 2                # GCN fc1/fc2
               + 4 * cfg.sou_len * d * d * 2)     # Combination projections
    dec = L * ((6 * t + 2 * s) * d * d * 2
               + 2 * (t * t + t * s) * d * 2
               + 2 * t * d * cfg.ffn_mult * d * 2)
    head = (t * d * v * 2 + s * d * d * 2 + t * d * d * 2 + t * s * d * 2)
    return float(enc + dec + head + 8.0 * geom.max_edges)


def _validated(cfg: FiraConfig, geom: BucketGeom) -> BucketGeom:
    full = full_geom(cfg)
    g = BucketGeom(*(int(x) for x in geom))  # firacheck: allow[HOST-SYNC] config ints from the declared bucket table; no device value exists in the packer
    if not (1 <= g.ast_len <= full.ast_len):
        raise ValueError(f"bucket ast_len {g.ast_len} outside "
                         f"[1, {full.ast_len}]")
    if not (1 <= g.tar_len <= full.tar_len):
        raise ValueError(f"bucket tar_len {g.tar_len} outside "
                         f"[1, {full.tar_len}]")
    min_edges = cfg.sou_len + cfg.sub_token_len + g.ast_len
    if not (min_edges <= g.max_edges <= full.max_edges):
        # every sample carries one self-loop per node of its geometry, so a
        # bucket with fewer edge slots than nodes can never admit anything
        raise ValueError(
            f"bucket max_edges {g.max_edges} outside "
            f"[{min_edges} (= nodes at ast_len {g.ast_len}, the self-loop "
            f"floor), {full.max_edges}]")
    return g


def bucket_table(cfg: FiraConfig) -> Tuple[BucketGeom, ...]:
    """The DECLARED bucket family: cfg.buckets validated, sorted by FLOP
    cost ascending, with the full geometry appended as the always-admissible
    fallback. ``cfg.buckets = ()`` yields just the full geometry — what
    :func:`decode_table` builds on; training asks :func:`train_table`."""
    full = full_geom(cfg)
    geoms = []
    for entry in cfg.buckets:
        g = _validated(cfg, BucketGeom(*entry))
        if g != full and g not in geoms:
            geoms.append(g)
    geoms.sort(key=lambda g: geom_cost(cfg, g))
    return tuple(geoms) + (full,)


def edge_ladder(cfg: FiraConfig) -> Tuple[BucketGeom, ...]:
    """The halving ladder of the COO pad: ``max_edges // 2^k`` for k = 0,
    1, 2, ... while the rung holds the geometry's self-loops
    (:func:`_validated`'s floor), ascending, AST tail and target length
    FULL on every rung — fira-full: 768, 1536, 3072, 6144 edge slots a
    row. A pure function of the configuration; the last rung is the full
    geometry, so the ladder is a bucket table like any declared one."""
    full = full_geom(cfg)
    rungs = [full]
    while rungs[0].max_edges // 2 >= cfg.graph_len:   # a self-loop a node
        rungs.insert(0, full._replace(max_edges=rungs[0].max_edges // 2))
    return tuple(rungs)


def train_table(cfg: FiraConfig) -> Tuple[BucketGeom, ...]:
    """The TRAIN plan's table (data/grouping.grouped_plan, train/loop.py):
    the user's declared table where there is one, else the edge ladder.

    HERE the train and decode tables part, for one stated reason: a train
    program a rung is compiled once and only for the rungs the split
    populates, while the decode engine pays a prefill and an insert
    program a geometry at every start (``setup_s``) — so
    :func:`decode_table` keeps ``cfg.buckets = ()`` at the full geometry
    alone until its prefill has a table of batch sizes for an edge rung
    to ride on (ROADMAP S11)."""
    return bucket_table(cfg) if cfg.buckets else edge_ladder(cfg)


# --------------------------------------------------------------------------
# per-sample extents + admissibility
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SampleExtents:
    """Per-sample used lengths along the bucketable axes (full-geometry
    edge counts; use :meth:`edges_at` for a truncated node region)."""

    ast: np.ndarray    # used AST+change nodes (labels OR family edges)
    edges: np.ndarray  # ragged edge count at FULL geometry (incl. all
                       # self-loops; the truncated tail subtracts off)
    msg: np.ndarray    # used msg/msg_tar positions (START..EOS inclusive)
    ast_change_len: int  # the full region length the counts were taken at

    def edges_at(self, ast_len: int) -> np.ndarray:
        """Edge counts once the node tail is truncated to ``ast_len``: the
        dropped pad nodes carried exactly one self-loop each."""
        return self.edges - (self.ast_change_len - ast_len)

    def admissible(self, geom: BucketGeom, *, use_msg: bool = True
                   ) -> np.ndarray:
        ok = (self.ast <= geom.ast_len) \
            & (self.edges_at(geom.ast_len) <= geom.max_edges)
        if use_msg:
            ok = ok & (self.msg <= geom.tar_len)
        return ok


def _last_nonzero_extent(a: np.ndarray) -> np.ndarray:
    """Per-row index-past-last-nonzero (0 for all-zero rows)."""
    nz = a != 0
    return np.where(nz.any(axis=1),
                    a.shape[1] - np.argmax(nz[:, ::-1], axis=1), 0)


def sample_extents(split: ProcessedSplit, cfg: FiraConfig) -> SampleExtents:
    """The split's extents, measured on the first call and kept with the
    split (``ProcessedSplit.derived``; its arrays are written once, when
    it is built): the train plan asks once an epoch, on the thread that
    feeds the dispatch loop, and must not walk every edge each time."""
    key = ("extents", cfg.sou_len, cfg.sub_token_len, cfg.ast_change_len)
    if key not in split.derived:
        split.derived[key] = _measure_extents(split, cfg)
    return split.derived[key]


def _measure_extents(split: ProcessedSplit, cfg: FiraConfig) -> SampleExtents:
    from fira_tpu.data.graph_build import EDGE_KIND_SELF_LOOP

    arr = split.arrays
    n = len(split)
    offsets = arr["edge_offsets"]
    counts = np.diff(offsets).astype(np.int64)

    # used ast nodes: nonzero labels, cross-checked against where family
    # (non-self-loop) edges actually point — belt and braces, both are
    # supposed to agree for graph_build output
    ast_ext = _last_nonzero_extent(arr["ast_change"]).astype(np.int64)
    ast_base = cfg.sou_len + cfg.sub_token_len
    hi_node = np.maximum(arr["edge_senders"], arr["edge_receivers"]
                         ).astype(np.int64)
    fam = (arr["edge_kinds"] != EDGE_KIND_SELF_LOOP) & (hi_node >= ast_base)
    if fam.any():
        owner = np.repeat(np.arange(n), counts)
        edge_ext = np.zeros(n, dtype=np.int64)
        np.maximum.at(edge_ext, owner[fam], hi_node[fam] - ast_base + 1)
        ast_ext = np.maximum(ast_ext, edge_ext)

    msg_ext = np.maximum(_last_nonzero_extent(arr["msg"]),
                         _last_nonzero_extent(arr["msg_tar"])).astype(np.int64)
    return SampleExtents(ast=ast_ext, edges=counts, msg=msg_ext,
                         ast_change_len=cfg.ast_change_len)


def assign_buckets(extents: SampleExtents, table: Sequence[BucketGeom], *,
                   use_msg: bool = True) -> np.ndarray:
    """Smallest admissible bucket per sample (table sorted cost-ascending;
    the trailing full geometry admits everything)."""
    n = len(extents.ast)
    out = np.full(n, len(table) - 1, dtype=np.int64)
    unassigned = np.ones(n, dtype=bool)
    for b, geom in enumerate(table[:-1]):
        fit = unassigned & extents.admissible(geom, use_msg=use_msg)
        out[fit] = b
        unassigned &= ~fit
    return out


def _round_up(x: int, unit: int) -> int:
    return ((int(x) + unit - 1) // unit) * unit  # firacheck: allow[HOST-SYNC] host numpy quantile scalar; the packer never holds device values


def choose_buckets(split: ProcessedSplit, cfg: FiraConfig,
                   n_buckets: int = 3) -> Tuple[Tuple[int, int, int], ...]:
    """Bucket table from the split's length histograms: per-axis quantiles
    at evenly spaced levels, rounded up to lane-friendly units (ast -> 8,
    edges -> 64, msg -> 4) and capped at the full geometry. Deterministic
    for a given split. The returned tuples go into ``cfg.buckets``; the
    full geometry stays the implicit fallback and is never declared."""
    if n_buckets < 1:
        raise ValueError(f"n_buckets must be >= 1, got {n_buckets}")
    ext = sample_extents(split, cfg)
    full = full_geom(cfg)
    out: List[Tuple[int, int, int]] = []
    for i in range(n_buckets):
        q = (i + 1) / n_buckets
        ast = min(full.ast_len,
                  max(1, _round_up(np.quantile(ext.ast, q), 8)))
        tar = min(full.tar_len,
                  max(2, _round_up(np.quantile(ext.msg, q), 4)))
        edges = min(full.max_edges,
                    _round_up(np.quantile(ext.edges_at(ast), q), 64))
        edges = max(edges, cfg.sou_len + cfg.sub_token_len + ast)
        geom = (ast, edges, tar)
        if geom != tuple(full) and geom not in out:
            out.append(geom)
    return tuple(out)


# --------------------------------------------------------------------------
# packing
# --------------------------------------------------------------------------

Plan = List[Tuple[np.ndarray, BucketGeom]]


def packed_plan(split: ProcessedSplit, cfg: FiraConfig, *,
                batch_size: Optional[int] = None,
                shuffle: bool = False,
                seed: int = 0,
                epoch: int = 0,
                table: Optional[Sequence[BucketGeom]] = None,
                extents: Optional[SampleExtents] = None,
                assignment: Optional[np.ndarray] = None,
                use_msg: bool = True) -> Plan:
    """The deterministic bucketed batch order of one epoch: a list of
    (index chunk, bucket geometry) pairs.

    shuffle=True (train): the exact ``epoch_order(seed, epoch)``
    permutation is walked greedily — each sample joins its bucket's open
    chunk, which is emitted the moment it fills; tails flush in table
    order. shuffle=False (dev/decode): a stable partition by bucket
    (in-bucket corpus order preserved) — sort-by-length packing.
    """
    from fira_tpu.data.batching import epoch_order

    bs = batch_size or cfg.batch_size
    table = tuple(table) if table is not None else bucket_table(cfg)
    if assignment is None:
        extents = extents or sample_extents(split, cfg)
        assignment = assign_buckets(extents, table, use_msg=use_msg)
    order = epoch_order(len(split), shuffle=shuffle, seed=seed, epoch=epoch)

    plan: Plan = []
    if shuffle:
        open_chunks: List[List[int]] = [[] for _ in table]
        for i in order:
            b = int(assignment[i])  # firacheck: allow[HOST-SYNC] host numpy assignment array — the packer runs on host index data only, never device values
            open_chunks[b].append(int(i))  # firacheck: allow[HOST-SYNC] host numpy permutation entry, same packer-side data
            if len(open_chunks[b]) == bs:
                plan.append((np.asarray(open_chunks[b]), table[b]))  # firacheck: allow[HOST-SYNC] list-of-host-ints to numpy chunk; no device round-trip
                open_chunks[b] = []
        for b, chunk in enumerate(open_chunks):
            if chunk:
                plan.append((np.asarray(chunk), table[b]))  # firacheck: allow[HOST-SYNC] same host-side tail flush as above
        return plan
    for b, geom in enumerate(table):
        members = order[assignment[order] == b]
        for start in range(0, len(members), bs):
            plan.append((members[start : start + bs], geom))
    return plan


def bucketed_assembly_tasks(split: ProcessedSplit, plan: Plan,
                            cfg: FiraConfig, *,
                            batch_size: Optional[int] = None,
                            stamp=None) -> Iterator:
    """One ``make_batch(geom=...)`` task per plan entry, for the async
    Feeder. Each batch carries two HOST-ONLY fields (stripped before
    device_put, data/feeder.py): ``_positions`` — the split-local sample
    index per row (-1 on pad rows), so drivers can restore corpus output
    order after packing reordered the stream — and ``_tag`` — the bucket's
    geometry tag for per-bucket compile-guard labels.

    ``stamp``: optional post-assembly hook run WORKER-side, like
    feeder.assembly_tasks' — the decode drivers pass
    decode.prefix_cache.stamp_digests under ``cfg.prefix_cache`` so
    content digests never hash on the scheduler thread."""
    from fira_tpu.data.batching import make_batch

    bs = batch_size or cfg.batch_size

    def task(chunk: np.ndarray, geom: BucketGeom):
        def build():
            batch = make_batch(split, chunk, cfg, batch_size=bs, geom=geom)
            positions = np.full(bs, -1, dtype=np.int64)
            positions[: len(chunk)] = chunk
            batch["_positions"] = positions
            batch["_tag"] = geom_tag(geom)
            return stamp(batch) if stamp is not None else batch
        # a failing worker's FeederTaskError names the poisoned chunk:
        # split positions + bucket geometry (data/feeder.task_note)
        from fira_tpu.data.feeder import task_note

        build.note = task_note(chunk, geom_tag=geom_tag(geom),
                               site="bucketed_assembly_tasks")
        return build

    for chunk, geom in plan:
        yield task(chunk, geom)


# --------------------------------------------------------------------------
# program-family warmup
# --------------------------------------------------------------------------

def decode_table(cfg: FiraConfig) -> Tuple[BucketGeom, ...]:
    """The decode-side bucket family, deduplicated, cost-sorted, full
    fallback last. Built on the DECLARED table: ``cfg.buckets = ()`` is
    the full geometry alone here, never the edge ladder — see
    :func:`train_table` for why the two part.

    Default (``cfg.decode_tar_buckets = False``): tar_len pinned to the
    FULL value on every bucket — beam output length is model-decided and
    must not be clipped.

    ``decode_tar_buckets = True`` (the longer-target-geometry mode,
    docs/DECODE_ENGINE.md "Paged KV arena"): each declared bucket KEEPS
    its own tar_len, assignment goes by reference-message extent
    (``use_msg=True`` — the caller's packing must match), and the slot
    engine caps each sample's generation at its bucket's tar budget,
    which is exactly the paged-KV block reservation the slot is seated
    with. This turns a raised ``cfg.tar_len`` (say 64) plus a
    common-case bucket (say tar 30) into two RESERVATION sizes against
    one block pool and ONE step program — not a per-length program or
    arena explosion. The batched-beam path ignores the cap (its scan is
    always the full budget), so tar-bucketed decode is equivalence-
    claimed only within the engine family (file-byte determinism across
    schedules is pinned by tests/test_buckets.py)."""
    full = full_geom(cfg)
    geoms: List[BucketGeom] = []
    for g in bucket_table(cfg)[:-1]:
        d = (g if cfg.decode_tar_buckets
             else BucketGeom(g.ast_len, g.max_edges, cfg.tar_len))
        if d != full and d not in geoms:
            geoms.append(d)
    geoms.sort(key=lambda g: geom_cost(cfg, g))
    return tuple(geoms) + (full,)


def warmup_batch(split: ProcessedSplit, cfg: FiraConfig, geom: BucketGeom,
                 batch_size: int):
    """An all-pad batch at one bucket geometry — the compile key for that
    bucket's program, with zero training effect (every row is invalid; the
    loss divides by max(count, 1))."""
    from fira_tpu.data.batching import make_batch

    return make_batch(split, np.arange(0), cfg, batch_size=batch_size,
                      geom=geom)


# --------------------------------------------------------------------------
# padding / wasted-FLOP metric
# --------------------------------------------------------------------------

def padding_report(split: ProcessedSplit, cfg: FiraConfig,
                   table: Optional[Sequence[BucketGeom]] = None, *,
                   use_msg: bool = True) -> Dict:
    """Corpus-level padded-FLOP accounting, single-geometry vs bucketed.

    ``padding_frac`` = 1 - (sum of per-sample ideal cost at the sample's
    own extents) / (sum of cost at the geometry actually dispatched) —
    the share of device FLOPs spent multiplying pad. Per-bucket rows ride
    along so the table's coverage is auditable."""
    table = tuple(table) if table is not None else bucket_table(cfg)
    ext = sample_extents(split, cfg)
    assignment = assign_buckets(ext, table, use_msg=use_msg)
    # scalar per-sample arithmetic: edges at the sample's own ast extent is
    # just its count minus its truncated self-loop tail (calling edges_at
    # per sample would rebuild a full length-n array each iteration)
    ideal = np.asarray([
        geom_cost(cfg, BucketGeom(
            int(ext.ast[i]),
            int(ext.edges[i]) - (ext.ast_change_len - int(ext.ast[i])),
            max(2, int(ext.msg[i]))))
        for i in range(len(split))
    ])
    full_cost = geom_cost(cfg, full_geom(cfg))
    bucket_costs = np.asarray([geom_cost(cfg, g) for g in table])
    assigned = bucket_costs[assignment]
    per_bucket = []
    for b, geom in enumerate(table):
        members = assignment == b
        n = int(members.sum())
        row = {"geom": geom_tag(geom), "n": n}
        if n:
            row["padding_frac"] = round(
                1.0 - float(ideal[members].sum())
                / float(assigned[members].sum()), 4)
        per_bucket.append(row)
    return {
        "n_samples": len(split),
        "padding_frac_single": round(
            1.0 - float(ideal.sum()) / (full_cost * len(split)), 4),
        "padding_frac_bucketed": round(
            1.0 - float(ideal.sum()) / float(assigned.sum()), 4),
        "flops_ratio_bucketed_vs_single": round(
            float(assigned.sum()) / (full_cost * len(split)), 4),
        "buckets": per_bucket,
    }


# --- prompt-length buckets (a token model's key block: cfg.lm.prompt_buckets) -

def prompt_tag(bucket: int) -> str:
    """Geometry tag of a prompt-length bucket in the engine's program
    family: ``engine_prefill[p1024]``, ``engine_insert[p1024]``."""
    return f"p{bucket}"


def prompt_bucket(lm, length: int) -> int:
    for b in lm.prompt_buckets:
        if length <= b:
            return b
    raise ValueError(f"a prompt of {length} tokens exceeds the largest "
                     f"bucket {lm.prompt_len_max} (lm.prompt_buckets)")


def prompt_batch(lm, bucket: int, rows) -> Dict:
    """One prefill dispatch of ``bucket``-long prompts. ``rows``: up to
    ``lm.bucket_rows(bucket)`` of (position, prompt ids, max_new_tokens).
    Host-only fields ("_"): the rows' stream positions, real lengths and
    position limits (a request generates ``max_new`` tokens after
    <start>, so its slot's limit is ``max_new + 1``), and the tag."""
    B = lm.bucket_rows(bucket)
    out = {"tokens": np.zeros((B, bucket), np.int32),
           "lengths": np.zeros((B,), np.int32),
           "valid": np.zeros((B,), bool),
           "_positions": np.full((B,), -1, np.int64),
           "_limits": np.ones((B,), np.int32),
           "_tag": prompt_tag(bucket)}
    for r, (pos, ids, max_new) in enumerate(rows):
        out["tokens"][r, :len(ids)] = ids
        out["lengths"][r] = len(ids)
        out["valid"][r] = True
        out["_positions"][r] = pos
        out["_limits"][r] = max_new + 1
    out["_prompt_len"] = out["lengths"]
    return out


def prompt_batches(lm, requests, flush: bool = True):
    """Form prefill batches from a stream of (position, prompt ids,
    max_new_tokens): a request waits in its length bucket until the bucket
    holds a dispatch's worth (``lm.prefill_token_budget`` padded tokens),
    so every dispatch but the stream's last few is full. ``flush``: emit
    the part-filled buckets when the stream ends."""
    waiting = {b: [] for b in lm.prompt_buckets}
    for req in requests:
        b = prompt_bucket(lm, len(req[1]))
        waiting[b].append(req)
        if len(waiting[b]) == lm.bucket_rows(b):
            yield prompt_batch(lm, b, waiting[b])
            waiting[b] = []
    if flush:
        for b, rows in waiting.items():
            if rows:
                yield prompt_batch(lm, b, rows)


def prompt_tasks(lm, requests, flush: bool = True):
    """:func:`prompt_batches` as feeder tasks (data/feeder.py)."""
    for batch in prompt_batches(lm, requests, flush):
        yield lambda b=batch: b


def prompt_warm_batches(lm):
    """One all-pad batch a bucket with its tag: the engine's prewarm."""
    return [(prompt_batch(lm, b, []), prompt_tag(b))
            for b in lm.prompt_buckets]
