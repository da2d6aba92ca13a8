"""Paged KV arena: host-side geometry, validation, and byte accounting.

Whole-sequence slot stripes — every slot owning ``tar_len`` cache
positions for its K beams — would couple slot count and target length
through HBM. The slot engine's self-attention caches live in a FIXED
POOL of KV blocks addressed through per-slot block tables instead
(vLLM's PagedAttention,
SOSP '23 — PAPERS.md "Continuous batching / inference serving" — under
this stack's static-shape discipline: fixed pool size P, fixed table
width W, gather/scatter by block id). A slot is handed exactly the
blocks its decode bucket's tar budget reserves at insert and returns
them WHOLE at harvest — freed blocks are unmapped, never zeroed (the
validity mask already multiplies unwritten positions by an exact 0.0,
beam.step_valid_mask), and longer-target decode buckets become new
reservation sizes against the same pool instead of a per-length arena
blow-up.

This module is the HOST half: block-size/pool resolution, the parse-time
knob validation the CLI turns into exit 2 (named-knob messages, matching
parallel.mesh.divisibility_errors style), and the per-slot HBM
accounting the bench records (``kv_bytes_per_slot`` / ``pool_blocks`` /
``pool_utilization``). The device half — the block-table gather/scatter
the attention reads ride — lives in model/layers.py
(``gather_block_kv`` / ``append_block_kv``) and
model.Decoder.decode_step_paged; the allocator driving it is the
engine's scheduler (decode/engine.py).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

from fira_tpu.config import FiraConfig


def declared_decode_tars(cfg: FiraConfig) -> Tuple[int, ...]:
    """Every tar budget a decode slot can be admitted at, ascending.
    ``decode_tar_buckets`` off: just ``cfg.tar_len`` (the decode table
    pins tar full). On: each declared bucket's own tar plus the full
    fallback."""
    tars = {int(cfg.tar_len)}
    if cfg.decode_tar_buckets:
        for _ast, _edges, tar in cfg.buckets:
            # firacheck: allow[HOST-SYNC] cfg.buckets entries are parse-time host ints, not device values; this runs once at engine construction
            tars.add(int(tar))
    return tuple(sorted(tars))


def auto_block_size(tars: Tuple[int, ...]) -> int:
    """Default block size: the largest common divisor of every declared
    tar budget that is <= min(16, smallest_tar // 2) — at least two
    blocks per sequence whenever the geometry allows it, capped at the
    usual lane-friendly 16. Always valid (1 divides everything)."""
    g = 0
    for t in tars:
        # firacheck: allow[HOST-SYNC] tar budgets are host ints from the config table; knob resolution happens once, pre-compile
        g = math.gcd(g, int(t))
    cap = max(1, min(16, min(tars) // 2))
    best = 1
    for d in range(1, g + 1):
        if g % d == 0 and d <= cap:
            best = d
    return best


def resolve_block_size(cfg: FiraConfig) -> int:
    return int(cfg.kv_block_size) or auto_block_size(declared_decode_tars(cfg))


def blocks_per_seq(tar: int, block_size: int) -> int:
    """Blocks one slot reserves for a ``tar``-budget sequence (all K
    beams ride inside the block, so no beam factor here)."""
    return -(-int(tar) // int(block_size))


def resolved_slots(cfg: FiraConfig) -> Tuple[int, int]:
    """(per-replica slots, replica count) under the fleet's slot split:
    a nonzero ``engine_slots`` is the fleet TOTAL; 0 gives every replica
    ``test_batch_size`` slots of its own."""
    reps = max(1, int(cfg.engine_replicas))
    total = int(cfg.engine_slots)
    if total:
        return max(1, total // reps), reps
    return int(cfg.test_batch_size), reps


def auto_pool_blocks(cfg: FiraConfig, slots: int) -> int:
    """Full-residency default: every slot can hold a full ``tar_len``
    sequence concurrently — admission never blocks on blocks."""
    return int(slots) * blocks_per_seq(cfg.tar_len, resolve_block_size(cfg))


def paging_errors(cfg: FiraConfig) -> List[str]:
    """Parse-time paging-knob admission check (the paged twin of
    parallel.mesh.divisibility_errors / fleet_divisibility_errors): one
    named-knob message per violation, CLI exit 2. Checks:

    - the whole-sequence arena is not asked for: it is gone, the engine's
      arena IS the paged pool;
    - ``kv_block_size`` divides every declared decode tar budget (table
      width x block must tile each budget exactly);
    - ``kv_pool_blocks`` splits evenly across ``engine_replicas`` (it is
      the fleet TOTAL, like engine_slots);
    - per replica, pool >= slots x ceil(smallest tar / block) — the
      full-slot-concurrency floor on the smallest geometry — and
      pool >= ceil(largest tar / block) — one worst-case sample must
      always fit when the pool is empty, the no-livelock floor.
    """
    if not cfg.engine_paged_kv:
        return ["engine_paged_kv False asks for the whole-sequence K/V "
                "arena, which no longer exists: the slot engine's arena is "
                "the paged pool (leave the knob at its default, True)"]
    if not cfg.decode_engine:
        return []
    errs: List[str] = []
    tars = declared_decode_tars(cfg)
    bs = resolve_block_size(cfg)
    if bs < 1:
        return [f"kv_block_size {cfg.kv_block_size} must be >= 1"]
    for t in tars:
        if t % bs:
            errs.append(
                f"kv_block_size {bs} does not divide decode tar budget {t} "
                f"(declared tars: {list(tars)}); block tables must tile "
                f"every budget exactly")
    slots, reps = resolved_slots(cfg)
    pool_total = int(cfg.kv_pool_blocks)
    if not pool_total:
        return errs  # auto pool: full residency, floors hold by construction
    if pool_total % reps:
        errs.append(
            f"kv_pool_blocks {pool_total} is not divisible by "
            f"engine_replicas {reps} (the fleet splits the total block "
            f"pool evenly across replicas, like engine_slots)")
        return errs
    pool = pool_total // reps
    if not errs:  # floors only meaningful once bs tiles the tars
        floor = slots * blocks_per_seq(tars[0], bs)
        if pool < floor:
            errs.append(
                f"kv_pool_blocks {pool} per replica < engine slots {slots} "
                f"x ceil(tar {tars[0]} / kv_block_size {bs}) = {floor}; "
                f"the pool must keep every slot servable on the smallest "
                f"decode tar budget")
        worst = blocks_per_seq(tars[-1], bs)
        if pool < worst:
            errs.append(
                f"kv_pool_blocks {pool} per replica < "
                f"ceil(tar {tars[-1]} / kv_block_size {bs}) = {worst}; one "
                f"largest-budget sample must fit an empty pool or the "
                f"scheduler can never admit it (livelock)")
    return errs


def prefix_cache_errors(cfg: FiraConfig) -> List[str]:
    """Parse-time prefix-cache knob admission check (docs/DECODE_ENGINE.md
    "Prefix cache & dedup"): one named-knob message per violation, CLI
    exit 2 — the cache twin of :func:`paging_errors`. The cache seats
    cached prefill artifacts into ENGINE slots, so it requires the engine
    path; its LRU needs at least one entry of capacity."""
    if not cfg.prefix_cache:
        return []
    errs: List[str] = []
    if not cfg.decode_engine:
        errs.append(
            "prefix_cache requires the decode engine (--engine, --perf "
            "production, or cli serve): cached prefill artifacts are "
            "seated into engine slots — the batched beam has no seat to "
            "map them into")
    if cfg.prefix_cache_entries < 1:
        errs.append(
            f"prefix_cache_entries {cfg.prefix_cache_entries} must be "
            f">= 1 cached prefill entry when prefix_cache is on (the LRU "
            f"needs capacity to hold at least one artifact set)")
    if cfg.prefix_cache_bytes < 0:
        errs.append(
            f"prefix_cache_bytes {cfg.prefix_cache_bytes} must be >= 0 "
            f"(0 = unbounded host bytes; otherwise the per-replica LRU "
            f"evicts until its payload bytes fit the budget)")
    return errs


def kv_itemsize(cfg: FiraConfig) -> int:
    """Bytes per K/V arena element under the serving tier (docs/
    DECODE_ENGINE.md "Low-precision tiers"): 2 when ``cfg.kv_dtype`` is
    ``bf16``, else the f32 default's 4. Host-side mirror of the engine's
    own accounting — the engine derives the itemsize from the prefill
    chunk's ``cache_seed`` dtype at allocation time; bench/test callers
    use this helper so their expected-bytes math names the same knob."""
    return 2 if cfg.kv_dtype == "bf16" else 4


def block_bytes(cfg: FiraConfig, block_size: int, itemsize: int) -> int:
    """HBM bytes of ONE pool block pair (K and V): all layers x all beam
    lanes x heads x block positions x head dim."""
    d_head = cfg.embedding_dim // cfg.num_head
    return (2 * cfg.num_layers * cfg.beam_size * cfg.num_head
            * int(block_size) * d_head * int(itemsize))


def kv_bytes_per_slot(cfg: FiraConfig, *, block_size: int,
                      pool_blocks: int, slots: int, itemsize: int) -> int:
    """The machine-recorded HBM claim: committed K+V self-attention cache
    bytes per engine slot. The pool is the commitment — its bytes
    amortize over the slots it serves, which is exactly where the
    equal-memory slot-count gain (or the longer-tar headroom) shows up."""
    return block_bytes(cfg, block_size, itemsize) * int(pool_blocks) \
        // max(1, int(slots))


def leaves_kv_bytes_per_slot(leaves, slots: int) -> int:
    """:func:`kv_bytes_per_slot` from the arena's DECLARED leaves
    (decode/slot_model.Leaf): the bytes of every leaf a model marks ``kv``
    — pools, a latent cache of whatever width — amortized over the slots
    they serve. For FIRA's K/V pools this is the number the per-head
    formula above gives."""
    return sum(leaves_kv_bytes_by_kind(leaves, slots).values())


def leaves_kv_bytes_by_kind(leaves, slots: int) -> Dict[str, int]:
    """Per-slot bytes of the ``kv`` leaves, added up by what they DECLARE
    to hold (``Leaf.kv_kind``): ``"full"`` (prompts kept whole),
    ``"window"`` (rings of a prompt's last positions), ``"state"``
    (state of fixed size — a beam lane's recurrent state, or a prompt's
    retention state once a slot, read-only and shared by its beams: bytes
    a slot that do not grow with the prompt), ``""`` (the pools of
    generated positions, and any cache a model does not tell apart by
    layer type). A model whose layers are of one kind has one entry. A
    leaf whose stored shape pads its values (``Leaf.kv_shape``: FIRA's
    pool blocks, rounded up to whole sublane tiles) counts the values."""
    import numpy as np

    total: Dict[str, int] = {}
    for leaf in leaves.values():
        if leaf.kv:
            total[leaf.kv_kind] = total.get(leaf.kv_kind, 0) + int(
                np.prod(leaf.kv_shape or leaf.shape)) \
                * np.dtype(leaf.dtype).itemsize
    return {kind: n // max(1, int(slots)) for kind, n in total.items()}
