"""Slot-refill continuous-batching decode engine.

The batched beam (decode/beam.py) dispatches whole batches: even with
``beam_early_exit`` the while_loop runs until the batch's LONGEST message
settles, so on real corpora (mean message ~8-10 tokens against the
tar_len-1 = 29 step budget) most rows of a dispatch are finished beams
burning device cycles. This module applies iteration-level continuous
batching (Orca, OSDI '22) under this stack's static-shape regime (slots as
a fixed-geometry KV arena, vLLM SOSP '23 — PAPERS.md "Continuous batching
/ inference serving"): a fixed arena of S slots, each holding one
sample's beam mid-flight at its OWN decode depth, advanced one token per
step program; settled slots are harvested and refilled with freshly
prefilled requests, so wall clock scales with TOTAL TOKENS EMITTED, not
with per-batch max length.

Program family (all fixed-shape, labelled for the compile guard —
``engine_prefill[<geom>]`` x the decode bucket table, ``engine_step``,
``engine_insert``; the harvest dispatches no program of its own: it reads
the step's own outputs; zero post-warmup retraces):

- **prefill** (one per decode bucket geometry): encoder forward +
  cross-attention K/V + copy-head source projection, once a request (a
  slot's beams share them), for ONE packed batch of new requests —
  the per-batch preamble of the batched beam up to its K-fold repeat, on
  exactly the batches the existing bucketed/sorted packer emits (the
  feeder assembles and ships them asynchronously, as for every driver).
- **step** (single geometry — the bucketable axes never reach the decoder:
  ``sou_len``/``sub_token_len`` are pinned by the copy-label id space and
  decode pins ``tar_len`` full): advance every live slot's beam
  ``cfg.engine_harvest_every`` positions at the slot's own depth
  (model.dist_parts_step_paged; the per-row ``s`` vector path of
  beam._selection_tail), with a per-slot
  finished/done mask instead of the batch path's global early-exit
  predicate. Idle/done slots compute garbage that is blended away — they
  are the occupancy loss the refill loop exists to keep near zero.
- **insert**: scatter up to one prefilled chunk's rows into freed slots
  (slot ids are data, not shapes: a (C,) vector with the out-of-range
  sentinel S marking rows not consumed this call, ``mode="drop"``).

Equivalence contract (pinned by tests/test_engine.py against the batched
beam in all four of ITS kv-cache x factored-topk forms): per sample, the
engine's tokens are BIT-EXACT equal to the batched beam's, and its probs
agree to float32 rounding (the paged self-attention sums the same terms
among the exact zeros of the other beam lanes). The argument has three legs:

1. beam search is per-sample independent — every batched-beam op acts
   row-wise (embeds, per-row matmuls, attention over the row's own
   sequence, per-row top-k), so a sample's trajectory does not depend on
   its batch neighbours (the test_batch_size knob already rides on this);
2. the step program runs the SAME selection math at a per-row position
   vector (beam._selection_tail treats scalar and vector ``s``
   identically per row), against the same prefill values the batched
   beam computes (same packed batches, same encode/decode_init program
   prefix);
3. per-slot termination replicates the early-exit predicate exactly —
   done = all-finished-before-step AND all-finished-after (the settling
   step that re-sorts beams), or position exhausted — and
   tests/test_beam_early_exit.py already pins that stopping there equals
   running the full scan.

The arena is paged (decode/paging.py, docs/DECODE_ENGINE.md "Paged KV
arena"): the per-slot self-attention caches live in a FIXED POOL of KV
blocks — ``k_pool``/``v_pool`` (L*P, G, H*d_head), a block a (layer,
pool block) and a row a (beam lane, position), G = beam*block rounded up
to whole sublane tiles, stored in the layout the step computes in —
addressed through a per-slot block table (S, W). The step
program appends each beam's new K/V into ITS lane of the live slot's tail
block and that is the last time those bytes move: what follows the beams
after a selection is the ``ancestry`` table (S, beam, tar_len) — the lane
that holds each position of each beam's history — and the step attends a
slot's beams over all lanes of its blocks, gathered once by block id,
under that table's mask (model.Decoder.decode_step_paged); ``insert`` hands a
fresh slot exactly the blocks its decode bucket's tar budget reserves;
``harvest`` returns a settled slot's blocks to the host free list WHOLE
— freed blocks are unmapped, never zeroed (beam.step_valid_mask already
multiplies unwritten positions by an exact 0.0). Everything stays
static-shape (fixed P, fixed W). The point: slot residency decouples
from sequence length — ``engine_slots`` grows past what whole-sequence
stripes would allow at equal HBM, and longer-tar decode buckets
(``cfg.decode_tar_buckets``) become smaller/larger block RESERVATIONS
against one pool instead of a per-length arena blow-up. The scheduler's
admission becomes reservation-based when the pool is undersized: the
head staged row waits until harvests return enough blocks (head-of-line,
deterministic), and parse-time floors (decode/paging.paging_errors)
guarantee it can always eventually be seated.

Host scheduler (:meth:`SlotEngine.run`): drains the packer stream via the
async feeder; a pass refills every freed slot, dispatches the step, then
prefills ahead while that step is in flight (``cfg.engine_prefill_depth``
chunks, and rows for the slots the last harvest freed; at most the model's
``prefill_budget`` dispatches between two steps where it declares one) —
so the device has the next chunk's prefill queued when the step ends —
and only then harvests settled slots, yielding one :class:`EngineItem`
per sample AS IT SETTLES (out of split order — the ordered streaming
writer, decode/stream.py, restores order on disk). The step writes out
what the harvest reads (done mask, tokens, probs, counters) and its host
copy starts at dispatch: the harvest's ONE transfer is the engine's
designated sync boundary, and the refill decision is host-side by
construction.

The scheduler is exposed as STEPPABLE pieces — ``begin_stream`` /
``wants_input`` / ``admit`` / ``refill`` / ``step_dispatch`` / ``harvest``
— and ``run()`` is just the single-engine loop over them. The replicated
decode fleet (parallel/fleet.py) round-robins the SAME pieces over N
engine instances pulling from one shared admission queue, so the fleet
inherits the single engine's scheduling semantics (and its per-sample
bit-exactness) by construction instead of re-implementing them.
``device``/``tag`` pin a replica to its own chip and suffix its guard
labels (``engine_step[r0]``), keeping the one-compile-per-label contract
honest when N replicas each compile their own program set.

Cross-request reuse (``cfg.prefix_cache``, default off — decode/
prefix_cache.py, docs/DECODE_ENGINE.md "Prefix cache & dedup"): ``admit``
content-addresses each valid row by a keyed blake2b digest of its packed
payload and applies two composable mechanisms before dispatching
prefill. (a) IN-FLIGHT DEDUP: a row byte-identical to one already
admitted on THIS engine coalesces onto the existing seat as a FOLLOWER —
no seat, no blocks, no prefill; ``harvest`` fans the leader's settled
(tokens, probs) out to every follower's own output position (one decode,
N commits). (b) PREFILL-RESULT CACHE: when every remaining row's
artifacts are cached, the staged chunk is assembled host-side from the
cached rows and seated WITHOUT a prefill dispatch (``prefills_saved``);
a chunk that does dispatch fills the cache with host copies of its rows.
Both are host-side lookups — no new program geometry exists, so the
zero-post-warmup-retrace contract holds with the cache armed — and both
are bit-exact: a cache-hit or coalesced response is byte-identical to
its cold run (tests/test_prefix_cache.py).

The paged block allocator is REFCOUNTED (the free list is a deque —
O(1) grants, the old ``list.pop(0)`` walk was O(n) per block): a grant
acquires each block at refcount 1, harvest/retire RELEASE grants (a
block returns to ``_free_blocks`` only at refcount zero) rather than
scribbling the free list wholesale, and double-grant/double-release are
asserted impossible (:meth:`SlotEngine.allocator_invariants`, pinned in
tier-1). Blocks whose seat serves a coalesced fan-out group are the
SHARED blocks of the reuse story — one grant serving N requests — and
their high-water mark is metered (``shared_block_peak``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fira_tpu.analysis.sanitizer import leak_guard, program_label
from fira_tpu.config import FiraConfig
from fira_tpu.decode import paging
from fira_tpu.decode import prefix_cache as prefix_cache_lib
from fira_tpu.decode import quant
from fira_tpu.decode import slot_model
from fira_tpu.decode import spec as spec_lib
from fira_tpu.decode.beam import _init_beam
from fira_tpu.utils import profiling

PREFILL_KIND = "engine_prefill"
STEP_LABEL = "engine_step"
INSERT_LABEL = "engine_insert"


@dataclasses.dataclass
class EngineStats:
    """Dispatch/occupancy accounting for one engine run."""

    slots: int
    prefills: int = 0            # prefill program dispatches (chunks)
    # ... of them, as ``run`` placed them: queued while a step was in
    # flight (they cover the host's harvest), and dispatched after a
    # harvest because the rows staged ahead fell short of the free slots
    # (on the critical path); the first fill of a stream is neither
    prefills_ahead: int = 0
    prefills_topup: int = 0
    refills: int = 0             # insert program dispatches
    slots_refilled: int = 0      # slot fills across all inserts
    steps: int = 0               # beam MICRO-steps run (cadence x dispatches)
    step_dispatches: int = 0     # step program dispatches
    occupied_slot_steps: int = 0  # exact count of (slot, micro-step) pairs
                                  # that did real beam work (device-counted)
    commits: int = 0             # samples harvested
    # paged-KV HBM accounting (decode/paging.py) — stamped by every step
    # dispatch so a stats reset between timed windows (bench.py /
    # tpu_decode_bench.py do exactly that) re-learns them
    pool_blocks: int = 0         # fixed pool size P
    kv_block_size: int = 0       # positions per block
    kv_bytes_per_slot: int = 0   # committed K+V cache HBM per slot
    # ... of them, what the model DECLARES by layer type (slot_model
    # Leaf.kv_kind): prompts kept whole | rings of a window's length; zero
    # for a model whose layers are all of one kind
    kv_bytes_per_slot_full: int = 0
    kv_bytes_per_slot_window: int = 0
    # ... and recurrent state (``"state"``): bytes a slot that do not grow
    # with the prompt
    kv_bytes_per_slot_state: int = 0
    block_steps: int = 0         # blocks in use, summed per step dispatch
    peak_blocks: int = 0         # high-water mark of blocks in use
    # harvest readback accounting: every harvest brings the step's own
    # outputs to the host in one transfer begun at dispatch, and a harvest
    # that settles rows takes ALL of them from it (rows a read =
    # harvest_row_reads / harvest_reads)
    harvest_reads: int = 0       # harvests whose transfer delivered
    #                              settled rows
    harvest_row_reads: int = 0   # settled-slot rows those reads delivered
    harvest_bytes_read: int = 0  # token/prob bytes that crossed D2H (every
    #                              slot's rows, every harvest)
    # cross-request reuse accounting (decode/prefix_cache.py; all zero
    # when cfg.prefix_cache is off — the byte-identical comparator)
    cache_hits: int = 0          # seated rows served from the prefill cache
    cache_misses: int = 0        # seated rows that paid a prefill dispatch
    #                              with the cache armed
    cache_evictions: int = 0     # LRU entries evicted for capacity
    cache_integrity_drops: int = 0  # entries dropped on checksum mismatch
    prefills_saved: int = 0      # admitted chunks that dispatched NO
    #                              prefill (all rows cache-hit or coalesced)
    cache_hbm_bytes_saved: int = 0  # prefill-artifact bytes served from
    #                              cache instead of materialized by dispatch
    dedup_fanout: int = 0        # requests coalesced onto an existing seat
    #                              (delivered by fan-out at harvest)
    shared_block_peak: int = 0   # high-water mark of paged blocks whose
    #                              seat serves a coalesced fan-out group
    # speculative draft-and-verify accounting (decode/spec.py; all zero
    # with cfg.spec_decode off — the byte-identical comparator). ``steps``
    # counts a verify dispatch as ONE step — the forwards-per-token framing
    # of the spec literature — so steps_per_commit falling under spec is
    # exactly "fewer dispatches bought the same commits"; the device-side
    # frames a verify actually ran are metered separately (spec_frames):
    # on CPU each frame costs one plain step's FLOPs, on a parallel-verify
    # backend it does not.
    drafted: int = 0             # draft tokens proposed (k x live slots
    #                              at verify entry)
    accepted: int = 0            # drafted tokens the verify frames matched
    verify_dispatches: int = 0   # draft->verify dispatches (vs plain steps)
    steps_saved: int = 0         # beam frames a verify advanced BEYOND its
    #                              frame-0 obligation — plain step
    #                              dispatches' worth of work avoided
    spec_frames: int = 0         # verify while_loop frames actually run
    # low-precision serving tiers (decode/quant.py; both "f32" on the
    # byte-identical contract path) — stamped by every step dispatch like
    # the pool fields, so stats resets between timed windows re-learn them
    kv_dtype: str = "f32"        # K/V arena storage dtype (f32|bf16)
    serve_precision: str = "f32"  # decode weight tier (f32|bf16|int8w)
    # prompt accounting (host-known at admit; zero for a model whose
    # prefill has one fixed geometry and no notion of a prompt length)
    prompt_tokens: int = 0        # real prompt tokens prefilled
    prompt_tokens_padded: int = 0  # the same, padded to their buckets
    # routed-expert accounting (model/axk1.COUNTERS; accumulated on the
    # device in the arena's ``counters`` leaf, read with the harvest's own
    # reads — zero for a model without an expert layer)
    moe_assignments: int = 0      # (token, expert) choices, all experts
    moe_assignments_held: int = 0  # ... of them, to experts held here
    moe_held_load_max: int = 0    # the busiest held expert's load, summed
    #                               over expert layers and dispatches
    moe_rows_expert_major: int = 0  # held assignments an expert-major pass
    #                               computed (model/axk1.moe_counters:
    #                               decode positions; a prefill's where
    #                               its loads chose expert-major)
    # window-attention accounting (model/afmoe.COUNTERS, the same leaf —
    # zero for a model without window layers)
    attn_keys_read: int = 0       # keys the steps' attention was ASKED to
    #                               cover: occupied slots x layers x the
    #                               keys inside window or context
    attn_keys_context: int = 0    # the same with every layer full
    # recurrent-state accounting (model/jamba.COUNTERS, the same leaf —
    # zero for a model without state-space layers)
    state_rows: int = 0           # slot-beams whose state a position
    #                               updated: occupied slots x beams
    # retention accounting (model/brumby.COUNTERS, the same leaf — zero for
    # a model without retention layers)
    state_reads: int = 0          # slot-layers whose prompt state a
    #                               position read: occupied slots x layers
    own_keys_read: int = 0        # the beams' own generated positions
    #                               attended, over the layers
    # expert reads (model/lfm2.COUNTERS, the same leaf — zero for a model
    # that does not count them)
    moe_experts_read: int = 0     # held experts some row of a decode
    #                               position routed to, over expert layers
    # per span name count/total_s/max_s and the compile counters, over
    # the spans that closed while THIS stats object lived (utils/
    # profiling.Phases) — a stats reset between timed windows resets the
    # phases with it; wall seconds, so honest but schedule-dependent
    phases: profiling.Phases = dataclasses.field(
        default_factory=profiling.collect, repr=False, compare=False)

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of seated rows served from the prefill cache."""
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify frames accepted."""
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def slot_occupancy(self) -> float:
        """Mean fraction of slots doing real beam work per micro-step."""
        total = self.steps * self.slots
        return self.occupied_slot_steps / total if total else 0.0

    @property
    def steps_per_commit(self) -> float:
        return self.steps / self.commits if self.commits else 0.0

    @property
    def pool_utilization(self) -> float:
        """Mean fraction of the KV pool mapped to live slots per step
        dispatch (0.0 before the first one)."""
        if self.pool_blocks and self.step_dispatches:
            return self.block_steps / (self.step_dispatches
                                       * self.pool_blocks)
        return 0.0

    @property
    def dispatches(self) -> int:
        return self.prefills + self.refills + self.step_dispatches

    def summary(self) -> Dict[str, float]:
        return {
            "slots": self.slots,
            "prefills": self.prefills,
            "prefills_ahead": self.prefills_ahead,
            "prefills_topup": self.prefills_topup,
            "refills": self.refills,
            "slots_refilled": self.slots_refilled,
            "steps_run": self.steps,
            "step_dispatches": self.step_dispatches,
            "commits": self.commits,
            "dispatches": self.dispatches,
            "slot_occupancy": round(self.slot_occupancy, 4),
            "steps_per_commit": round(self.steps_per_commit, 3),
            "pool_blocks": self.pool_blocks,
            "kv_block_size": self.kv_block_size,
            "kv_bytes_per_slot": self.kv_bytes_per_slot,
            "kv_bytes_per_slot_full": self.kv_bytes_per_slot_full,
            "kv_bytes_per_slot_window": self.kv_bytes_per_slot_window,
            "kv_bytes_per_slot_state": self.kv_bytes_per_slot_state,
            "peak_blocks": self.peak_blocks,
            "pool_utilization": round(self.pool_utilization, 4),
            "harvest_reads": self.harvest_reads,
            "harvest_row_reads": self.harvest_row_reads,
            "harvest_bytes_read": self.harvest_bytes_read,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_hit_rate": round(self.cache_hit_rate, 4),
            "cache_evictions": self.cache_evictions,
            "cache_integrity_drops": self.cache_integrity_drops,
            "prefills_saved": self.prefills_saved,
            "cache_hbm_bytes_saved": self.cache_hbm_bytes_saved,
            "dedup_fanout": self.dedup_fanout,
            "shared_block_peak": self.shared_block_peak,
            "drafted": self.drafted,
            "accepted": self.accepted,
            "acceptance_rate": round(self.acceptance_rate, 4),
            "verify_dispatches": self.verify_dispatches,
            "steps_saved": self.steps_saved,
            "spec_frames": self.spec_frames,
            "kv_dtype": self.kv_dtype,
            "serve_precision": self.serve_precision,
            "prompt_tokens": self.prompt_tokens,
            "prompt_tokens_padded": self.prompt_tokens_padded,
            "moe_assignments": self.moe_assignments,
            "moe_assignments_held": self.moe_assignments_held,
            "moe_held_load_max": self.moe_held_load_max,
            "moe_rows_expert_major": self.moe_rows_expert_major,
            "attn_keys_read": self.attn_keys_read,
            "attn_keys_context": self.attn_keys_context,
            "state_rows": self.state_rows,
            "state_reads": self.state_reads,
            "own_keys_read": self.own_keys_read,
            "moe_experts_read": self.moe_experts_read,
            "phases": self.phases.summary(),
        }


@dataclasses.dataclass
class EngineItem:
    """One settled sample: the per-sample view of the batched beam's
    output — ``tokens[argmax(probs)]`` is the prediction, copy ids already
    resolved at extension time (identical contract to decode/beam.py)."""

    position: int        # split-local sample position (output order key)
    host: Dict           # the host batch this sample rode in on
    row: int             # its row within that batch (indexes host fields)
    tokens: np.ndarray   # (beam, tar_len) int32
    probs: np.ndarray    # (beam,) float32


@dataclasses.dataclass
class _Staged:
    """A prefilled chunk whose rows are not all inserted yet."""

    chunk: Dict                  # device pytree from the prefill program
    host: Dict                   # host batch (text-cooking fields + meta)
    rows: "collections.deque[Tuple[int, int]]"  # (row, split position)
    limit: int                   # per-slot tar budget for this chunk's rows
                                 # (the bucket's tar under decode_tar_buckets,
                                 # else cfg.tar_len) — sets the paged block
                                 # reservation AND the generation cap
    row_limits: Optional[np.ndarray] = None  # per-row budgets where the
                                 # host batch carries ``_limits`` (each
                                 # request its own max_new_tokens + 1);
                                 # they take ``limit``'s place row by row
    fresh: bool = True           # no row of the chunk inserted yet

    def limit_of(self, row: int) -> int:
        return (self.limit if self.row_limits is None
                else int(self.row_limits[row]))


class SlotEngine:
    """S-slot continuous-batching beam decoder over one model/params.

    ``slots``: arena size (default ``cfg.engine_slots`` or, when that is 0,
    ``cfg.test_batch_size`` — equal geometry with the batched beam, which
    is also what the bit-exactness golden tests pin). ``guard``: an armed
    analysis.sanitizer.CompileGuard; every dispatch is labelled, so the
    one-compile-per-label contract covers the whole engine family.
    ``device``: pin the arena, params inputs, and every admitted chunk to
    ONE device (a fleet replica's chip); None keeps the default placement.
    ``tag``: label suffix (the fleet's ``r<i>``) so each replica's own
    compiles stay one-per-label under the guard.
    """

    # read by tests/benchmark/test_benchmark_spans.py (a yardstick file no
    # PR but a `benchmark` one may edit) and by nothing in fira_tpu/: the
    # arena IS paged. Goes with that reader (ROADMAP D14).
    _paged = True

    @profiling.span("engine.init")
    def __init__(self, model, params, cfg: FiraConfig, *,
                 slots: Optional[int] = None, guard=None,
                 device=None, tag: Optional[str] = None,
                 pool_blocks: Optional[int] = None, faults=None):
        self.model = model
        self.params = params
        self.cfg = cfg
        # robust.faults.FaultInjector (or None — the zero-overhead
        # default): checks the engine.{prefill,step,harvest} sites at
        # each dispatch. ``retired`` is set by retire(): a replica whose
        # dispatch raised or blew the watchdog is dead — every steppable
        # piece bails early on it, including an abandoned watchdog thread
        # that wakes up after the retirement (docs/FAULTS.md).
        self._faults = faults
        # resource-lifecycle sanitizer (--sanitize / chaos harness):
        # armed, every paged-block grant is ledgered with its acquire
        # site and assert_clean() at teardown names what leaked; unarmed
        # (None — the default) each allocator path pays one is-None
        # branch and records nothing (analysis.sanitizer.LeakGuard)
        self._leaks = leak_guard()
        self.retired = False
        self.slots = int(slots or cfg.engine_slots or cfg.test_batch_size)
        if self.slots < 1:
            raise ValueError(f"engine needs >= 1 slot, got {self.slots}")
        self.guard = guard
        self.device = device
        self.tag = tag
        from fira_tpu.config import arch_errors

        aerrs = arch_errors(cfg)
        if aerrs:
            raise ValueError("; ".join(aerrs))
        # low-precision serving tiers (decode/quant.py). The tier tag
        # suffixes EVERY program label of this engine ("" on the f32/f32
        # contract path — the default label set is unchanged), and the
        # weight tier builds a quantized copy of the decode-side params
        # ONCE, here: a fleet respawn or spare prewarm constructs a fresh
        # SlotEngine from the original f32 params, so re-quantization is
        # automatic by construction.
        qerrs = quant.quant_errors(cfg)
        if qerrs:
            raise ValueError("; ".join(qerrs))
        self._tier_tag = quant.tier_tag(cfg)
        self._tier_ns = quant.tier_namespace(cfg)
        self._decode_params, self._wq_scales = quant.quantize_decode_params(
            params, cfg)
        if self._decode_params is not params:
            self._decode_params = jax.device_put(self._decode_params, device)
        # paged KV arena geometry (decode/paging.py). ``pool_blocks`` is
        # THIS engine's pool (a fleet replica's per-chip share); None
        # falls back to cfg.kv_pool_blocks, 0 to the full-residency auto
        # size (slots x table width — admission never waits for blocks).
        self._kv_bytes_by_kind: Dict[str, int] = {}
        self._block_size = paging.resolve_block_size(cfg)
        if cfg.tar_len % self._block_size:
            raise ValueError(
                f"kv_block_size {self._block_size} does not divide "
                f"tar_len {cfg.tar_len}; the block table must tile "
                f"the arena budget exactly (decode/paging.py)")
        self._table_width = cfg.tar_len // self._block_size
        self._pool_blocks = int(
            pool_blocks if pool_blocks is not None
            else cfg.kv_pool_blocks) or self.slots * self._table_width
        if self._pool_blocks < self._table_width:
            raise ValueError(
                f"kv_pool_blocks {self._pool_blocks} < table width "
                f"{self._table_width}: one full-tar sample must fit "
                f"an empty pool or admission livelocks")
        # cross-request prefill cache (decode/prefix_cache.py): one LRU
        # PER ENGINE — a fleet replica's cache is per-chip like its KV
        # arena (cached artifacts re-enter via device_put onto this
        # engine's own device). None = off, zero hot-path overhead.
        self._cache = None
        if cfg.prefix_cache:
            self._cache = prefix_cache_lib.PrefixCache(
                cfg.prefix_cache_entries,
                max_bytes=cfg.prefix_cache_bytes, faults=faults)
        # the model behind the seam (decode/slot_model.py): what prefill
        # leaves, what a step reads and writes, which arena leaves follow
        # the beams — everything below is the model's, nothing named here
        self.smodel = slot_model.for_config(
            model, cfg, self.slots, self._block_size, self._pool_blocks)
        self._leaves: Dict[str, slot_model.Leaf] = {}
        self._counters_seen = None
        self.stats = EngineStats(slots=self.slots)
        self._state = None
        self._prefill = jax.jit(self._prefill_fn)
        # the big slot arena is donated through step/insert: the engine
        # holds exactly one live state, rebound on every dispatch
        self._step = jax.jit(self._step_fn, donate_argnums=(1,))
        self._insert = jax.jit(self._insert_fn, donate_argnums=(0,))
        # what the last step dispatch wrote out for the harvest (_outputs:
        # buffers of their own, not views of the arena the next dispatch
        # donates), their host copy already started; None once harvested
        self._pending_out = None
        # speculative draft-and-verify (decode/spec.py; cfg.spec_decode):
        # the drafter reads the arena (never donated — the verify right
        # behind it consumes the same state), the verify donates it like
        # the plain step and writes its device-side [tested, matched,
        # iters] counters out beside the step's (no new host syncs).
        # _spec_cd is the stall cooldown — plain dispatches to run before
        # re-arming after a verify whose drafts all missed (scheduling
        # only; output bytes are invariant by the spec.py exactness
        # argument).
        self._spec_tier = (cfg.spec_decode
                           if cfg.spec_decode not in (None, "off") else None)
        self._spec_k = int(cfg.engine_spec_k)
        self._spec_cd = 0
        if self._spec_tier is not None:
            errs = spec_lib.spec_errors(cfg)
            if errs:
                raise ValueError("; ".join(errs))
            # the drafter runs on the same decode-side weight tier as the
            # step it feeds: int8w leaves dequant at the trace top (a
            # no-op identity for f32/bf16 — scales is None)
            base_draft = spec_lib.make_drafter(model, cfg, self.slots)
            self._draft = jax.jit(lambda p, st: base_draft(
                quant.dequant_tree(p, self._wq_scales), st))
            self._verify = jax.jit(self._verify_fn, donate_argnums=(1,))
        self.begin_stream()

    def label(self, kind: str, geom_tag: Optional[str] = None) -> str:
        """Guard label for one of THIS engine's programs: the geometry tag
        (prefill family), the low-precision tier tag (decode/quant.py —
        empty on the f32/f32 contract path) and the replica tag compose
        into the standard ``program_label`` format —
        ``engine_prefill[a16.e256.t12.r1]``, ``engine_step[bf16kv.int8w.r1]``;
        with no tags the single-engine labels are unchanged."""
        mods = ".".join(t for t in (geom_tag, self._tier_tag, self.tag) if t)
        return program_label(kind, mods or None)

    def labels(self, table=None) -> List[str]:
        """This engine's full declared program family: one prefill label
        per decode bucket geometry (or the untagged prefill when no table)
        plus step + insert."""
        from fira_tpu.data.buckets import geom_tag

        prefills = ([self.label(PREFILL_KIND, geom_tag(g)) for g in table]
                    if table is not None else [self.label(PREFILL_KIND)])
        return prefills + [self.label(STEP_LABEL),
                           self.label(INSERT_LABEL)] + self._spec_labels()

    def labels_for_tags(self, geom_tags) -> List[str]:
        """The declared family from already-computed geometry tags (the
        respawn path holds the stored warm-batch tags, not the bucket
        table — parallel/fleet.py replace_slot): one prefill label per
        tag (None = the untagged single-geometry prefill) plus the step
        and the insert(s)."""
        prefills = [self.label(PREFILL_KIND, t) for t in geom_tags] \
            or [self.label(PREFILL_KIND)]
        inserts = ([self.label(INSERT_LABEL, t) for t in geom_tags]
                   if self.smodel.insert_by_geometry
                   else [self.label(INSERT_LABEL)])
        return prefills + [self.label(STEP_LABEL)] + inserts \
            + self._spec_labels()

    def _spec_labels(self) -> List[str]:
        """The (S, k) draft/verify pair when spec is armed (the ``k<k>``
        geometry mod composes with the replica tag —
        ``engine_verify[k4.r1]``); empty with cfg.spec_decode off, so the
        non-spec declared family is byte-for-byte unchanged."""
        if self._spec_tier is None:
            return []
        km = f"k{self._spec_k}"
        return [self.label(spec_lib.DRAFT_LABEL, km),
                self.label(spec_lib.VERIFY_LABEL, km)]

    # --- jitted programs -------------------------------------------------

    def _prefill_fn(self, params, batch):
        """What one packed batch of new requests leaves for its slots: the
        model's own prefill (slot_model: FIRA's encoder preamble, an LM's
        prompt latents), traced under this name — the benchmark's readers
        find the program by it."""
        return self.smodel.prefill(params, batch)

    def _step_fn(self, params, state):
        """Advance every live, not-yet-done slot ``cfg.engine_harvest_every``
        beam positions at its own depth (a lax.scan of identical one-step
        bodies — slots that settle mid-scan self-mask out, so the cadence
        changes WHICH dispatch a harvest lands in, never the math);
        everything else passes through unchanged. Returns (state, what
        the harvest reads — :meth:`_outputs`, with the occupied-slot-step
        count: the occupancy numerator, counted exactly, micro-step by
        micro-step).

        ``params`` is the engine's DECODE-SIDE tree (self._decode_params):
        under serve_precision="int8w" the quantized leaves dequant ONCE
        here, at the trace top (per-channel scales embed as trace-time
        constants), so the scan body below reuses one reconstructed tree
        instead of dequantizing per micro-step; f32/bf16 pass through
        untouched (scales is None)."""
        params = quant.dequant_tree(params, self._wq_scales)
        R = max(1, int(self.cfg.engine_harvest_every))
        if R == 1:
            state, occ = self._one_step(params, state)
            return state, self._outputs(state, occ)

        def body(carry, _):
            st, acc = carry
            st, occ = self._one_step(params, st)
            return (st, acc + occ), None

        (state, occ), _ = jax.lax.scan(
            body, (state, jnp.int32(0)), None, length=R)
        return state, self._outputs(state, occ)

    def _verify_fn(self, params, state, drafts):
        """The speculative verify program: up to ``engine_spec_k`` gated
        EXACT step frames in one dispatch (decode/spec.run_verify over
        this engine's own :meth:`_one_step` — the identical per-position
        HLO the plain step runs, which is the whole exactness argument).
        Returns (state', :meth:`_outputs` with occ_entry as the occupancy
        and the [tested, matched, iters] counter vector as ``spec``)."""
        # same trace-top dequant as _step_fn: the while_loop frames reuse
        # one reconstructed tree (identity for f32/bf16 weight tiers)
        params = quant.dequant_tree(params, self._wq_scales)
        step = functools.partial(self._one_step, params)
        state, occ, spec = spec_lib.run_verify(
            step, state, drafts, self._spec_k, self.cfg.tar_len)
        return state, self._outputs(state, occ, spec)

    def _outputs(self, state, occ, spec=None) -> Dict:
        """What a harvest reads of a step, as outputs of the program's own
        (not the arena's leaves, which the next dispatch donates): the
        occupancy count, the done mask, every slot's tokens and probs, the
        model's device counters where it declares them and the verify's
        where it ran. Copying all S rows costs less than the round trip a
        gather of the settled ones would add after the step."""
        out = {"occ": occ, "done": state["done"], "tokens": state["tokens"],
               "probs": state["probs"]}
        if self.smodel.arena_counters:
            out["counters"] = state["counters"]
        if spec is not None:
            out["spec"] = spec
        return out

    def _one_step(self, params, state, gate=None):
        """One beam position for every live, not-yet-done slot.

        ``gate`` (None on every plain path — the trace is unchanged): a
        (S,) bool the spec verify program (decode/spec.py) ANDs into the
        active mask, freezing rows whose drafts already diverged. A frozen
        row is handled by the inactive-row discipline that already exists
        for idle/done slots — blended state (its ancestry rows among it),
        sentinel-masked block table — so it RESUMES with its history
        intact."""
        cfg = self.cfg
        S, K, T = self.slots, cfg.beam_size, cfg.tar_len
        neg = (jnp.float32(-1.0) if cfg.beam_compat_prob_space
               else jnp.float32(-np.inf))

        tokens, probs, finished = (state["tokens"], state["probs"],
                                   state["finished"])
        pos = state["pos"]
        active = state["live"] & ~state["done"]
        if gate is not None:
            active = active & gate
        # idle/done rows clamp to a legal position; their computation is
        # garbage by construction and blended away below
        pos_c = jnp.minimum(pos, T - 2)
        all_fin_before = jnp.all(finished, axis=1)   # (S,)
        # idle and done slots must neither write nor permute the pool:
        # their table rows may still name blocks harvest already returned
        # to the free list and insert re-granted to ANOTHER slot. Masking
        # their rows to the sentinel P turns every such gather into
        # clamped (blended-away) garbage and every such scatter into a
        # drop.
        tab_step = jnp.where(active[:, None], state["block_tab"],
                             jnp.int32(self._pool_blocks))
        # beam ancestry (a model that declares it): this position goes
        # into each beam's OWN lane of the slot's blocks, so the table the
        # step reads through names lane k at ``pos`` for beam k
        ancestry = None
        if self.smodel.beam_ancestry:
            ancestry = jnp.where(
                jnp.arange(T)[None, None, :] == pos_c[:, None, None],
                jnp.arange(K, dtype=jnp.int32)[None, :, None],
                state["ancestry"])
        # beam parents (a model that declares recurrent state): the lane
        # whose state each beam continues from — the last selection's
        # source beam; the step reads through it as it writes
        parent = state["parent"] if self.smodel.beam_parent else None
        view = slot_model.StepView(
            flat=tokens.reshape(S * K, T), pos_c=pos_c,
            pos_bk=jnp.repeat(pos_c, K), active=active, tab_step=tab_step,
            ancestry=ancestry, parent=parent)
        parts, out_caches = self.smodel.step(params, state, view)
        with jax.named_scope("topk"):
            new_tokens, new_probs, new_finished, src_beam = \
                self.smodel.select(parts, tokens, probs, finished, pos_c,
                                   state, neg)
        # cached histories follow their beams as the model DECLARED: by
        # the ancestry table (slot_model ``beam_ancestry``: FIRA's pools
        # are never moved — further down), or leaf by leaf
        # (slot_model.Leaf.reorder): a pool leaf moves block contents
        # inside each active slot's own grant (A.X-K1's ``lat_pool``; the
        # sentinel table rows of inactive slots drop).
        # tokens/probs/finished/pos blend below: they must survive until
        # harvest.
        reordered = [n for n, leaf in self._leaves.items()
                     if leaf.reorder and n in out_caches]
        if reordered:
            with jax.named_scope("kv_reorder"):
                for name in reordered:
                    out_caches[name] = slot_model.permute_pool(
                        out_caches[name], tab_step, src_beam)

        if ancestry is not None:
            # the pools stay where they were written: what follows the
            # beams is which lane holds each position of their histories,
            # S x K x T small ints. Inactive rows keep their table as
            # tokens/probs keep theirs below — a verify-frozen row RESUMES
            # with its history intact.
            with jax.named_scope("kv_reorder"):
                followed = jnp.take_along_axis(
                    ancestry, src_beam[:, :, None], axis=1)
            out_caches["ancestry"] = jnp.where(
                active[:, None, None], followed, state["ancestry"])

        if parent is not None:
            # recurrent state is not moved either: the NEXT position reads
            # each beam's state from the lane of the beam it came from.
            # Inactive rows keep their parents with their state.
            out_caches["parent"] = jnp.where(active[:, None], src_beam,
                                             parent)

        tokens = jnp.where(active[:, None, None], new_tokens, tokens)
        probs = jnp.where(active[:, None], new_probs, probs)
        finished = jnp.where(active[:, None], new_finished, finished)
        new_pos = jnp.where(active, pos + 1, pos)
        all_fin_after = jnp.all(finished, axis=1)
        # the early-exit predicate, per slot: stopping is exact once the
        # settling step has re-sorted an all-finished beam set
        # (decode/beam._run_steps; tests/test_beam_early_exit.py), or when
        # the position budget is exhausted — the SLOT's own budget: its
        # decode bucket's tar under cfg.decode_tar_buckets (the paged
        # block reservation it was seated with), cfg.tar_len otherwise
        done = state["done"] | (active & ((new_pos >= state["limit"] - 1)
                                          | (all_fin_before & all_fin_after)))
        return (dict(state, tokens=tokens, probs=probs, finished=finished,
                     pos=new_pos, done=done, **out_caches),
                jnp.sum(active.astype(jnp.int32)))

    def _insert_fn(self, state, chunk, slot_ids, limits, block_rows,
                   fresh=None):
        """Scatter chunk rows into slots. ``slot_ids``: (C,) int32, row j
        goes to slot ``slot_ids[j]``; the out-of-range sentinel S marks
        rows NOT consumed by this call (their scatter drops). ``limits``:
        (C,) int32 per-row tar budget. ``block_rows``: (C, W) int32 block
        grants, sentinel-P-padded past the row's reservation. ``fresh``
        (None, or an int32 0/1 for a model that counts on the device): 1
        on a chunk's first insert.

        INVARIANT — no cache zeroing. A fresh slot's unwritten cache
        positions are exactly -1e9-masked by the step's validity rule
        (beam.step_valid_mask) and exp(-1e9 - m) underflows to 0.0 in the
        stable softmax dtype, so stale values multiply a hard zero: the
        arena has nothing to zero — freed blocks are simply UNMAPPED.
        tests/test_paged_kv.py pins this by object identity on the
        k/v buffers through an eager insert AND by bit-exact reuse of a
        dirty arena, so a zeroing cannot silently appear."""
        cfg = self.cfg
        K = cfg.beam_size
        C = slot_ids.shape[0]
        tokens0, probs0, finished0, _neg = _init_beam(C, cfg)
        sid = slot_ids.astype(jnp.int32)

        new = dict(state)

        def put(field, value):
            new[field] = state[field].at[sid].set(value, mode="drop")

        put("tokens", tokens0)
        put("probs", probs0)
        put("finished", finished0)
        new["pos"] = state["pos"].at[sid].set(0, mode="drop")
        new["live"] = state["live"].at[sid].set(True, mode="drop")
        new["done"] = state["done"].at[sid].set(False, mode="drop")
        new["limit"] = state["limit"].at[sid].set(
            limits.astype(jnp.int32), mode="drop")
        # the model's own leaves (slot_model): what prefill left for each
        # seated row; the pools are untouched (INVARIANT above)
        new.update(self.smodel.insert(state, chunk, sid, fresh))
        # hand the seated rows their block grants
        new["block_tab"] = state["block_tab"].at[sid].set(
            block_rows.astype(jnp.int32), mode="drop")
        if self.smodel.beam_ancestry:
            # a fresh slot's beams each start in their own lane
            new["ancestry"] = state["ancestry"].at[sid].set(
                jnp.arange(K, dtype=jnp.int32)[None, :, None], mode="drop")
        if self.smodel.beam_parent:
            # a fresh slot's beams share one history: all continue from
            # the state the model's insert put in lane 0
            new["parent"] = state["parent"].at[sid].set(0, mode="drop")
        return new

    # --- state ----------------------------------------------------------

    def arena_shapes(self, chunk) -> Dict[str, jax.ShapeDtypeStruct]:
        """The slot arena's leaves, shape and dtype each, from the first
        chunk's (arrays or ``jax.ShapeDtypeStruct``s): what
        :meth:`_ensure_state` allocates, and what a program over the arena
        can be lowered from without allocating it."""
        cfg = self.cfg
        S, K, T = self.slots, cfg.beam_size, cfg.tar_len
        spec = {
            "tokens": ((S, K, T), np.int32),
            "probs": ((S, K), np.float32),
            "finished": ((S, K), bool),
            "pos": ((S,), np.int32),
            "live": ((S,), bool),
            "done": ((S,), bool),
            "limit": ((S,), np.int32),
        }
        # the model's leaves, as it declares them (slot_model.Leaf)
        self._leaves = self.smodel.leaves(chunk)
        for name, leaf in self._leaves.items():
            spec[name] = (leaf.shape, leaf.dtype)
        spec["block_tab"] = ((S, self._table_width), np.int32)
        if self.smodel.beam_ancestry:
            spec["ancestry"] = ((S, K, T), np.int32)
        if self.smodel.beam_parent:
            spec["parent"] = ((S, K), np.int32)
        return {name: jax.ShapeDtypeStruct(shape, np.dtype(dtype))
                for name, (shape, dtype) in spec.items()}

    def _ensure_state(self, chunk) -> None:
        """Allocate the slot arena (all slots dead) from the first chunk's
        shapes/dtypes. Plain host zeros + one device_put: no compiled
        program, so nothing for the compile guard to mis-attribute."""
        if self._state is not None:
            return
        K, T = self.cfg.beam_size, self.cfg.tar_len
        z = {name: np.zeros(a.shape, a.dtype)
             for name, a in self.arena_shapes(chunk).items()}
        # per-slot tar budget: full until an insert seats a shorter-budget
        # sample (cfg.decode_tar_buckets, or a request that carries its
        # own limit)
        z["limit"][:] = T
        z["block_tab"][:] = self._pool_blocks                  # unmapped
        if "ancestry" in z:
            z["ancestry"][:] = np.arange(K, dtype=np.int32)[None, :, None]
        self._kv_bytes_by_kind = paging.leaves_kv_bytes_by_kind(
            self._leaves, self.slots)
        # firacheck: allow[RETIRED-RECHECK] arena-state write: retire() deliberately leaves the arena in place ("the arena and stats stay") and a dead engine's _state is never read again — only scheduling/guard state needs the post-dispatch re-check
        self._state = jax.device_put(z, self.device)

    # --- host scheduler --------------------------------------------------

    def _guard_step(self, label: str) -> None:
        if self.guard is not None:
            self.guard.step(label)

    @profiling.span("engine.prewarm")
    def prewarm(self, warm_batches: Iterable[Tuple[Dict, Optional[str]]]
                ) -> None:
        """Compile the WHOLE program family up front: one all-pad batch
        per decode bucket geometry (the prefill compile keys), then one
        no-op insert (every slot id the drop sentinel) and one step over
        the all-dead arena (no slot active — the state is untouched; its
        outputs are never harvested). Outputs are unchanged
        by construction (pinned by the byte-equality tests); the point is
        that NO dispatch after prewarm pays a compile — which the
        per-dispatch wall-clock watchdog (docs/FAULTS.md) depends on: a
        first-use XLA compile inside a watchdogged dispatch would read as
        a hung replica."""
        # one span a program: what a span holds is that program's trace,
        # lowering, compile (or cache load) and dispatch — the host's
        # share — and the build listener's jax.trace / jax.lower /
        # xla.compile events land under the program that paid them
        # (utils/profiling.py). The spans do NOT wait for
        # the device: closed on block_until_ready they read the arena's
        # upload (4-5 s at benchmark size, under `insert`) and each first
        # run, but the waiting cost every process 2-5 s of set-up that
        # otherwise overlaps the host work after prewarm (PERF.md, PR 26)
        chunk = None
        for host, tag in warm_batches:
            with profiling.span("engine.prewarm.prefill"):
                wire = {k: v for k, v in host.items()
                        if not k.startswith("_")}
                chunk = self._prefill(self.params,
                                      jax.device_put(wire, self.device))
            self._guard_step(self.label(PREFILL_KIND, tag))
            self._ensure_state(chunk)
            if self.smodel.insert_by_geometry:
                # a chunk is as long as its bucket: one insert program a
                # geometry, compiled here beside its prefill
                self._prewarm_insert(chunk, tag)
        if chunk is None:
            return
        if not self.smodel.insert_by_geometry:
            self._prewarm_insert(chunk, None)
        with profiling.span("engine.prewarm.step"):
            self._state, _out = self._step(self._decode_params, self._state)
        self._guard_step(self.label(STEP_LABEL))
        if self._spec_tier is not None:
            # compile the (S, k) draft/verify pair over the all-dead arena:
            # the verify's while_loop condition is false at frame 0 (no
            # live row), so the state passes through unchanged — but both
            # programs compile here, not inside a watchdogged dispatch
            km = f"k{self._spec_k}"
            with profiling.span("engine.prewarm.spec"):
                drafts = self._draft(self._decode_params, self._state)
                self._guard_step(self.label(spec_lib.DRAFT_LABEL, km))
                self._state, _out = self._verify(
                    self._decode_params, self._state, drafts)
                self._guard_step(self.label(spec_lib.VERIFY_LABEL, km))

    def _prewarm_insert(self, chunk, tag: Optional[str]) -> None:
        """One no-op insert of ``chunk``'s geometry: every slot id the
        drop sentinel, so the arena passes through unchanged."""
        C = self.smodel.chunk_rows(chunk)
        sentinel_ids = np.full((C,), self.slots, dtype=np.int32)  # all drop
        limits = np.full((C,), self.cfg.tar_len, dtype=np.int32)
        block_rows = np.full((C, self._table_width), self._pool_blocks,
                             dtype=np.int32)
        with profiling.span("engine.prewarm.insert"):
            new_state = self._insert(self._state, chunk, sentinel_ids,
                                     limits, block_rows,
                                     self._fresh_arg(False))
        if self.retired:
            return
        self._state = new_state
        self._guard_step(self.label(INSERT_LABEL, tag))

    def _fresh_arg(self, fresh: bool):
        """The insert program's ``fresh`` argument: None for a model that
        counts nothing on the device (its program takes no such input)."""
        return np.int32(fresh) if self.smodel.arena_counters else None

    # --- steppable scheduler pieces (the fleet round-robins these) -------

    def begin_stream(self) -> None:
        """Reset the host-side scheduling state for a fresh input stream
        (the slot arena and stats persist — stats accumulate across runs,
        exactly as before the scheduler was made steppable)."""
        self._staged: "collections.deque[_Staged]" = collections.deque()
        self._staged_rows = 0
        self._free: "collections.deque[int]" = collections.deque(
            range(self.slots))
        self._busy: Dict[int, Tuple[int, Dict, int]] = {}
        # the previous stream's step outputs name its seats, not this one's
        self._pending_out = None
        # slots the last harvest freed: what the step in flight is expected
        # to free, which ``run`` stages rows for ahead of the harvest
        self._settled_last = 0
        # paged-KV block allocator: the free list (a deque — O(1) grants)
        # and the per-slot grant map reset with the scheduler; the POOL
        # CONTENTS do not — stale block values are exactly masked, never
        # read (beam.step_valid_mask). Grants are refcounted: a block
        # returns to _free_blocks only at refcount zero (_release_blocks),
        # and double-grant/double-release assert (allocator_invariants).
        self._free_blocks: "collections.deque[int]" = collections.deque(
            range(self._pool_blocks))
        self._block_refs: Dict[int, int] = {}
        self._slot_blocks: Dict[int, List[int]] = {}
        # in-flight dedup maps (cfg.prefix_cache): digest -> leader
        # position for every admitted-but-unharvested row, and leader
        # position -> coalesced followers awaiting fan-out delivery
        self._inflight: Dict[str, int] = {}
        self._row_digest: Dict[int, str] = {}
        self._followers: Dict[int, List[Tuple[int, Dict, int]]] = {}
        # positions whose seat serves a fan-out group COALESCED ABOVE the
        # engine (the serve loop's fleet-global dedup keeps its followers
        # in the loop, not here) — stamped by the loop each round purely
        # so shared_block_peak meters those seats' grants too
        self.shared_positions: set = set()
        # cache miss-fills DEFERRED to the harvest boundary: admit only
        # schedules the D2H (copy_to_host_async) and parks the chunk
        # here; harvest — the engine's designated sync point — drains it.
        # Admission therefore never blocks on a prefill readback, and the
        # store-later window is covered by dedup (the rows' digests sit
        # in _inflight until the same harvest that drains their fill).
        # A harvest drains only the fills admitted before the step it
        # reads (``_fills_due``, counted at step_dispatch): a chunk
        # prefilled behind that step is read at the next harvest, so the
        # harvest never waits on a prefill queued after the step.
        self._pending_fills: List[Tuple[List[Tuple[int, str]], Dict]] = []
        self._fills_due = 0

    # --- refcounted paged-block allocator -------------------------------

    def _acquire_blocks(self, need: int) -> List[int]:
        """Grant ``need`` blocks off the free deque at refcount 1. The
        caller checked availability (head-of-line admission); a granted
        block being granted again is an allocator bug, asserted here."""
        grant: List[int] = []
        for _ in range(need):
            b = self._free_blocks.popleft()
            assert self._block_refs.get(b, 0) == 0, \
                f"block {b} granted while already held (double grant)"
            self._block_refs[b] = 1
            grant.append(b)
        if self._leaks is not None:
            for b in grant:
                self._leaks.note_acquire(
                    "block", f"{self.tag or 'engine'}@{id(self):x}:{b}",
                    what=f"paged block {b}")
        return grant

    def _release_blocks(self, blocks) -> None:
        """Decrement each block's refcount; a block returns to the free
        deque only at refcount ZERO. Today every grant is exclusive
        (refcount 1 — fan-out sharing is SEAT-level: one grant serves
        the whole coalesced group, so no second holder exists), so the
        refcounts are the double-grant/double-release guard and the
        forward surface for true multi-holder mappings. Release paths:
        harvest (seat settled), retire (engine dead); a shed follower
        detaches without holding blocks at all."""
        for b in blocks:
            n = self._block_refs.get(b, 0)
            assert n > 0, f"block {b} released while not granted"
            if n == 1:
                del self._block_refs[b]
                self._free_blocks.append(b)
                if self._leaks is not None:
                    self._leaks.note_release(
                        "block", f"{self.tag or 'engine'}@{id(self):x}:{b}")
            else:
                self._block_refs[b] = n - 1

    def allocator_invariants(self) -> List[str]:
        """Machine-checkable allocator health (tier-1-pinned): every pool
        block is exactly free or granted, no block is granted twice, and
        refcounts agree with the grant map. Empty list = healthy."""
        errs: List[str] = []
        free = list(self._free_blocks)
        if len(set(free)) != len(free):
            errs.append("duplicate blocks on the free list")
        granted: Dict[int, int] = {}
        for slot, blocks in self._slot_blocks.items():
            for b in blocks:
                granted[b] = granted.get(b, 0) + 1
        for b, holders in granted.items():
            refs = self._block_refs.get(b, 0)
            if refs < holders:
                errs.append(f"block {b} held by {holders} grant(s) but "
                            f"refcount {refs}")
        for b, refs in self._block_refs.items():
            if refs < 1:
                errs.append(f"block {b} carries refcount {refs} <= 0")
        overlap = set(granted) & set(free)
        if overlap:
            errs.append(f"blocks {sorted(overlap)[:4]} both free and granted")
        if len(free) + len(self._block_refs) != self._pool_blocks:
            errs.append(
                f"free ({len(free)}) + granted ({len(self._block_refs)}) "
                f"!= pool ({self._pool_blocks})")
        return errs

    # --- prefix-cache surface -------------------------------------------

    def _drain_pending_fills(self, n: int) -> None:
        """Materialize the first ``n`` deferred miss-fills (the D2H was
        scheduled async at admit) and store each row by its content
        digest. Runs at the harvest sync boundary only."""
        for _ in range(min(n, len(self._pending_fills))):
            fills, chunk = self._pending_fills.pop(0)
            chunk_host = {}
            for f in prefix_cache_lib.ARTIFACT_FIELDS:
                chunk_host[f] = np.asarray(jax.device_get(chunk[f]))  # firacheck: allow[HOST-SYNC] deferred prefill-cache miss-fill draining at the harvest sync boundary; the D2H itself was scheduled async at admit (copy_to_host_async), so this materialization is the designated host copy, not a mid-admission stall
            entries = prefix_cache_lib.extract_payloads(
                chunk_host, [r for r, _d in fills])
            for r, d in fills:
                self.stats.cache_evictions += self._cache.put(d, entries[r])

    def cache_contains(self, digest) -> bool:
        """Non-mutating cache probe (the serve loop partitions admission
        batches into hit/miss chunks with this — serve/server.py)."""
        return self._cache is not None and self._cache.contains(digest)

    def cache_put(self, digest, payload) -> None:
        """Seed one externally-prefilled artifact payload (the
        disaggregated prefill tier's delivery seam — serve/disagg.py):
        the next admission of this digest takes the all-hit cache path —
        host assemble + one device_put, ZERO prefill dispatches on this
        replica. Same eviction meter as a miss-fill; a no-op without a
        cache (cfg.prefix_cache off) or for a pad digest."""
        if self._cache is not None and digest is not None:
            self.stats.cache_evictions += self._cache.put(digest, payload)

    def cache_clear(self) -> None:
        """Drop every cached prefill entry (bench hygiene: a warm pass
        must not hand the timed window its hits)."""
        if self._cache is not None:
            self._cache.clear()

    def cache_len(self) -> int:
        return len(self._cache) if self._cache is not None else 0

    def wants_input(self, ahead: int = 0) -> bool:
        """Prefill-ahead policy: keep ``engine_prefill_depth`` chunks
        staged, and at least enough rows to refill every free slot plus
        ``ahead`` more — the slots a step still in flight is expected to
        free (``run`` passes what the last harvest freed)."""
        depth = max(1, int(self.cfg.engine_prefill_depth))
        return (len(self._staged) < depth
                or self._staged_rows < len(self._free) + ahead)

    def in_flight(self) -> int:
        return len(self._busy)

    def in_flight_positions(self) -> List[int]:
        """Split positions currently seated in slots (the serving loop
        stamps seat/first-step latencies off this — serve/server.py)."""
        return [pid for (pid, _host, _row) in self._busy.values()]

    @property
    def staged_rows(self) -> int:
        """Admitted (prefilled) rows not yet seated in a slot."""
        return self._staged_rows

    def pending_positions(self) -> List[int]:
        """Every admitted-but-unfinished request position: seated in a
        slot, staged for refill, OR coalesced onto a seat as a dedup
        follower — exactly the set a retirement must requeue onto
        surviving replicas."""
        pos = [pid for (pid, _host, _row) in self._busy.values()]
        pos += [pid for e in self._staged for (_r, pid) in e.rows]
        pos += [fpos for fl in self._followers.values()
                for (fpos, _h, _r) in fl]
        return pos

    def retire(self) -> List[Dict]:
        """Mark THIS engine dead and hand back re-admission payloads for
        every request it still owed: one host batch per partially-served
        chunk with ``valid`` restricted to the owed rows and the rows'
        split positions pinned in ``_positions`` — same geometry, same
        ``_tag``, so re-prefilling them on a surviving replica stays
        inside the declared program family and (by per-row beam
        independence) reproduces the lost rows' results bit-exactly.
        Scheduling state clears; the arena and stats stay (a retired
        replica's commits are still real commits)."""
        self.retired = True  # set FIRST: stops an abandoned watchdog
        #                      thread the moment it wakes up
        groups: Dict[int, List] = {}
        hosts: Dict[int, Dict] = {}
        for _slot, (pid, host, r) in sorted(self._busy.items()):
            hosts[id(host)] = host
            groups.setdefault(id(host), []).append((r, pid))
        for entry in self._staged:
            hosts[id(entry.host)] = entry.host
            groups.setdefault(id(entry.host), []).extend(entry.rows)
        # dedup followers are owed requests too: each re-admits from its
        # OWN host batch (byte-identical payload), so a survivor serves
        # it bit-exactly whether it re-coalesces there or seats fresh —
        # re-admission payloads survive dedup instead of being lost
        for _leader, fl in sorted(self._followers.items()):
            for fpos, fhost, frow in fl:
                hosts[id(fhost)] = fhost
                groups.setdefault(id(fhost), []).append((frow, fpos))
        payloads: List[Dict] = []
        for hid, rows in groups.items():
            host = hosts[hid]
            requeued = dict(host)
            valid = np.zeros_like(np.asarray(host["valid"]))  # firacheck: allow[HOST-SYNC] host["valid"] is the feeder's host-side numpy batch field; no device value exists here
            positions = np.full(valid.shape[0], -1, dtype=np.int64)
            for r, pid in rows:
                valid[r] = True
                positions[r] = pid
            requeued["valid"] = valid
            requeued["_positions"] = positions
            payloads.append(requeued)
        # canonical order for determinism: by the smallest owed position
        payloads.sort(
            key=lambda b: int(b["_positions"][b["_positions"] >= 0].min()))
        self._busy.clear()
        self._staged.clear()
        self._staged_rows = 0
        self._free = collections.deque(range(self.slots))
        # RELEASE every seat's grant through the refcounted path (never
        # scribble the free list wholesale): shared blocks drop to zero
        # holders here, and the invariant checks stay meaningful on a
        # retired engine (the chaos leak check reads exactly this)
        for slot in list(self._slot_blocks):
            self._release_blocks(self._slot_blocks.pop(slot))
        self._inflight.clear()
        self._row_digest.clear()
        self._followers.clear()
        self._pending_fills.clear()   # a dead replica fills no cache
        return payloads

    @profiling.span("engine.admit")
    def admit(self, host: Dict, index: int, device_batch=None) -> None:
        """Prefill one packed batch and stage its real rows for refill.
        ``device_batch``: the feeder's already-transferred wire batch;
        None (or an engine pinned to its own device — a fleet replica
        cannot use a chunk committed elsewhere) re-ships the host batch,
        stripping the "_"-prefixed host-only fields exactly like the
        feeder does.

        With ``cfg.prefix_cache`` armed, two host-side reuse passes run
        first (decode/prefix_cache.py): rows byte-identical to a request
        already in flight COALESCE onto the existing seat (fan-out at
        harvest), and a chunk whose remaining rows are ALL cached seats
        from the cache without dispatching prefill. Dedup/cache maps
        commit only AFTER staging succeeds, so a prefill that raises (or
        a watchdog abandonment) leaves no orphaned followers or phantom
        in-flight digests behind."""
        if self._faults is not None:
            self._faults.check("engine.prefill")
        if self.retired:
            return  # abandoned by a watchdog mid-dispatch; engine is dead
        positions = host.get("_positions")  # bucketed stream only
        valid = host["valid"]
        C = valid.shape[0]
        row_ids: List[Tuple[int, int]] = []
        for r in range(C):
            if not valid[r]:
                continue
            pos_id = (int(positions[r]) if positions is not None  # firacheck: allow[HOST-SYNC] _positions is a host-only numpy field (feeder strips it from the wire); no device value exists here
                      else index * C + r)
            row_ids.append((r, pos_id))
        digests = None
        if self._cache is not None and row_ids:
            digests = host.get("_digests")  # worker-side stamp when present
            if digests is None:
                # digests are TIER-NAMESPACED (decode/quant.py): a cached
                # f32 artifact can never seat a bf16 slot — a tier change
                # is a cache miss, never a wrong answer
                digests = prefix_cache_lib.payload_digests(
                    host, namespace=self._tier_ns)
        # PASS 1 — in-flight dedup (pure reads; maps commit below): rows
        # whose digest matches an admitted-but-unharvested row become
        # followers of that seat instead of taking one of their own
        followers: List[Tuple[int, int, int]] = []  # (leader_pos, pos, row)
        seat_rows: List[Tuple[int, int]] = []
        if digests is not None:
            batch_leaders: Dict[str, int] = {}
            for r, pos_id in row_ids:
                d = digests[r]
                leader = None
                if d is not None:
                    leader = self._inflight.get(d)
                    if leader is None:
                        leader = batch_leaders.get(d)
                if leader is not None:
                    followers.append((leader, pos_id, r))
                else:
                    if d is not None:
                        batch_leaders[d] = pos_id
                    seat_rows.append((r, pos_id))
        else:
            seat_rows = row_ids

        # PASS 2 — prefill-result cache: all-hit chunks assemble host-side
        # from cached artifacts (one device_put, ZERO compiled programs —
        # the insert sees the exact pytree the prefill would have produced)
        chunk = None
        payloads: Dict[int, Dict] = {}
        pending_fill = None
        st = self.stats
        if seat_rows and self._cache is not None and all(
                self._cache.contains(digests[r]) for r, _p in seat_rows):
            for r, _pos in seat_rows:
                payload, outcome = self._cache.take(digests[r])
                if outcome == "integrity_drop":
                    st.cache_integrity_drops += 1
                if payload is None:   # fault_miss / integrity_drop:
                    payloads.clear()  # the whole chunk re-prefills — a
                    break             # cache fault is a miss, never a
                #                       wrong answer
                payloads[r] = payload
        if seat_rows and len(payloads) == len(seat_rows) and payloads:
            st.cache_hits += len(payloads)
            st.cache_hbm_bytes_saved += sum(
                prefix_cache_lib.payload_nbytes(p) for p in payloads.values())
            st.prefills_saved += 1
            chunk = jax.device_put(
                prefix_cache_lib.build_chunk(payloads, C), self.device)
            self._ensure_state(chunk)
        elif seat_rows:
            if device_batch is None or self.device is not None:
                wire = {k: v for k, v in host.items()
                        if not k.startswith("_")}
                device_batch = jax.device_put(wire, self.device)
            lengths = host.get("_prompt_len")   # an LM's batches only
            if lengths is None:
                chunk = self._prefill(self.params, device_batch)
            else:
                real = int(np.sum(lengths[valid]))
                padded = int(np.prod(host["tokens"].shape))
                with profiling.span("engine.prefill",
                                    bucket=host.get("_tag"),
                                    requests=len(seat_rows), tokens=real,
                                    padded_tokens=padded):
                    chunk = self._prefill(self.params, device_batch)
                st.prompt_tokens += real
                st.prompt_tokens_padded += padded
            if self.retired:
                # the watchdog expired while the prefill ran and the
                # replica was retired: its requests were requeued
                # elsewhere — staging them here too would decode them
                # twice (and no dedup/cache map was touched yet)
                return
            self._guard_step(self.label(PREFILL_KIND, host.get("_tag")))
            self._ensure_state(chunk)
            st.prefills += 1
            if self._cache is not None:
                # miss-fill, DEFERRED: schedule the artifact D2H now
                # (async — overlaps the decode steps) and store at the
                # next harvest, the designated sync boundary. Rows whose
                # entries existed but could not serve (this chunk
                # dispatched) count as misses and are refreshed there.
                st.cache_misses += len(seat_rows)
                fills = [(r, digests[r]) for r, _pos in seat_rows
                         if digests[r] is not None]
                if fills:
                    for f in prefix_cache_lib.ARTIFACT_FIELDS:
                        a = chunk[f]
                        if hasattr(a, "copy_to_host_async"):
                            a.copy_to_host_async()
                    # committed below with the other shared maps: retire()
                    # clears _pending_fills ("a dead replica fills no
                    # cache"), and an abandoned thread appending after
                    # that clear would resurrect a fill on a dead engine
                    pending_fill = (fills, chunk)

        # COMMIT — maps and staging mutate only on a fully-successful
        # path, and only on a LIVE engine: the cache-hit branch above
        # dispatches nothing but still crossed a device_put a watchdog
        # could have abandoned this thread inside — committing here
        # would mutate _staged/_inflight/_followers under a concurrent
        # retire() (the same race the miss path's post-dispatch re-check
        # guards)
        if self.retired:
            return
        if pending_fill is not None:
            self._pending_fills.append(pending_fill)
        if followers:
            for leader, pos_id, r in followers:
                self._followers.setdefault(leader, []).append(
                    (pos_id, host, r))
            st.dedup_fanout += len(followers)
            if not seat_rows:
                st.prefills_saved += 1  # whole chunk coalesced: no dispatch
        if not seat_rows:
            return
        if digests is not None:
            for r, pos_id in seat_rows:
                if digests[r] is not None:
                    self._inflight[digests[r]] = pos_id
                    self._row_digest[pos_id] = digests[r]
        # the chunk's tar budget: its bucket geometry is visible in
        # the packed msg width (make_batch slices msg to the bucket's
        # tar) — under decode_tar_buckets that budget caps generation
        # and sizes the paged block reservation; otherwise every slot
        # gets the full arena budget, the historical behavior
        limit = (int(host["msg"].shape[1]) if self.cfg.decode_tar_buckets
                 else self.cfg.tar_len)
        self._staged.append(_Staged(
            chunk=chunk, host=host,
            rows=collections.deque(seat_rows), limit=limit,
            row_limits=host.get("_limits")))
        self._staged_rows += len(seat_rows)

    @profiling.span("engine.refill")
    def refill(self, refill_order: str = "fifo") -> None:
        """Insert staged rows into every free slot (one insert dispatch
        per staged chunk touched). Each seated row is granted
        its reservation — ceil(limit / block) blocks — from the free
        list; when the pool cannot cover the HEAD row's reservation the
        refill stops there and waits for harvests to return blocks
        (head-of-line, so admission order — hence output bytes — stays a
        pure function of the stream, pool size included)."""
        # retired-engine bail-early (docs/FAULTS.md): checked at every
        # loop boundary so an abandoned watchdog thread that wakes up
        # mid-refill stops mutating scheduling state a concurrent
        # retire() is handing to the survivors
        while not self.retired and self._free and self._staged:
            entry = self._staged[0]

            def need_of(row: int) -> int:
                return paging.blocks_per_seq(entry.limit_of(row),
                                             self._block_size)
            if len(self._free_blocks) < need_of(entry.rows[0][0]):
                break  # head-of-line: blocks return at the next harvest
            C = entry.host["valid"].shape[0]
            slot_ids = np.full((C,), self.slots, dtype=np.int32)  # S = drop
            limits = np.full((C,), entry.limit, dtype=np.int32)
            block_rows = np.full((C, self._table_width), self._pool_blocks,
                                 dtype=np.int32)  # P = unmapped sentinel
            n_ins = 0
            while (not self.retired and self._free and entry.rows
                   and len(self._free_blocks) >= need_of(entry.rows[0][0])):
                r, pos_id = entry.rows.popleft()
                slot = (self._free.popleft() if refill_order == "fifo"
                        else self._free.pop())
                slot_ids[r] = slot
                limits[r] = entry.limit_of(r)
                need = need_of(r)
                grant = self._acquire_blocks(need)
                block_rows[r, :need] = grant
                self._slot_blocks[slot] = grant
                self._busy[slot] = (pos_id, entry.host, r)
                n_ins += 1
            new_state = self._insert(self._state, entry.chunk, slot_ids,
                                     limits, block_rows,
                                     self._fresh_arg(entry.fresh))
            entry.fresh = False
            if self.retired:
                # the watchdog expired while the insert dispatch ran and
                # the replica was retired: retire() already requeued
                # every owed row — the live loop owns the guard, stats,
                # and staging state now; this abandoned thread must not
                # touch them (RETIRED-RECHECK discipline)
                return
            self._state = new_state
            self._guard_step(self.label(
                INSERT_LABEL, entry.host.get("_tag")
                if self.smodel.insert_by_geometry else None))
            self.stats.refills += 1
            self.stats.slots_refilled += n_ins
            self._staged_rows -= n_ins
            if not entry.rows:
                self._staged.popleft()

    @profiling.span("engine.step_dispatch")
    def step_dispatch(self) -> None:
        """Dispatch one step program (async — the fleet dispatches every
        replica's step before any harvest readback, so replica compute
        overlaps across chips) and start the host copy of what its
        harvest will read."""
        if self._faults is not None:
            self._faults.check("engine.step")
        if self.retired:
            return  # abandoned by a watchdog mid-dispatch; engine is dead
        # speculative draft->verify->accept replaces the harvest-cadence
        # scan when armed and not cooling down after an acceptance stall
        # (decode/spec.py): the drafter reads the arena, the verify donates
        # it exactly like the plain step. Either program family member
        # advances every live slot at least one frame, so the
        # step->harvest cadence contract is unchanged.
        spec_now = self._spec_tier is not None and self._spec_cd == 0
        if spec_now:
            drafts = self._draft(self._decode_params, self._state)
            new_state, out = self._verify(
                self._decode_params, self._state, drafts)
        else:
            new_state, out = self._step(self._decode_params, self._state)
        # the harvest's transfer starts now: it lands as the step ends,
        # whatever the host queues behind the step meanwhile
        for leaf in out.values():
            leaf.copy_to_host_async()
        if self.retired:
            # the watchdog expired while the dispatch call was in flight:
            # do NOT touch the shared compile guard or stats from this
            # abandoned thread — the live loop owns them now
            return
        self._state, self._pending_out = new_state, out
        self._fills_due = len(self._pending_fills)
        if self._spec_cd > 0:
            self._spec_cd -= 1
        st = self.stats
        if spec_now:
            km = f"k{self._spec_k}"
            self._guard_step(self.label(spec_lib.DRAFT_LABEL, km))
            self._guard_step(self.label(spec_lib.VERIFY_LABEL, km))
            # ONE step: the forwards-per-token accounting (see EngineStats)
            # — the frames the verify actually ran land in spec_frames at
            # harvest, where the device counters are drained
            st.steps += 1
            st.verify_dispatches += 1
        else:
            self._guard_step(self.label(STEP_LABEL))
            st.steps += max(1, int(self.cfg.engine_harvest_every))
        st.step_dispatches += 1
        # pool accounting, re-stamped every dispatch so the bench's stats
        # resets between timed windows keep the HBM fields populated
        st.pool_blocks = self._pool_blocks
        st.kv_block_size = self._block_size
        by_kind = self._kv_bytes_by_kind
        st.kv_bytes_per_slot = sum(by_kind.values())
        st.kv_bytes_per_slot_full = by_kind.get("full", 0)
        st.kv_bytes_per_slot_window = by_kind.get("window", 0)
        st.kv_bytes_per_slot_state = by_kind.get("state", 0)
        st.kv_dtype = self.cfg.kv_dtype
        st.serve_precision = self.cfg.serve_precision
        used = self._pool_blocks - len(self._free_blocks)
        st.block_steps += used
        st.peak_blocks = max(st.peak_blocks, used)
        if self._followers or self.shared_positions:
            # shared blocks: grants whose seat is serving a coalesced
            # fan-out group — one block set, N requests' worth of
            # decode (the dedup half of the HBM-reuse story; groups
            # coalesced by the serve loop arrive via shared_positions)
            fan = self.shared_positions
            shared = sum(
                len(self._slot_blocks.get(s, ()))
                for s, (pid, _h, _r) in self._busy.items()
                if pid in self._followers or pid in fan)
            st.shared_block_peak = max(st.shared_block_peak, shared)

    @profiling.span("engine.harvest")
    def harvest(self) -> List[EngineItem]:
        """Read back what the dispatched step wrote out for it and return
        every newly settled slot's sample. ONE blocking ``device_get``
        brings the step's own outputs — occupancy, done mask, every slot's
        (tokens, probs), the device counters — to the host, their copy
        begun at dispatch, and the settled rows are taken there (a round
        trip to the chip costs ~1.5 ms whatever it carries; the bytes
        never were the cost: PERF.md §6). No program is dispatched
        here, so nothing the host queued behind the step — the next
        chunk's prefill — stands between the step and this read. COPIES,
        not views: the outputs are device buffers of their own, which the
        next dispatch's donation of the arena cannot touch, and
        ``np.array`` makes the host side writable and independent of
        them. Items are materialized EAGERLY (a plain list, not a lazy
        generator): the bookkeeping below must be whole before a caller's
        refill()."""
        if self._faults is not None:
            self._faults.check("engine.harvest")
        if self.retired or self._pending_out is None:
            return []  # abandoned by a watchdog / no step since the last
        if self._cache is not None and self._fills_due:
            # commit deferred miss-fills BEFORE any dedup bookkeeping is
            # popped below: a digest leaves _inflight only once its
            # entry is stored, so a repeat arriving next round finds
            # either the in-flight leader or the cached artifacts
            self._drain_pending_fills(self._fills_due)
            self._fills_due = 0
        stats = self.stats
        # engine.harvest.wait: the ONE blocking read — the HOST waiting for
        # the dispatched step (device busy); everything after it in this
        # method is host work. PHASE 1 — the readback only, no
        # bookkeeping: a watchdog expiry mid-device_get abandons this
        # thread with every settled slot still in _busy, so retire()
        # requeues ALL of them. Phase 2 is pure host dict work —
        # microseconds, nothing left to hang on.
        with profiling.span("engine.harvest.wait"):
            got = jax.device_get(self._pending_out)
            if self.retired:
                return []  # abandoned by a watchdog mid-readback
            self._pending_out = None
            occ_now = int(got["occ"])
            stats.occupied_slot_steps += occ_now
            stats.harvest_bytes_read += (got["tokens"].nbytes
                                         + got["probs"].nbytes)
            if "spec" in got:
                # the verify's device counters ride the SAME transfer —
                # spec metering adds no host sync of its own
                # (decode/spec.run_verify)
                tested, matched, iters = (int(x) for x in got["spec"])
                stats.drafted += self._spec_k * occ_now
                stats.accepted += matched
                stats.steps_saved += tested - occ_now
                stats.spec_frames += iters
                if occ_now and matched == 0:
                    # acceptance stalled (a rare-token span the drafter
                    # cannot see): run a few plain dispatches before
                    # re-arming, so a cold stretch does not pay
                    # draft+verify per emitted token
                    self._spec_cd = spec_lib.STALL_COOLDOWN
            if "counters" in got:
                # the model's device-side counts (slot_model:
                # model/axk1.COUNTERS); int32 on the device, so the
                # window's share is the wrapped difference
                now = np.array(got["counters"]).astype(np.uint32)
                grown = now - (self._counters_seen
                               if self._counters_seen is not None else 0)
                self._counters_seen = now
                for name, n in zip(self.smodel.arena_counters,
                                   grown.tolist()):
                    setattr(stats, name, getattr(stats, name) + n)
        done = got["done"]
        newly = [s for s in self._busy if done[s]]
        self._settled_last = len(newly)
        items: List[EngineItem] = []
        if newly:   # a harvest that settles nothing records no read
            with profiling.span("engine.harvest.read", rows=len(newly)):
                toks_np, probs_np = np.array(got["tokens"]), \
                    np.array(got["probs"])
                stats.harvest_reads += 1
                # PHASE 2 — the readback landed: retire the bookkeeping
                for s in newly:
                    pos_id, host, r = self._busy.pop(s)
                    self._free.append(s)
                    # the slot's block grant is RELEASED through the
                    # refcounted allocator — contents stay as the slot left
                    # them (unmapped, not zeroed; the next grantee's validity
                    # mask makes them an exact 0.0), and a block returns to
                    # the free deque only at refcount zero
                    self._release_blocks(self._slot_blocks.pop(s, ()))
                    stats.commits += 1
                    stats.harvest_row_reads += 1
                    toks_s, probs_s = toks_np[s], probs_np[s]
                    items.append(EngineItem(position=pos_id, host=host, row=r,
                                            tokens=toks_s, probs=probs_s))
                    # dedup fan-out delivery: every follower coalesced onto
                    # this seat gets the leader's settled beams at its OWN
                    # output position (one decode, N commits — byte-identical
                    # by construction: same digest => same payload bytes)
                    d = self._row_digest.pop(pos_id, None)
                    if d is not None:
                        self._inflight.pop(d, None)
                    for fpos, fhost, frow in self._followers.pop(pos_id, ()):
                        stats.commits += 1
                        items.append(EngineItem(position=fpos, host=fhost,
                                                row=frow, tokens=toks_s,
                                                probs=probs_s))
        return items

    def run(self, feed, *, refill_order: str = "fifo"
            ) -> Iterator[EngineItem]:
        """Drive the engine over ``feed`` — an iterable of
        data.feeder.FedBatch items carrying the SAME packed batches the
        batched-beam path decodes (item.device is the prefill input;
        item.host keeps the text-cooking fields and the packer's
        ``_positions``/``_tag`` metadata).

        ``refill_order``: which freed slot a waiting request lands in —
        "fifo" (queue) or "lifo" (stack). Output is identical either way
        (results are keyed by split position and samples are slot-
        independent); the knob exists so the determinism tests can pin
        exactly that.

        Yields one :class:`EngineItem` per real sample as it settles.
        """
        if refill_order not in ("fifo", "lifo"):
            raise ValueError(f"refill_order {refill_order!r} not in "
                             f"{{'fifo', 'lifo'}}")
        self.begin_stream()
        feed_iter = iter(feed)
        exhausted = False
        harvested = False
        # prefill dispatches between two step dispatches, both admits of a
        # pass counted (slot_model: prefill_budget); 0: no limit
        budget = self.smodel.prefill_budget
        admitted = 0

        def admit_while(wanted) -> int:
            """Admit from the feed while ``wanted()`` and the budget
            allow; -> the prefill programs that dispatched."""
            nonlocal exhausted, admitted
            before = self.stats.prefills
            while not exhausted and wanted() and not (
                    budget and admitted >= budget):
                admitted += 1
                try:
                    item = next(feed_iter)
                except StopIteration:
                    exhausted = True
                    break
                # a put=False feed (the fleet's shared queue) leaves
                # item.device == item.host; admit re-ships it then
                self.admit(item.host, item.index,
                           None if item.device is item.host
                           else item.device)
            return self.stats.prefills - before

        # engine.run is a generator: its root span is opened and closed
        # explicitly and is the thread's parent only inside the `with
        # root` stretches — never across a yield, where the consumer's
        # own spans must not become this root's children
        root = profiling.begin("engine.run")
        try:
            while True:
                with root:
                    # top-up: rows for every free slot the rows staged
                    # ahead do not cover (the first fill of the stream,
                    # then only where a harvest freed more than was
                    # staged for) — on the critical path
                    n = admit_while(
                        lambda: self._staged_rows < len(self._free))
                    if harvested:
                        self.stats.prefills_topup += n

                    # refill every free slot from the staged queue
                    self.refill(refill_order)

                    if not self._busy:
                        if exhausted:
                            break
                        admitted = 0
                        continue  # nothing in flight yet: pull more input

                    self.step_dispatch()
                    admitted = 0
                    # prefill ahead, queued on the device behind the step
                    # in flight: `depth` chunks staged, and rows for what
                    # the step is expected to free — the last harvest's
                    # count — so the device runs the next chunk's prefill
                    # while the host harvests
                    self.stats.prefills_ahead += admit_while(
                        lambda: self.wants_input(self._settled_last))
                    items = self.harvest()
                    harvested = True
                yield from items
        finally:
            root.end()
