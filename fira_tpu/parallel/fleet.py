"""Replicated slot-engine decode fleet: N engines, one admission queue.

The slot-refill engine (decode/engine.py) made decode wall clock scale
with tokens emitted — on ONE chip. This module is the multi-chip half of
that story (ROADMAP item 3; Orca's iteration-level scheduling generalized
to a serving fleet, PAPERS.md "Continuous batching / inference serving"):
N :class:`~fira_tpu.decode.engine.SlotEngine` replicas — one per
data-mesh slice, each with its own per-chip KV arena, params copy, and
compiled program set — pull packed chunks from ONE shared admission queue
(the async feeder stream every decode driver already uses) and
harvest/refill interleave across replicas.

Scheduling is the single engine's own steppable scheduler, round-robined:

- **admission**: replicas claim chunks from the shared queue in replica
  order whenever their prefill-ahead policy wants input (same
  ``engine_prefill_depth`` staging per replica). The feeder runs
  ``put=False`` — which replica a chunk lands on is a scheduling
  decision, so the H2D transfer happens at admission, onto the claiming
  replica's own device.
- **step interleave**: every live replica's step program is dispatched
  BEFORE any replica's harvest readback, so replica compute overlaps
  across chips while the host walks the fleet.
- **harvest/refill**: each replica harvests its settled slots (yielding
  :class:`~fira_tpu.decode.engine.EngineItem` exactly like the single
  engine) and refills from its staged chunks on the next round.

Output invariance (pinned by tests/test_fleet.py): per-sample results are
bit-exact regardless of which replica/slot computes them — same params,
same prefill batches (a chunk is always prefilled WHOLE, wherever it
lands), same per-slot step math — so the decoded file bytes are identical
to the single-engine path for ANY replica count and refill interleaving.

Guard labels: each replica suffixes its labels with ``r<i>``
(``engine_step[r1]``, ``engine_prefill[a16.e256.t12.r1]``) because each
replica compiles its own program set (per-device executables); the
declared family is the union over replicas (:meth:`EngineFleet.labels`)
and still closes at one compile per label.

Cross-request reuse (``cfg.prefix_cache`` — decode/prefix_cache.py):
each replica owns a PER-CHIP prefix cache and in-flight dedup map,
exactly like its per-chip KV arena (cached artifacts re-enter via
``device_put`` onto the owning replica's device, so no cross-chip
traffic exists to coordinate). Dedup therefore coalesces within a
replica in drain mode (the serve loop's admission-time dedup,
serve/server.py, is the fleet-GLOBAL layer); output bytes stay invariant
either way because a coalesced delivery is byte-identical to a fresh
decode of the same payload. Retirement RELEASES a dead replica's shared
block grants through the refcounted allocator and folds its coalesced
followers into the re-admission payloads — requeued requests survive
dedup (re-coalescing or seating fresh on a survivor, both bit-exact)
instead of being lost or decoded twice.

Graceful degradation (docs/FAULTS.md): a replica whose dispatch raises —
or exceeds ``cfg.dispatch_watchdog_s`` wall seconds and is abandoned on
its watchdog thread — is RETIRED: removed from the service rotation, its
in-flight and staged requests requeued onto the surviving replicas (the
dead replica excluded by construction), and the drain continues
degraded. Requeued requests re-prefill inside the same declared program
family and, by per-row beam independence, produce bit-identical results
wherever they land — so the decoded file bytes of a run that lost a
replica equal the no-fault run's exactly (pinned by tests/test_robust
.py). Retirements and requeues are machine-recorded in FleetStats.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence

import jax
import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.decode.engine import EngineItem, EngineStats, SlotEngine
from fira_tpu.model.model import FiraModel
from fira_tpu.robust import recovery as recovery_lib
from fira_tpu.robust.watchdog import run_with_watchdog


def fleet_divisibility_errors(cfg: FiraConfig) -> List[str]:
    """Parse-time fleet admission check (the decode twin of
    parallel.mesh.divisibility_errors): a nonzero ``engine_slots`` is the
    fleet-TOTAL arena, split evenly across replicas — reject a non-divisor
    up front instead of failing in the arena allocation mid-run. The
    paged-KV pool splits the same way (``kv_pool_blocks`` is the fleet
    total), but its split and floors are owned by
    decode/paging.paging_errors, which the CLI runs right after this
    check — one message per violation, not two."""
    reps = max(1, int(cfg.engine_replicas))
    if reps > 1 and cfg.engine_slots and cfg.engine_slots % reps:
        return [f"engine_slots {cfg.engine_slots} is not divisible by "
                f"engine_replicas {reps} (the fleet splits the total slot "
                f"arena evenly across replicas)"]
    return []


@dataclasses.dataclass
class FleetStats:
    """Aggregate + per-replica accounting for one fleet run."""

    replicas: List[EngineStats]
    # degradation accounting (docs/FAULTS.md): one entry per retired
    # replica ({"replica": tag, "error": str}) and the total requests
    # requeued onto survivors across all retirements
    retirements: List[Dict] = dataclasses.field(default_factory=list)
    requeues: int = 0
    # recovery accounting (robust/recovery.py): one entry per respawned
    # replacement ({"replica": new tag, "origin": lineage, "spare": bool})
    respawns: List[Dict] = dataclasses.field(default_factory=list)

    @property
    def commits(self) -> int:
        return sum(r.commits for r in self.replicas)

    def summary(self) -> Dict:
        tot = lambda f: sum(getattr(r, f) for r in self.replicas)  # noqa: E731
        steps_x_slots = sum(r.steps * r.slots for r in self.replicas)
        # fleet-wide paged-KV pool accounting: pools are per-chip, so
        # blocks total across replicas and utilization weights each
        # replica's pool by its own dispatch count
        pool_capacity = sum(r.step_dispatches * r.pool_blocks
                            for r in self.replicas)
        pool_util = (round(tot("block_steps") / pool_capacity, 4)
                     if pool_capacity else 0.0)
        return {
            "pool_blocks": tot("pool_blocks"),
            "kv_block_size": max((r.kv_block_size for r in self.replicas),
                                 default=0),
            "kv_bytes_per_slot": max((r.kv_bytes_per_slot
                                      for r in self.replicas), default=0),
            # low-precision serving tiers (decode/quant.py): cfg-uniform
            # across the fleet, so any replica's stamp is THE answer —
            # "f32" when no replica has dispatched yet
            "kv_dtype": next((r.kv_dtype for r in self.replicas
                              if r.step_dispatches), "f32"),
            "serve_precision": next((r.serve_precision
                                     for r in self.replicas
                                     if r.step_dispatches), "f32"),
            "peak_blocks": tot("peak_blocks"),
            "pool_utilization": pool_util,
            "replicas": len(self.replicas),
            "slots": tot("slots"),
            "prefills": tot("prefills"),
            # where SlotEngine.run placed them (zero under the fleet's own
            # round order, which admits before the step)
            "prefills_ahead": tot("prefills_ahead"),
            "prefills_topup": tot("prefills_topup"),
            "refills": tot("refills"),
            "slots_refilled": tot("slots_refilled"),
            "steps_run": tot("steps"),
            "step_dispatches": tot("step_dispatches"),
            "commits": self.commits,
            "dispatches": sum(r.dispatches for r in self.replicas),
            # harvest readback accounting (decode/engine.py): reads that
            # delivered settled rows, the rows they delivered and the
            # per-replica D2H bytes, totalled across the fleet
            "harvest_reads": tot("harvest_reads"),
            "harvest_row_reads": tot("harvest_row_reads"),
            "harvest_bytes_read": tot("harvest_bytes_read"),
            # cross-request reuse accounting (decode/prefix_cache.py):
            # caches are per-chip, so counts total across replicas and
            # the hit rate is the fleet-wide served-from-cache fraction
            "cache_hits": tot("cache_hits"),
            "cache_misses": tot("cache_misses"),
            "cache_hit_rate": round(
                tot("cache_hits") / (tot("cache_hits")
                                     + tot("cache_misses")), 4)
            if tot("cache_hits") + tot("cache_misses") else 0.0,
            "cache_evictions": tot("cache_evictions"),
            "cache_integrity_drops": tot("cache_integrity_drops"),
            "prefills_saved": tot("prefills_saved"),
            "cache_hbm_bytes_saved": tot("cache_hbm_bytes_saved"),
            "dedup_fanout": tot("dedup_fanout"),
            "shared_block_peak": tot("shared_block_peak"),
            # speculative draft-and-verify accounting (decode/spec.py):
            # drafters are per-replica, so counts total across the fleet
            # and the acceptance rate is the fleet-wide accepted fraction;
            # per-replica rates ride alongside like occupancy does
            "drafted": tot("drafted"),
            "accepted": tot("accepted"),
            "acceptance_rate": round(tot("accepted") / tot("drafted"), 4)
            if tot("drafted") else 0.0,
            "verify_dispatches": tot("verify_dispatches"),
            "steps_saved": tot("steps_saved"),
            "spec_frames": tot("spec_frames"),
            "per_replica_acceptance": [
                round(r.acceptance_rate, 4) for r in self.replicas],
            # fleet-wide mean fraction of slots doing real beam work
            "slot_occupancy": round(
                tot("occupied_slot_steps") / steps_x_slots, 4
            ) if steps_x_slots else 0.0,
            "per_replica_occupancy": [
                round(r.slot_occupancy, 4) for r in self.replicas],
            "per_replica_commits": [r.commits for r in self.replicas],
            # graceful-degradation record: which replicas were retired
            # (dispatch raised / watchdog expired) and how many requests
            # were requeued onto survivors
            "retirements": len(self.retirements),
            "retired_replicas": [r["replica"] for r in self.retirements],
            "requeues": self.requeues,
            # self-healing record (robust/recovery.py): replacements that
            # joined the fleet mid-run, and whether each was a warm-spare
            # attach or a fresh mid-run build
            "respawns": len(self.respawns),
            "respawned_replicas": [r["replica"] for r in self.respawns],
            "spare_attaches": sum(1 for r in self.respawns if r["spare"]),
        }


class EngineFleet:
    """N-replica slot-engine decode over one shared admission queue.

    ``replicas``: engine count. ``slots``: fleet-TOTAL arena (must divide
    by ``replicas``); 0/None falls back to each replica's own default
    (``cfg.engine_slots`` total when nonzero, else ``cfg.test_batch_size``
    slots PER replica). ``devices``: one device per replica; defaults to
    ``jax.devices()`` round-robin, so on an N-device mesh each replica
    owns its own chip and on a single chip the replicas share it (still
    output-identical — the tests pin exactly that).
    """

    def __init__(self, model: FiraModel, params, cfg: FiraConfig, *,
                 replicas: int, slots: Optional[int] = None, guard=None,
                 devices: Optional[Sequence] = None, faults=None):
        if replicas < 1:
            raise ValueError(f"fleet needs >= 1 replica, got {replicas}")
        total = int(slots or cfg.engine_slots or 0)
        if total and total % replicas:
            raise ValueError(
                f"engine_slots {total} is not divisible by engine_replicas "
                f"{replicas} (the fleet splits the total slot arena evenly "
                f"across replicas)")
        per_replica = total // replicas if total else None
        # kv_pool_blocks is the fleet TOTAL like engine_slots: each
        # replica owns a per-chip pool of total/replicas blocks (0 keeps
        # each engine's own full-residency auto size)
        pool_total = int(cfg.kv_pool_blocks)
        if pool_total and pool_total % replicas:
            raise ValueError(
                f"kv_pool_blocks {pool_total} is not divisible by "
                f"engine_replicas {replicas} (the fleet splits the total "
                f"KV block pool evenly across replicas)")
        per_replica_pool = pool_total // replicas if pool_total else None
        if devices is None:
            devs = jax.devices()
            devices = [devs[i % len(devs)] for i in range(replicas)]
        elif len(devices) < replicas:
            raise ValueError(f"{len(devices)} devices for {replicas} "
                             f"replicas")
        self.cfg = cfg
        self.faults = faults
        # degradation record (docs/FAULTS.md) — ``engines`` stays the
        # FULL roster (stats/labels must keep counting a retired
        # replica's commits); the run loop keeps its own live list
        self.retirements: List[Dict] = []
        self.requeues: int = 0
        # recovery machinery (robust/recovery.py): replace_slot needs the
        # build inputs a respawn re-runs (the ORIGINAL params — each
        # replacement re-device_puts its own copy), the stored warm
        # batches, the per-lineage respawn ordinals, and the warm-spare
        # pool (built on demand by build_spares)
        self._model = model
        self._params = params
        self._guard = guard
        self._per_replica = per_replica
        self._per_replica_pool = per_replica_pool
        self._devices = list(devices)
        self._warm: Optional[List] = None
        self._respawn_counts: Dict[str, int] = {}
        self._spare_seq = 0
        self.respawns: List[Dict] = []
        self.spares: List[SlotEngine] = []
        self.engines = [
            SlotEngine(model, jax.device_put(params, devices[i]), cfg,
                       slots=per_replica, guard=guard, device=devices[i],
                       tag=f"r{i}", pool_blocks=per_replica_pool,
                       faults=faults)
            for i in range(replicas)
        ]

    @property
    def stats(self) -> FleetStats:
        return FleetStats([e.stats for e in self.engines],
                          retirements=list(self.retirements),
                          requeues=self.requeues,
                          respawns=list(self.respawns))

    def labels(self, table=None) -> List[str]:
        """The fleet's declared program family: the union of every
        replica's (geometry x {prefill, step, insert}) labels."""
        return [lbl for e in self.engines for lbl in e.labels(table)]

    def cache_put(self, digest, payload) -> None:
        """Fan one externally-prefilled artifact payload out to EVERY
        live replica's prefix cache (the disaggregated prefill tier's
        delivery seam — serve/disagg.py): whichever replica's rotation
        claims the request, its admission takes the all-hit cache path.
        The payload is host numpy shared by reference — the caches store
        it read-only and ``build_chunk`` re-packs copies at seat."""
        for eng in self.engines:
            eng.cache_put(digest, payload)

    def prewarm(self, warm_batches) -> None:
        """Compile every replica's prefill family up front (each replica
        owns its own executables — per-device compiles are real compiles,
        and the guard budget prices them per replica label). The batches
        are KEPT: a respawned replacement prewarms through the same
        declared family (replace_slot), so post-warmup dispatches on it
        never pay a first-use compile either."""
        batches = list(warm_batches)
        self._warm = batches
        for eng in self.engines:
            eng.prewarm(batches)

    # --- self-healing (robust/recovery.py; docs/FAULTS.md) ---------------

    def _build_replacement(self, device, tag: str) -> SlotEngine:
        """One fresh engine on ``device``: params re-``device_put``, the
        per-replica paged pool re-allocated, labels declared under the
        new tag, and the stored warm batches prewarmed — the replacement
        pays its compiles HERE (each new label's warmup dispatch), never
        on a post-warmup serving dispatch."""
        params = (jax.device_put(self._params, device)
                  if device is not None else self._params)
        eng = SlotEngine(self._model, params, self.cfg,
                         slots=self._per_replica, guard=self._guard,
                         device=device, tag=tag,
                         pool_blocks=self._per_replica_pool,
                         faults=self.faults)
        if self._guard is not None and self._guard.family_closed:
            # additive declare into the ALREADY-closed family only: on an
            # open family (the unbucketed drivers never declare) a first
            # declare here would close it around just the replacement's
            # labels and outlaw every serving replica's programs
            tags = [t for (_h, t) in (self._warm or [])] or [None]
            self._guard.declare(eng.labels_for_tags(tags))
        if self._warm:
            eng.prewarm(self._warm)
        return eng

    def build_spares(self, count: int) -> None:
        """Build the warm-spare pool: ``count`` prewarmed standby engines
        (tags ``sp<i>``, devices round-robin like the fleet), idle until
        a retirement attaches one. Refills up to ``count`` — a reused
        warm fleet must not double its pool — and tags from a monotone
        sequence, never reusing an attached spare's tag (labels and
        heartbeat/lineage records key on it)."""
        while len(self.spares) < int(count):  # firacheck: allow[HOST-SYNC] count is the engine_spares config int; no device value exists here
            i = self._spare_seq
            self._spare_seq += 1
            self.spares.append(self._build_replacement(
                self._devices[i % len(self._devices)], f"sp{i}"))

    def take_spare(self, device) -> Optional[SlotEngine]:
        """Pop a spare, preferring one already on ``device`` (zero
        cross-device params movement); any spare otherwise — restored
        capacity beats placement."""
        for i, sp in enumerate(self.spares):
            if sp.device is device:
                return self.spares.pop(i)
        return self.spares.pop(0) if self.spares else None

    def replace_slot(self, origin: str, device):
        """Replace one retired lineage: a warm spare when the pool has
        one (O(attach)), else a fresh build on the lineage's device
        (O(compile)). The replacement joins the ROSTER here (its commits
        count in FleetStats); the caller owns adding it to the live
        service rotation. Returns (engine, from_spare)."""
        spare = self.take_spare(device)
        if spare is not None:
            self.engines.append(spare)
            self.respawns.append({"replica": spare.tag or "r0",
                                  "origin": origin, "spare": True})
            return spare, True
        k = self._respawn_counts.get(origin, 0) + 1
        self._respawn_counts[origin] = k
        tag = f"{origin}{recovery_lib.RESPAWN_TAG_SEP}{k}"
        eng = self._build_replacement(device, tag)
        self.engines.append(eng)
        self.respawns.append({"replica": tag, "origin": origin,
                              "spare": False})
        return eng, False

    @staticmethod
    def _as_payload(item) -> Dict:
        """Normalize a feeder item into a requeue-able admission payload:
        positions pinned in ``_positions`` (the unbucketed stream derives
        them from the item index, exactly like SlotEngine.admit would),
        so the SAME host batch can be admitted on ANY replica, including
        after the first attempt's replica died mid-prefill."""
        host = dict(item.host)
        if host.get("_positions") is None:
            C = host["valid"].shape[0]
            host["_positions"] = (item.index * C
                                  + np.arange(C, dtype=np.int64))
        return host

    def _retire(self, eng: SlotEngine, alive: List[SlotEngine],
                pending: "collections.deque", err: BaseException,
                recovery=None) -> None:
        """Retire one replica: drop it from the service rotation, requeue
        every request it still owed at the FRONT of the shared admission
        stream (they arrived earliest), and record the event. With
        ``recovery`` armed (cfg.max_respawns — robust/recovery.py) dead
        lineages with budget left are respawned HERE, immediately and
        wall-backed-off (drain mode has no scheduler rounds to gate on),
        and the replacements join the live rotation. With no survivors
        and no respawn budget there is nothing to degrade onto — a drain
        run must fail loudly, never hang."""
        alive.remove(eng)
        payloads = eng.retire()
        # TOCTOU guard: an admit the watchdog abandoned can finish
        # STAGING in the window between the timeout raising here and
        # retire() flipping the retired flag — its chunk would then come
        # back in `payloads` while ALSO still sitting at pending[0]
        # (never popleft'd, because the admit call raised). Requeuing
        # both copies would decode the same positions twice and blow the
        # ordered writer's duplicate check, so rows already owed by a
        # queued payload are masked out here (the serve loop dedups the
        # same way via its `seen` set).
        pending_pos = set()
        for b in pending:
            v = np.asarray(b["valid"], dtype=bool)  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
            pending_pos.update(int(p) for p in  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
                               np.asarray(b["_positions"])[v])  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
        n_req = 0
        kept = []
        for p in payloads:
            v = np.asarray(p["valid"], dtype=bool).copy()  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
            pos = np.asarray(p["_positions"])  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
            for r in range(v.shape[0]):
                if v[r] and int(pos[r]) in pending_pos:  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
                    v[r] = False
            if v.any():
                p["valid"] = v.astype(np.asarray(p["valid"]).dtype)  # firacheck: allow[HOST-SYNC] requeue payloads are host numpy batches (SlotEngine.retire / _as_payload); no device value exists in this dedup
                kept.append(p)
                n_req += int(v.sum())
        for p in reversed(kept):
            pending.appendleft(p)
        self.requeues += n_req
        self.retirements.append({"replica": eng.tag or "r0",
                                 "error": f"{type(err).__name__}: {err}"})
        if recovery is not None:
            recovery.note_retirement(eng, -1,
                                     error=f"{type(err).__name__}: {err}")
            for new in recovery.heal_all():
                new.begin_stream()
                alive.append(new)
        if not alive:
            raise RuntimeError(
                f"all {len(self.engines)} fleet replicas retired; last "
                f"error on {eng.tag or 'r0'}: {err}") from err

    def run(self, feed, *, refill_order: str = "fifo"
            ) -> Iterator[EngineItem]:
        """Drive the fleet over ``feed`` (data.feeder.FedBatch items from
        a ``put=False`` feeder — the shared admission queue). Yields one
        EngineItem per real sample as it settles, across all replicas;
        results are keyed by split position, so the ordered writer
        downstream is replica-agnostic.

        Degradation: each replica's service round runs under
        ``cfg.dispatch_watchdog_s`` (0 = off) and a try/except — a raise
        or watchdog expiry retires the replica and requeues its requests
        (:meth:`_retire`); requeued payloads are admitted BEFORE fresh
        feed items, onto whichever surviving replica wants input next."""
        if refill_order not in ("fifo", "lifo"):
            raise ValueError(f"refill_order {refill_order!r} not in "
                             f"{{'fifo', 'lifo'}}")
        for eng in self.engines:
            eng.begin_stream()
        feed_iter = iter(feed)
        exhausted = False
        wd = float(self.cfg.dispatch_watchdog_s)
        # self-healing (robust/recovery.py): with a respawn budget armed,
        # a retirement is followed by an immediate wall-backed-off
        # replacement instead of staying a permanent capacity loss
        recovery = (recovery_lib.RecoveryManager(self, self.cfg,
                                                 wall_clock=True)
                    if self.cfg.max_respawns > 0 else None)
        if recovery is not None and self.cfg.engine_spares:
            # the drain path arms its own spare pool (the serve driver
            # builds it in serve_split) — a knob that validates must act
            self.build_spares(self.cfg.engine_spares)
        # re-admission payloads from retired replicas, served head-first
        pending: "collections.deque" = collections.deque()
        alive = [eng for eng in self.engines if not eng.retired]
        while True:
            # admission + refill, replica order (deterministic: which
            # replica gets a chunk never changes the chunk's results)
            for eng in list(alive):
                try:
                    if self.faults is not None:
                        self.faults.check("fleet.replica")
                    while eng.wants_input():
                        if not pending:
                            if exhausted:
                                break
                            try:
                                item = next(feed_iter)
                            except StopIteration:
                                exhausted = True
                                break
                            # normalize EVERY item to a requeue-able
                            # payload first (positions pinned): if this
                            # replica dies mid-prefill, the chunk being
                            # admitted survives at the head of pending —
                            # fleet feeds run put=False, so re-shipping
                            # at admission was the contract already
                            pending.append(self._as_payload(item))
                        payload = pending[0]   # PEEK: a failed admit
                        #                        leaves it queued for the
                        #                        next surviving replica
                        run_with_watchdog(
                            lambda p=payload: eng.admit(p, 0), wd,
                            label=f"prefill[{eng.tag}]")
                        pending.popleft()
                    run_with_watchdog(lambda: eng.refill(refill_order), wd,
                                      label=f"refill[{eng.tag}]")
                except Exception as e:
                    self._retire(eng, alive, pending, e, recovery)
            live = [eng for eng in alive if eng.in_flight()]
            if not live:
                if exhausted and not pending:
                    return
                continue  # nothing in flight yet: pull more input
            # dispatch EVERY live replica's step before any harvest
            # readback: replica compute overlaps across chips while the
            # host walks the fleet
            for eng in live:
                try:
                    run_with_watchdog(eng.step_dispatch, wd,
                                      label=f"step[{eng.tag}]")
                except Exception as e:
                    self._retire(eng, alive, pending, e, recovery)
            for eng in live:
                if eng.retired:
                    continue
                try:
                    items = run_with_watchdog(eng.harvest, wd,
                                              label=f"harvest[{eng.tag}]")
                except Exception as e:
                    self._retire(eng, alive, pending, e, recovery)
                    continue
                yield from items
