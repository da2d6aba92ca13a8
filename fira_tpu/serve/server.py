"""Arrival-timed serving loop over the slot engine (docs/SERVING.md).

The drain drivers (decode/runner.py, parallel/fleet.py) hand the engine a
pre-packed corpus stream and measure commits/s on the drained batch. This
module is the ROADMAP-item-1 other half: a long-lived SERVER under
open-loop load, where requests arrive over time (serve/arrivals.py), the
scheduler refills slots from live arrivals, and the interesting numbers
are p50/p99 TTFT and end-to-end latency against offered rate — the
Orca/vLLM serving regime, not the batch-job regime.

One scheduler round (``ServeLoop._round``), round-robined over the
engine replicas exactly like parallel/fleet.py:

1. **poll arrivals** — every request whose arrival time has passed moves
   into the admission queue (bounded by ``cfg.serve_queue_cap``; an
   arrival that finds it full is SHED immediately — rejection recorded,
   never a hang). Request payloads are pre-assembled ahead of time by the
   async Feeder (one single-row ``make_batch`` task per request, split
   order), so admission never blocks on host assembly. With
   ``cfg.prefix_cache`` armed, an arrival byte-identical to a request
   already in flight (same worker-stamped content digest —
   decode/prefix_cache.py) COALESCES onto that leader instead of taking
   a queue slot: one decode, N output positions at the leader's harvest,
   each request keeping its own arrival/deadline/TTFT stamps. A shed
   follower detaches without killing the leader's seat; a shed leader
   hands its group to the oldest surviving follower (promotion).
2. **shed deadlines** — queued requests older than
   ``cfg.serve_deadline_steps`` step dispatches are shed (a request that
   exhausted its whole deadline without being seated cannot answer in
   time; seated requests always run to harvest and late completions are
   flagged, not killed).
3. **admit** — up to ``cfg.serve_prefill_budget`` prefill dispatches PER
   REPLICA: the head-of-queue request's bucket is flushed into one packed
   batch (up to ``test_batch_size`` same-bucket requests in arrival
   order, padded with invalid rows) and prefilled on the claiming
   replica. The budget is the latency-aware refill knob: every prefill
   dispatched here stalls the seated slots' next decode step, so a small
   budget bounds the stall seated requests pay per new admission and a
   large one trades their tail latency for admission throughput.
4. **refill / step / harvest** — the engine's own steppable pieces,
   unchanged: every live replica's step is dispatched before any harvest
   readback; harvested samples are cooked/written through the same
   position-keyed ordered writer as drain mode.

Equivalence contract (tests/test_serve.py): on a REPLAYED arrival trace
with no shedding, output file bytes are IDENTICAL to drain-mode decode —
per-sample beam math is batch-composition-invariant (every batched op is
row-wise; the contract decode/engine.py's bit-exactness tests pin), and
the writer keys by split position — and invariant to replica count,
harvest cadence, and feeder worker count, with zero post-warmup retraces
under the same declared (geometry x {prefill, step, insert})
program family: serve-mode batches reuse the drain packer's exact
geometries and batch size, so no new program ever compiles.

Clocks: ``wall`` (the bench — arrivals are paced in real time and idle
waits sleep) or ``virtual`` (replay — time advances by a fixed cost per
prefill/step dispatch and jumps across idle gaps), both observing
latencies only at dispatch/harvest boundaries, which is what the host
can honestly see.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from fira_tpu.analysis import sanitizer
from fira_tpu.config import FiraConfig
from fira_tpu.data import buckets as buckets_lib
from fira_tpu.data.dataset import FiraDataset
from fira_tpu.data.feeder import Feeder
from fira_tpu.decode import paging
from fira_tpu.decode.engine import SlotEngine
from fira_tpu.decode.runner import output_name, sample_emitter
from fira_tpu.decode.stream import OrderedStreamWriter
from fira_tpu.model.model import FiraModel
from fira_tpu.robust import faults as faults_lib
from fira_tpu.robust.watchdog import WatchdogTimeout, run_with_watchdog
from fira_tpu.serve import disagg as disagg_lib
from fira_tpu.utils import profiling

# serve_metrics snapshot cadence: the partial artifact refreshes every
# this many scheduler rounds (plus once at startup and once on abort),
# so a SIGKILL at any point leaves a recent, valid-JSON snapshot
SNAPSHOT_EVERY_ROUNDS = 16

# prefix-cache miss micro-batching window (rounds): with the cache ON,
# cache hits admit for free and drain the queue fast, so the misses left
# behind would otherwise dispatch as fragmentary prefill batches — the
# dispatches the cache exists to save. Once the cache is actually
# serving hits (repeated traffic; cold streams keep legacy admission),
# a partial miss group WAITS (returned to the queue head) until it
# fills, its head has waited this many step-dispatch rounds, or the
# claiming replica would otherwise idle — a bounded dynamic-batching
# delay, recorded honestly in the latency stamps. Cache off: never
# holds (byte-identical legacy admission).
MISS_HOLD_ROUNDS = 16


# --------------------------------------------------------------------------
# parse-time knob validation (CLI exit 2 — the serving twin of
# parallel.mesh.divisibility_errors / decode.paging.paging_errors)
# --------------------------------------------------------------------------

def serve_errors(cfg: FiraConfig, *, trace: bool = False) -> List[str]:
    """Named-knob serving admission check. ``trace``: an arrival-trace
    file was given (the offered-rate knob is then unused)."""
    errs: List[str] = []
    if cfg.serve_rate < 0:
        errs.append(f"serve_rate {cfg.serve_rate} must be >= 0 requests/s")
    elif not trace and cfg.serve_rate == 0:
        errs.append(
            "serve_rate must be > 0 requests/s when no arrival trace is "
            "given (the open-loop Poisson generator needs an offered rate)")
    slots, _reps = paging.resolved_slots(cfg)
    if not 1 <= cfg.serve_prefill_budget <= slots:
        errs.append(
            f"serve_prefill_budget {cfg.serve_prefill_budget} must be >= 1 "
            f"and <= the per-replica engine slots ({slots}): it caps "
            f"prefill dispatches interleaved between step dispatches, and "
            f"a budget past the slot count can never seat more rows")
    if cfg.serve_deadline_steps < 0:
        errs.append(
            f"serve_deadline_steps {cfg.serve_deadline_steps} must be 0 "
            f"(no deadline) or >= 1: a request cannot complete in less "
            f"than one step dispatch")
    if cfg.serve_queue_cap < 0:
        errs.append(
            f"serve_queue_cap {cfg.serve_queue_cap} must be 0 (unbounded) "
            f"or >= 1 queued request")
    return errs


# --------------------------------------------------------------------------
# clocks
# --------------------------------------------------------------------------

class VirtualClock:
    """Deterministic replay clock: a fixed cost per prefill/step dispatch,
    idle gaps jumped. Makes a replayed trace's scheduling — hence its
    latency records — a pure function of the trace and the knobs."""

    def __init__(self, *, step_cost_s: float = 1.0,
                 prefill_cost_s: float = 1.0):
        self.step_cost_s = float(step_cost_s)
        self.prefill_cost_s = float(prefill_cost_s)
        self._now = 0.0

    def now(self) -> float:
        return self._now

    def advance_to(self, t: float) -> None:
        self._now = max(self._now, float(t))

    def on_prefill(self) -> None:
        self._now += self.prefill_cost_s

    def on_step(self) -> None:
        self._now += self.step_cost_s


class WallClock:
    """Real time: arrivals are paced against the monotonic clock and an
    idle server sleeps until the next scheduled arrival (open loop — the
    generator never waits for the server, only the server for it)."""

    def __init__(self):
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def advance_to(self, t: float) -> None:
        dt = float(t) - self.now()
        if dt > 0:
            time.sleep(dt)

    def on_prefill(self) -> None:
        pass

    def on_step(self) -> None:
        pass


# --------------------------------------------------------------------------
# per-request metering
# --------------------------------------------------------------------------

@dataclasses.dataclass
class RequestRecord:
    """One request's lifecycle timestamps (clock units — wall seconds or
    virtual units; every stamp is observed at a dispatch/harvest boundary,
    the only place the host honestly sees device progress)."""

    position: int            # split-local sample position
    arrival_t: float         # scheduled (open-loop) arrival time
    status: str = "pending"  # queued|staged|seated|done|shed_queue_full|
                             # shed_deadline|shed_error
    arrival_round: int = -1  # step-dispatch counter at arrival (deadline base)
    admit_t: float = math.nan       # prefill dispatched (chunk staged)
    seat_t: float = math.nan        # inserted into a slot
    seat_round: int = -1            # step-dispatch counter at seating: with
                                    # arrival_round/done_round it links the
                                    # request to the serve.round spans it
                                    # lived through (utils/profiling.py)
    first_step_t: float = math.nan  # end of its first step dispatch's
                                    # harvest phase — the TTFT stamp
    done_t: float = math.nan        # harvested (all beams settled)
    done_round: int = -1
    deadline_missed: bool = False   # completed, but past its deadline
    # poison-quarantine / retirement accounting (docs/FAULTS.md)
    error: Optional[str] = None     # recorded failure when shed_error
    retries: int = 0                # assembly/admission/prefill retries paid
    requeues: int = 0               # times re-queued off a retired replica
    # in-flight dedup (docs/DECODE_ENGINE.md "Prefix cache & dedup"): set
    # when this request coalesced onto a byte-identical leader's seat —
    # it is delivered by fan-out at the leader's harvest, keeping its OWN
    # arrival/deadline/TTFT stamps (None for leaders and cache-off runs)
    coalesced_into: Optional[int] = None
    # raw-diff ingest lifecycle stamps (docs/INGEST.md): per-stage
    # worker-side seconds (lex_s/parse_s/assemble_s), token count, the
    # deterministic-truncation record, the extraction-degradation reason,
    # and OOV fallback counts — stamped by ingest.service on the payload
    # (``_ingest``) and copied here at arrival. None on corpus-graph
    # requests, which never ran ingest.
    ingest: Optional[Dict] = None
    # disaggregated prefill-tier lifecycle stamps (docs/SERVING.md
    # "Disaggregated tiers"): wall seconds from first tier sighting to
    # pool submission (prefill_queue_s), submission to checksum-verified
    # delivery into the decode tier's caches (transport_s — the full
    # tier round trip, worker compute included), and the delivered
    # artifact's host footprint. None whenever serve_tiers=off, so
    # tier-less records stay byte-stable.
    prefill_queue_s: Optional[float] = None
    transport_s: Optional[float] = None
    artifact_bytes: Optional[int] = None

    @property
    def queue_wait_s(self) -> float:
        return self.seat_t - self.arrival_t

    @property
    def ttft_s(self) -> float:
        return self.first_step_t - self.arrival_t

    @property
    def e2e_s(self) -> float:
        return self.done_t - self.arrival_t


def _pct(values: List[float], q: float) -> Optional[float]:
    return round(float(np.percentile(np.asarray(values), q)), 6) \
        if values else None


@dataclasses.dataclass
class ServeStats:
    """Aggregate serving accounting: per-request records plus the
    scheduler counters the knee curve and the A/B rows read."""

    records: List[RequestRecord]
    completions: List[int] = dataclasses.field(default_factory=list)
    rounds: int = 0
    admits: int = 0                 # prefill batches formed from arrivals
    max_admits_per_round: int = 0   # <= serve_prefill_budget x replicas
    peak_queue_depth: int = 0
    shed_queue_full: int = 0
    shed_deadline: int = 0
    # graceful degradation (docs/FAULTS.md): requests shed with a
    # recorded error (poison quarantine / lost replicas), replicas
    # retired mid-run, and requests requeued off retired replicas
    shed_error: int = 0
    retirements: List[Dict] = dataclasses.field(default_factory=list)
    requeues: int = 0
    # self-healing + health signals (robust/recovery.py; docs/FAULTS.md
    # "Recovery contracts") — recorded UNCONDITIONALLY, recovery armed or
    # not, like feed-stall: the ROADMAP item-3 scale-up/down control
    # signal. ``replicas_alive_over_time`` appends one entry per change
    # in the live-replica set ({"round", "alive", "queue_depth",
    # "deadline_pressure"}); ``heartbeats`` stamps each replica's
    # last-dispatch round and dispatch count per scheduler round;
    # ``respawns`` records each replacement that rejoined the rotation;
    # ``admission_paused_rounds`` counts all-replicas-lost rounds spent
    # waiting on a respawn instead of shedding the remainder; ``resumed``
    # counts positions restored from a prior run's journal + output
    # prefix by ``--resume`` (never re-served, never re-emitted twice)
    replicas_alive_over_time: List[Dict] = dataclasses.field(
        default_factory=list)
    respawns: List[Dict] = dataclasses.field(default_factory=list)
    heartbeats: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    admission_paused_rounds: int = 0
    resumed: int = 0
    # in-flight dedup accounting (cfg.prefix_cache): requests coalesced
    # onto a byte-identical leader's seat, how many fan-out groups
    # delivered, and the largest group (leader + followers)
    dedup_coalesced: int = 0
    dedup_groups: int = 0
    dedup_fanout_max: int = 0
    # the ingest twin of feed-stall (docs/INGEST.md): seconds the
    # scheduler blocked waiting for a request's payload to come off the
    # feeder workers at arrival time — for raw-diff serving this is
    # exactly the ingest pipeline failing to stay ahead of arrivals
    assembly_stall_s: float = 0.0
    # REAL elapsed seconds of the whole loop run (perf_counter), the
    # stall fraction's denominator — the scheduling clock may be
    # virtual, but the stall is wall time, so the ratio must be too
    wall_s: float = 0.0
    # ingest whole-diff result-cache meter (ingest/cache.py; raw-diff
    # serving only): a zero-arg callable returning the cache's summary
    # dict, bound by serve_diffs so the final summary reads the
    # END-of-run counters — None on corpus-graph serves and with
    # cfg.ingest_cache off
    ingest_cache: Optional[object] = None
    # (workers, effective pipeline depth) of the raw-diff ingest feeder
    # — serve_diffs scales depth with the worker count past the
    # configured feeder_depth, so the actually-applied bound is
    # recorded rather than silently diverging from the knob
    ingest_pipeline: Optional[tuple] = None
    # disaggregated prefill-tier meter (serve/disagg.TierStats; docs/
    # SERVING.md "Disaggregated tiers"): a zero-arg callable returning
    # the tier's summary dict, bound by serve_split so the final
    # summary reads END-of-run counters — None with serve_tiers=off, so
    # tier-less summaries stay byte-stable (the ingest_cache pattern)
    tiers: Optional[object] = None
    # per span name count/total_s/max_s and the compile counters, over
    # the spans that closed while this loop's stats lived (utils/
    # profiling.Phases): a round's composition — poll, admit, step
    # dispatch, harvest wait/read, emit — without a profiler. Wall
    # seconds, so honest but schedule-dependent, like the ingest stamps
    phases: profiling.Phases = dataclasses.field(
        default_factory=profiling.collect, repr=False, compare=False)

    def summary(self) -> Dict:
        done = [r for r in self.records if r.status == "done"]
        ttft = [r.ttft_s for r in done if not math.isnan(r.first_step_t)]
        e2e = [r.e2e_s for r in done]
        qw = [r.queue_wait_s for r in done]
        last_done = max((r.done_t for r in done), default=0.0)
        last_arr = max((r.arrival_t for r in self.records), default=0.0)
        n = len(self.records)
        return {
            "offered": n,
            "completed": len(done),
            # the harvest-order completion timeline (positions in the
            # order their beams settled) — recorded since PR 11 but only
            # serialized since the STATS-SCHEMA gate caught the drift
            "completion_order": list(self.completions),
            "shed_queue_full": self.shed_queue_full,
            "shed_deadline": self.shed_deadline,
            "shed_error": self.shed_error,
            "replica_retirements": len(self.retirements),
            "retired_replicas": [r["replica"] for r in self.retirements],
            "requeued_requests": self.requeues,
            "respawns": len(self.respawns),
            "respawned_replicas": [r["replica"] for r in self.respawns],
            "spare_attaches": sum(1 for r in self.respawns if r["spare"]),
            "replicas_alive_over_time": list(self.replicas_alive_over_time),
            # sorted: keys are inserted as replicas first dispatch, and
            # under real-clock retirement/respawn that order tracks wall
            # timing — identical request streams must serialize identical
            # metrics bytes (firacheck DET-TAINT)
            "heartbeats": {t: dict(h)
                           for t, h in sorted(self.heartbeats.items())},
            "admission_paused_rounds": self.admission_paused_rounds,
            "resumed": self.resumed,
            "request_retries": sum(r.retries for r in self.records),
            "deadline_missed": sum(r.deadline_missed for r in done),
            "dedup_coalesced": self.dedup_coalesced,
            "dedup_groups": self.dedup_groups,
            "dedup_fanout_max": self.dedup_fanout_max,
            "rounds": self.rounds,
            "admits": self.admits,
            "max_admits_per_round": self.max_admits_per_round,
            "peak_queue_depth": self.peak_queue_depth,
            "offered_rate_rps": round(n / last_arr, 4) if last_arr else None,
            "makespan_s": round(last_done, 6),
            "throughput_rps": round(len(done) / last_done, 4)
            if last_done else None,
            "p50_ttft_s": _pct(ttft, 50), "p99_ttft_s": _pct(ttft, 99),
            "p50_e2e_s": _pct(e2e, 50), "p99_e2e_s": _pct(e2e, 99),
            "mean_e2e_s": round(float(np.mean(e2e)), 6) if e2e else None,
            "p50_queue_wait_s": _pct(qw, 50), "p99_queue_wait_s": _pct(qw, 99),
            **self._ingest_summary(),
            **({"tiers": dict(self.tiers()
                              if callable(self.tiers) else self.tiers)}
               if self.tiers is not None else {}),
            "phases": self.phases.summary(),
        }

    def _ingest_summary(self) -> Dict:
        """Aggregate raw-diff ingest stamps (docs/INGEST.md) — present
        only when any request actually ran ingest, so corpus-graph serve
        summaries stay byte-stable (the worker-count determinism
        contract: ingest stage times and the assembly stall are real
        wall seconds, honest but schedule-dependent)."""
        ing = [r.ingest for r in self.records if r.ingest]
        if not ing:
            return {}
        stage = {s: [i[s] for i in ing if s in i]
                 for s in ("lex_s", "parse_s", "assemble_s")}
        totals = [sum(i.get(s, 0.0) for s in
                      ("lex_s", "parse_s", "assemble_s")) for i in ing]
        out = {"requests_ingested": len(ing),
               "truncated": sum(1 for i in ing if i.get("truncated")),
               "degraded": sum(1 for i in ing if i.get("degraded")),
               "oov_word_fallbacks": sum(int(i.get("oov_words", 0))
                                         for i in ing),
               "oov_ast_fallbacks": sum(int(i.get("oov_ast", 0))
                                        for i in ing),
               # the fast-path hit split (docs/INGEST.md "Fast path"):
               # whole-diff hits replayed the stored payload (the
               # `cached` stamp); memo hits/misses are hunk-level AST
               # reuse INSIDE whole-diff misses — the partial-hit meter
               "cache_hits": sum(1 for i in ing if i.get("cached")),
               "memo_hits": sum(int(i.get("memo_hits", 0)) for i in ing),
               "memo_misses": sum(int(i.get("memo_misses", 0))
                                  for i in ing)}
        if self.ingest_cache is not None:
            out["cache"] = dict(self.ingest_cache()
                                if callable(self.ingest_cache)
                                else self.ingest_cache)
        if self.ingest_pipeline is not None:
            out["workers"], out["pipeline_depth"] = self.ingest_pipeline
        for s, vals in stage.items():
            out[f"mean_{s}"] = (round(float(np.mean(vals)), 9)
                                if vals else None)
        out["p50_total_s"] = _pct(totals, 50)
        out["p99_total_s"] = _pct(totals, 99)
        # the ingest twin of feed-stall: seconds the scheduler blocked at
        # arrival waiting for a payload still on the ingest workers, and
        # that stall as a fraction of the run's REAL wall time (both
        # sides perf_counter seconds — a virtual-clock makespan would be
        # a dimensionally meaningless denominator)
        out["stall_s"] = round(self.assembly_stall_s, 6)
        out["stall_frac"] = (round(self.assembly_stall_s / self.wall_s, 4)
                             if self.wall_s else None)
        return {"ingest": out}


@dataclasses.dataclass
class _Queued:
    record: RequestRecord
    host: Dict      # the request's single-row assembled batch
    bucket: int     # decode-table index (0 when unbucketed)
    digest: Optional[str] = None  # content digest (cfg.prefix_cache;
    #                               worker-stamped in _request_tasks)


# --------------------------------------------------------------------------
# the serving loop
# --------------------------------------------------------------------------

class ServeLoop:
    """Drives N engine replicas under arrival-timed admission. ``emit`` /
    ``shed`` are callbacks into the output layer (the driver below wires
    them to the ordered writer)."""

    def __init__(self, engines: Sequence[SlotEngine], cfg: FiraConfig, *,
                 arrival_times: np.ndarray, feed, table, assignment,
                 templates: Dict[int, Dict], clock, emit, shed,
                 refill_order: str = "fifo", faults=None, snapshot=None,
                 positions=None, journal=None, recovery=None, tier=None):
        self.engines = list(engines)
        self.cfg = cfg
        self.clock = clock
        self.emit = emit
        self.shed_cb = shed
        self.refill_order = refill_order
        self._table = table
        self._assignment = assignment
        self._templates = templates
        self._bs = int(cfg.test_batch_size)
        self._budget = max(1, int(cfg.serve_prefill_budget))
        self._deadline = max(0, int(cfg.serve_deadline_steps))
        self._cap = max(0, int(cfg.serve_queue_cap))
        # graceful degradation knobs (docs/FAULTS.md): the poison-request
        # retry budget, the per-dispatch wall-clock watchdog (0 = off),
        # the armed fault injector (None = off, zero overhead), and the
        # partial-metrics snapshot hook (crash contract)
        self._retries = max(0, int(cfg.robust_retries))
        self._watchdog = float(cfg.dispatch_watchdog_s)
        self._faults = faults
        self._snapshot = snapshot
        self._times = np.asarray(arrival_times, dtype=np.float64)
        self._feed_iter = iter(feed)
        self._arr_idx = 0
        self._rr = 0   # admission round-robin start (load balance)
        self._queue: "collections.deque[_Queued]" = collections.deque()
        # fleet-GLOBAL in-flight dedup (cfg.prefix_cache): digest ->
        # leader position for every non-final enqueued request, the
        # reverse map for cleanup, leader position -> coalesced follower
        # entries awaiting fan-out delivery, and followers promoted to
        # leader when their leader shed (drained into the queue outside
        # any deque walk — _drain_promotions)
        self._dedup_on = bool(cfg.prefix_cache)
        self._leaders: Dict[str, int] = {}
        self._leader_digest: Dict[int, str] = {}
        self._followers: Dict[int, List[_Queued]] = {}
        self._promoted: List[_Queued] = []
        # single-row payloads of every taken-but-unfinished request, by
        # position: the requeue source when a replica retires mid-flight
        self._payloads: Dict[int, _Queued] = {}
        self._awaiting_first_step: List[RequestRecord] = []
        self._final = 0
        # output position per arrival-stream request: identity normally;
        # a ``--resume`` run serves the not-yet-done SUFFIX of a prior
        # run's positions (robust/recovery.py), so positions are sparse
        # original indices and every position-keyed lookup goes through
        # ``_rec_by_pos`` instead of indexing the records list
        pos_arr = (np.asarray(positions, dtype=np.int64)
                   if positions is not None
                   else np.arange(len(self._times), dtype=np.int64))
        self.stats = ServeStats(records=[
            RequestRecord(position=int(p), arrival_t=float(t))
            for p, t in zip(pos_arr, self._times)])
        self._rec_by_pos: Dict[int, RequestRecord] = {
            r.position: r for r in self.stats.records}
        # self-healing + health machinery (docs/FAULTS.md "Recovery
        # contracts"): the write-ahead request journal (None = off), the
        # respawn policy (None = PR-9 retire-and-degrade), and the
        # always-on alive/heartbeat record (satellite of ROADMAP item 3)
        self._journal = journal
        self._recovery = recovery
        # disaggregated prefill tier (serve/disagg.PrefillTier, None =
        # in-process serve): while alive it OWNS every miss's prefill —
        # the admission walk holds tier-held misses queued until their
        # artifacts land in the replicas' caches and they admit as hits,
        # so the decode tier never dispatches a prefill program
        self._tier = tier
        self._shed_log: List[Dict] = []   # round-buffered shed WAL records
        self._alive_changed()

    # --- pieces ---------------------------------------------------------

    def _bucket_of(self, i: int, item) -> int:
        """A request's decode bucket: the split-wide assignment array for
        corpus-graph requests, the worker-stamped ``_bucket`` host field
        for raw-diff ingest requests (assigned per request by measured
        extents — ingest.service), 0 when unbucketed."""
        if self._assignment is not None:
            return int(self._assignment[i])
        if item.host is not None and "_bucket" in item.host:
            return int(item.host["_bucket"])
        return 0

    def _poll_arrivals(self, now: float) -> None:
        """Move every due request into the admission queue. An arrival is
        shed on the spot when the bounded queue is full, when its payload
        arrived POISONED (the feeder's per-task error channel: assembly
        failed even after its worker-side retries — recorded, never a
        re-raise), or when the serve.admit fault site rejects it past the
        retry budget."""
        while self._arr_idx < len(self._times) \
                and self._times[self._arr_idx] <= now:
            item = next(self._feed_iter)   # pre-assembled, split order
            i = self._arr_idx
            rec = self.stats.records[i]
            rec.arrival_round = self.stats.rounds
            rec.retries += int(item.retries)  # firacheck: allow[HOST-SYNC] FedBatch.retries is a host int counter stamped by the feeder worker; no device value exists here
            if item.host is not None:
                rec.ingest = item.host.get("_ingest")
            self.stats.assembly_stall_s += float(item.stall_s)  # firacheck: allow[HOST-SYNC] FedBatch.stall_s is a host perf_counter float stamped by the feeder; no device value exists here
            digest = None
            if self._dedup_on and item.host is not None:
                dl = item.host.get("_digests")
                digest = dl[0] if dl else None
            if item.error is not None:
                # poison-request quarantine: the request's assembly raised
                # (and its feeder-side retries were spent) — shed with the
                # error recorded; its output position holds an empty line
                rec.error = str(item.error)
                self._shed(rec, "shed_error")
            elif digest is not None and digest in self._leaders:
                # in-flight dedup: a byte-identical request is already
                # queued/staged/seated — COALESCE onto that leader's seat
                # instead of taking a queue slot. A coalesced request
                # consumes no seat capacity, but its payload is real host
                # memory pinned until the leader harvests, so the queue
                # cap still bounds each fan-out GROUP: a retry storm of
                # one hot digest sheds past-cap followers exactly like
                # any other flood (backpressure survives dedup).
                # Delivered by fan-out at the leader's harvest; keeps
                # its OWN arrival/deadline/TTFT stamps.
                leader = self._leaders[digest]
                if self._cap and len(self._followers.get(leader, [])) \
                        >= self._cap:
                    self._shed(rec, "shed_queue_full")
                else:
                    lrec = self._rec_by_pos[leader]
                    e = _Queued(rec, item.host, self._bucket_of(i, item),
                                digest=digest)
                    self._followers.setdefault(leader, []).append(e)
                    rec.coalesced_into = leader
                    rec.status = "queued"
                    if lrec.status in ("staged", "seated"):
                        # the leader's prefill/seat already happened: the
                        # follower inherits those milestones at coalesce
                        # time
                        rec.admit_t = now
                        rec.status = "staged"
                    if lrec.status == "seated":
                        rec.seat_t = now
                        rec.seat_round = self.stats.rounds
                        rec.status = "seated"
                        self._awaiting_first_step.append(rec)
                    self.stats.dedup_coalesced += 1
            elif self._cap and len(self._queue) >= self._cap:
                self._shed(rec, "shed_queue_full")
            elif not self._admit_gate(rec):
                pass  # serve.admit fault past the retry budget: shed inside
            else:
                rec.status = "queued"
                if digest is not None:
                    self._leaders[digest] = rec.position
                    self._leader_digest[rec.position] = digest
                self._queue.append(_Queued(rec, item.host,
                                           self._bucket_of(i, item),
                                           digest=digest))
            self._arr_idx += 1
        self.stats.peak_queue_depth = max(self.stats.peak_queue_depth,
                                          len(self._queue))

    def _backoff(self, attempt: int) -> None:
        """Quarantine retry backoff: real sleep on the wall clock only —
        a virtual-clock replay is deterministic by construction (every
        retry is a fresh keyed draw, not a time-dependent one), so
        burning real wall time per retried fault would only slow the
        replay down."""
        if isinstance(self.clock, WallClock):
            time.sleep(faults_lib.backoff_s(attempt))

    def _admit_gate(self, rec: RequestRecord) -> bool:
        """The serve.admit fault site, with the quarantine retry policy:
        every attempt is a fresh deterministic draw, so a transient
        admission fault is absorbed by the retry budget and a persistent
        one sheds the request with its error recorded."""
        if self._faults is None or not self._faults.armed("serve.admit"):
            return True
        attempt = 0
        while True:
            try:
                self._faults.check("serve.admit")
                return True
            except Exception as e:
                if attempt < self._retries:
                    attempt += 1
                    rec.retries += 1
                    self._backoff(attempt)
                    continue
                rec.error = (f"admission rejected after {attempt + 1} "
                             f"attempt(s): {e}")
                self._shed(rec, "shed_error")
                return False

    def _shed(self, rec: RequestRecord, status: str) -> None:
        rec.status = status
        if status == "shed_queue_full":
            self.stats.shed_queue_full += 1
        elif status == "shed_deadline":
            self.stats.shed_deadline += 1
        else:
            self.stats.shed_error += 1
        self._final += 1
        self._payloads.pop(rec.position, None)
        # a shed FOLLOWER detaches from its leader's fan-out group — the
        # leader's seat is untouched (the dedup/shed contract)
        if rec.coalesced_into is not None:
            fl = self._followers.get(rec.coalesced_into)
            if fl:
                self._followers[rec.coalesced_into] = [
                    e for e in fl if e.record is not rec]
        # a shed LEADER hands its group to the oldest surviving follower:
        # the promotee re-enters the queue (via _drain_promotions — never
        # mid-walk of the deque) with its OWN arrival/deadline stamps and
        # its own byte-identical payload, and the remaining followers
        # re-point at it
        d = self._leader_digest.pop(rec.position, None)
        if d is not None:
            self._leaders.pop(d, None)
            fl = self._followers.pop(rec.position, [])
            if fl:
                head, rest = fl[0], fl[1:]
                head.record.coalesced_into = None
                self._leaders[d] = head.record.position
                self._leader_digest[head.record.position] = d
                for e in rest:
                    e.record.coalesced_into = head.record.position
                if rest:
                    self._followers[head.record.position] = rest
                self._promoted.append(head)
        self.shed_cb(rec)
        # terminal WAL record AFTER the writer took the empty line (so
        # the record never claims a position whose line missed the
        # disk); buffered and flushed once per scheduler round like the
        # admit/done batches — one fsync per round, not per shed, which
        # matters exactly on the mass-shed collapse path
        if self._journal is not None:
            self._shed_log.append({"kind": "shed", "pos": rec.position,
                                   "status": status, "error": rec.error})

    def _drain_promotions(self) -> None:
        """Enqueue followers promoted to leader by a leader shed. Runs
        OUTSIDE any queue walk (a shed mid-walk must not mutate the deque
        being iterated). A promotee whose own deadline already lapsed is
        shed here — which may promote the next follower in turn, so the
        loop runs until the promotion chain settles."""
        while self._promoted:
            e = self._promoted.pop(0)
            rec = e.record
            if self._deadline and (self.stats.rounds - rec.arrival_round
                                   >= self._deadline):
                self._shed(rec, "shed_deadline")
                continue
            rec.status = "queued"
            rec.admit_t = rec.seat_t = rec.first_step_t = math.nan
            rec.seat_round = -1
            self._queue.append(e)

    def _shed_deadlines(self) -> None:
        """Drop queued requests whose whole deadline elapsed un-seated.
        Dedup followers mirror queued semantics until their leader seats:
        a follower past its OWN deadline detaches (the leader's seat is
        never killed); once the leader is seated the group rides to
        harvest with late completions flagged per follower, exactly like
        any seated request."""
        if not self._deadline:
            return
        keep: "collections.deque[_Queued]" = collections.deque()
        for e in self._queue:
            if self.stats.rounds - e.record.arrival_round >= self._deadline:
                self._shed(e.record, "shed_deadline")
            else:
                keep.append(e)
        self._queue = keep
        self._drain_promotions()
        for leader, fl in list(self._followers.items()):
            lrec = self._rec_by_pos[leader]
            if lrec.status not in ("queued", "staged"):
                continue
            for e in list(fl):
                if (self.stats.rounds - e.record.arrival_round
                        >= self._deadline):
                    self._shed(e.record, "shed_deadline")
        self._drain_promotions()

    def _take_chunk(self, eng: SlotEngine):
        """Same-bucket requests off the queue head, arrival order
        preserved for taken AND left-behind; returns (bucket, groups).
        Cache off: one group of up to ``test_batch_size`` requests — the
        historical take. Cache on: the walk PARTITIONS into a hit group
        (artifacts in ``eng``'s prefix cache — admitted from cache, no
        prefill dispatch) and a miss group, each packing up to a full
        batch: hits don't consume miss-batch rows, so repeated traffic
        cannot fragment the misses' prefill batches (which is where the
        dispatch saving lives). Order within each group stays arrival
        order, and output is position-keyed, so bytes are unchanged."""
        bucket = self._queue[0].bucket
        hits: List[_Queued] = []
        misses: List[_Queued] = []
        rest: "collections.deque[_Queued]" = collections.deque()
        probe = self._dedup_on
        while self._queue and len(hits) < self._bs \
                and len(misses) < self._bs:
            e = self._queue.popleft()
            if e.bucket != bucket:
                rest.append(e)
                continue
            if probe and eng.cache_contains(e.digest):
                hits.append(e)
            elif self._tier is not None and self._tier.holds(e.digest):
                # the prefill tier owns this miss (docs/SERVING.md
                # "Disaggregated tiers"): hold it queued — NEVER a
                # prefill dispatch on this decode replica — until its
                # shipped artifacts land and it re-walks as a hit. The
                # tier going dead or giving the digest up flips holds()
                # false and the next walk takes the in-process path.
                rest.append(e)
            else:
                misses.append(e)
        held: List[_Queued] = []
        if probe and 0 < len(misses) < self._bs:
            # fragmentary miss group: hold it (back to the true queue
            # head, ahead of everything the walk skipped) so it packs
            # with later misses instead of wasting a prefill dispatch —
            # bounded by MISS_HOLD_ROUNDS on the group head's wait and
            # by replica idleness (a group never waits while the
            # claiming replica has nothing else to do, and rounds only
            # advance while work is in flight, so the hold can never
            # deadlock)
            busy = eng.in_flight() > 0 or eng.staged_rows > 0
            warm = bool(hits) or eng.stats.cache_hits > 0
            head_wait = self.stats.rounds - min(
                e.record.arrival_round for e in misses)
            if busy and warm and head_wait < MISS_HOLD_ROUNDS:
                held, misses = misses, []
        rest.extend(self._queue)
        self._queue = rest
        for e in reversed(held):
            self._queue.appendleft(e)
        for e in hits + misses:
            # keep the single-row payload until the request finishes: the
            # requeue source if the replica serving it retires mid-flight
            self._payloads[e.record.position] = e
        return bucket, [g for g in (hits, misses) if g]

    def _form_batch(self, bucket: int, take: List[_Queued]) -> Dict:
        """Pack the taken requests' pre-assembled rows into one batch at
        the bucket's geometry (pad rows from the cached all-pad template —
        exactly a drain-mode packed batch with serve-chosen membership)."""
        tmpl = self._templates[bucket]
        batch = {k: np.array(v) for k, v in tmpl.items()}
        positions = np.full(self._bs, -1, dtype=np.int64)
        for j, e in enumerate(take):
            for k in batch:
                batch[k][j] = e.host[k][0]
            positions[j] = e.record.position
        batch["_positions"] = positions
        if self._table is not None:
            batch["_tag"] = buckets_lib.geom_tag(self._table[bucket])
        if any(e.host is not None and "_var" in e.host for e in take):
            # per-request anonymization maps (raw-diff ingest requests,
            # docs/INGEST.md): ride the packed batch as a host-only
            # column so the emitter can de-anonymize each row's output
            vm = [(e.host.get("_var") or [None])[0] if e.host else None
                  for e in take]
            batch["_var"] = vm + [None] * (self._bs - len(take))
        if self._dedup_on:
            # forward the worker-stamped content digests so the engine's
            # cache lookup never re-hashes (host-only field, wire-stripped)
            batch["_digests"] = ([e.digest for e in take]
                                 + [None] * (self._bs - len(take)))
        return batch

    def _prefill_quarantined(self, eng: SlotEngine, batch: Dict,
                             take: List[_Queued]) -> Optional[bool]:
        """One prefill dispatch under the quarantine policy: a RAISE is a
        request problem — retried with backoff (every attempt a fresh
        fault draw), then the whole chunk shed with its error recorded; a
        WATCHDOG EXPIRY is a replica problem — the replica retires and
        the chunk requeues. Returns True (staged), False (chunk shed), or
        None (replica retired — the caller moves on)."""
        attempt = 0
        while True:
            try:
                run_with_watchdog(lambda: eng.admit(batch, 0),
                                  self._watchdog,
                                  label=f"serve_prefill[{eng.tag or 'r0'}]")
                return True
            except WatchdogTimeout as e:
                self._retire_replica(eng, e, requeue=take)
                return None
            except Exception as e:
                if attempt < self._retries:
                    attempt += 1
                    for el in take:
                        el.record.retries += 1
                    self._backoff(attempt)
                    continue
                for el in take:
                    el.record.error = (f"prefill failed after "
                                       f"{attempt + 1} attempt(s): {e}")
                    self._shed(el.record, "shed_error")
                return False

    def _retire_replica(self, eng: SlotEngine, err: BaseException, *,
                        requeue: Optional[List[_Queued]] = None) -> None:
        """Retire one replica (dispatch raised or blew the watchdog):
        drop it from the rotation and push every request it still owed —
        seated, staged, plus the caller's un-staged ``requeue`` chunk —
        back to the FRONT of the admission queue in position order (they
        arrived earliest). Their lifecycle stamps reset to 'queued'; the
        deadline clock does NOT reset (arrival_round stands), so a
        request that cannot be re-served in time is recorded-shed, never
        silently dropped. Stamps, counts, and the retired replica are
        machine-recorded in ServeStats."""
        if eng not in self.engines:
            return
        owed = set(eng.pending_positions())
        eng.retire()
        self.engines.remove(eng)
        self.stats.retirements.append(
            {"replica": eng.tag or "r0",
             "error": f"{type(err).__name__}: {err}"})
        # health record + respawn clock (robust/recovery.py): the
        # heartbeat goes cold, the alive trace steps down, and — with
        # recovery armed — the lineage's round-gated backoff starts
        hb = self.stats.heartbeats.get(eng.tag or "r0")
        if hb is not None:
            hb["alive"] = False
        if self._recovery is not None:
            self._recovery.note_retirement(
                eng, self.stats.rounds,
                error=f"{type(err).__name__}: {err}")
        self._alive_changed()
        entries: List[_Queued] = []
        seen: set = set()
        for pos in owed:
            e = self._payloads.get(pos)
            if e is not None and pos not in seen:
                seen.add(pos)
                entries.append(e)
        for e in (requeue or []):
            if e.record.position not in seen:
                seen.add(e.record.position)
                entries.append(e)
        entries.sort(key=lambda e: e.record.position)
        for e in entries:
            rec = e.record
            rec.requeues += 1
            rec.status = "queued"
            rec.admit_t = rec.seat_t = rec.first_step_t = math.nan
            rec.seat_round = -1
            # a requeued leader drags its coalesced followers back to the
            # queued milestone with it (they stay attached — re-admission
            # payloads survive dedup; the deadline clocks do not reset)
            for f in self._followers.get(rec.position, []):
                f.record.status = "queued"
                f.record.admit_t = f.record.seat_t = math.nan
                f.record.first_step_t = math.nan
                f.record.seat_round = -1
        self.stats.requeues += len(entries)
        for e in reversed(entries):
            self._queue.appendleft(e)
        self._awaiting_first_step = [
            r for r in self._awaiting_first_step if r.status == "seated"]
        self._rr = self._rr % len(self.engines) if self.engines else 0

    def _shed_all_remaining(self, reason: str) -> None:
        """No live replicas: every request not yet final is shed with the
        reason recorded — the run terminates with a position-complete
        output file and an honest metrics artifact, never a hang."""
        while self._queue or self._promoted:
            e = (self._promoted.pop(0) if self._promoted
                 else self._queue.popleft())
            e.record.error = e.record.error or reason
            self._shed(e.record, "shed_error")
        # safety net: followers whose leader is neither queued nor
        # promoted (the shed->promote chain above normally drains them)
        for _leader, fl in list(self._followers.items()):
            for e in list(fl):
                if e.record.status not in ("done", "shed_queue_full",
                                           "shed_deadline", "shed_error"):
                    e.record.error = e.record.error or reason
                    self._shed(e.record, "shed_error")
        self._followers.clear()
        while self._arr_idx < len(self._times):
            item = next(self._feed_iter)
            rec = self.stats.records[self._arr_idx]
            rec.retries += int(item.retries)  # firacheck: allow[HOST-SYNC] FedBatch.retries is a host int counter stamped by the feeder worker; no device value exists here
            if item.host is not None:
                rec.ingest = item.host.get("_ingest")
            rec.error = rec.error or (str(item.error) if item.error
                                      else reason)
            self._shed(rec, "shed_error")
            self._arr_idx += 1

    def _admit(self) -> None:
        """Budgeted admission, replica round-robin: at most
        ``serve_prefill_budget`` prefill dispatches per replica between
        step dispatches. The starting replica ROTATES per round so a
        lightly loaded fleet spreads admissions instead of feeding
        replica 0 first every time (which replica serves a request never
        changes its result — the fleet's output-invariance contract —
        so rotation is purely a load-balance choice, and a
        deterministic one)."""
        admitted = 0
        admitted_pos: List[int] = []
        order = (self.engines[self._rr:] + self.engines[:self._rr])
        self._rr = (self._rr + 1) % len(self.engines) if self.engines else 0
        for eng in order:
            if eng not in self.engines:
                continue  # retired earlier in this very round
            n = 0
            retired = False
            while n < self._budget and self._queue and eng.wants_input():
                # hit/miss partition (cfg.prefix_cache): requests whose
                # prefill artifacts sit in THIS replica's cache form
                # their own chunk, admitted from cache with no prefill
                # dispatch and no budget charge (that is the latency win
                # — a cached admission never stalls the seated slots'
                # next step); misses pack a normal prefilled chunk.
                bucket, groups = self._take_chunk(eng)
                if not groups:
                    break  # a held miss group: it dispatches within
                    #        MISS_HOLD_ROUNDS once rounds advance
                for gi, group in enumerate(groups):
                    before = eng.stats.prefills
                    with profiling.span("serve.form_batch"):
                        batch = self._form_batch(bucket, group)
                    staged = self._prefill_quarantined(eng, batch, group)
                    if staged is None:
                        retired = True
                        # the replica died dispatching THIS group (it was
                        # requeued by _retire_replica); any group taken
                        # off the queue but not yet dispatched must go
                        # back too, or its requests are stranded in
                        # 'queued' forever and the loop stalls
                        for g in reversed(groups[gi + 1:]):
                            for e in reversed(g):
                                self._queue.appendleft(e)
                        break
                    if not staged:
                        # chunk shed; promotions from shed leaders re-enter
                        self._drain_promotions()
                        continue
                    # the virtual clock and the latency budget charge per
                    # PREFILL DISPATCH: a cache-served or fully-coalesced
                    # admission dispatched nothing and costs neither
                    if eng.stats.prefills > before:
                        self.clock.on_prefill()
                        n += 1
                    t = self.clock.now()
                    for e in group:
                        e.record.admit_t = t
                        e.record.status = "staged"
                        admitted_pos.append(e.record.position)
                        for f in self._followers.get(e.record.position, []):
                            f.record.admit_t = t
                            f.record.status = "staged"
                            admitted_pos.append(f.record.position)
                if retired:
                    break
            admitted += n
            if eng not in self.engines:
                continue
            try:
                run_with_watchdog(lambda: eng.refill(self.refill_order),
                                  self._watchdog,
                                  label=f"serve_refill[{eng.tag or 'r0'}]")
            except Exception as e:
                self._retire_replica(eng, e)
        if self._journal is not None and admitted_pos:
            # admit WAL records: one per request, one fsync per round.
            # Resume correctness rides on the BEGIN record (stream
            # identity) + the writer crash pair; these per-request
            # records are the crash-surviving outcome/post-mortem log —
            # "never admitted" vs "admitted but unfinished" for capacity
            # analysis, shed statuses+errors that would otherwise exist
            # only in the metrics snapshot, and the progress probe the
            # kill legs poll (scripts/chaos_bench.py)
            with profiling.span("serve.journal"):
                self._journal.admit(admitted_pos)
        self.stats.admits += admitted
        self.stats.max_admits_per_round = max(
            self.stats.max_admits_per_round, admitted)
        t = self.clock.now()
        for eng in self.engines:
            for pid in eng.in_flight_positions():
                rec = self._rec_by_pos[pid]
                if math.isnan(rec.seat_t):
                    rec.seat_t = t
                    rec.seat_round = self.stats.rounds
                    rec.status = "seated"
                    self._awaiting_first_step.append(rec)
                    # a seated leader seats its whole fan-out group: each
                    # follower keeps its own stamps but reaches the seat
                    # milestone at the same dispatch boundary
                    for f in self._followers.get(pid, []):
                        if math.isnan(f.record.seat_t):
                            f.record.seat_t = t
                            f.record.seat_round = self.stats.rounds
                            f.record.status = "seated"
                            self._awaiting_first_step.append(f.record)

    # --- health signals + self-healing (robust/recovery.py) -------------

    def _deadline_pressure(self) -> float:
        """Fraction of queued requests past HALF their deadline — the
        scale-up urgency gauge the alive trace records (0.0 with no
        deadline armed or an empty queue)."""
        if not self._deadline or not self._queue:
            return 0.0
        tight = sum(1 for e in self._queue
                    if self.stats.rounds - e.record.arrival_round
                    >= self._deadline / 2)
        return round(tight / len(self._queue), 4)

    def _alive_changed(self) -> None:
        """Append one alive-trace entry (the ROADMAP item-3 control
        signal): called at start, on every retirement, and on every
        respawn — the entries ARE the capacity-restored-over-time curve
        the recovery bench reads."""
        self.stats.replicas_alive_over_time.append({
            "round": self.stats.rounds,
            "alive": len(self.engines),
            "queue_depth": len(self._queue),
            "deadline_pressure": self._deadline_pressure(),
        })

    def _stamp_heartbeats(self) -> None:
        """Per-replica per-round heartbeat: last-dispatch round + total
        dispatches (a retired replica's stamp goes cold and its
        last-dispatch AGE grows — the health signal respawn decisions
        and post-mortems read). Recorded unconditionally, recovery armed
        or not."""
        for eng in self.engines:
            hb = self.stats.heartbeats.setdefault(
                eng.tag or "r0",
                {"last_dispatch_round": -1, "rounds": 0, "alive": True})
            hb["last_dispatch_round"] = self.stats.rounds
            hb["rounds"] += 1
            hb["alive"] = True

    def _flush_shed_log(self) -> None:
        """Flush the round's buffered shed WAL records (one fsync for
        the whole batch — see _shed)."""
        if self._journal is not None and self._shed_log:
            with profiling.span("serve.journal"):
                self._journal.append_many(self._shed_log)
            self._shed_log = []

    def _heal(self) -> None:
        """Respawn every dead lineage whose backoff elapsed and whose
        budget is not exhausted: the replacement (warm spare or fresh
        build — EngineFleet.replace_slot) attaches to the shared
        admission queue and starts pulling next round. Machine-recorded
        in ServeStats.respawns + the alive trace."""
        if self._recovery is None:
            return
        for slot in self._recovery.due(self.stats.rounds):
            attempt = slot.respawns + 1
            eng, from_spare = self._recovery.respawn(slot,
                                                     self.stats.rounds)
            if eng is None:
                continue   # builder failed: budget consumed, backoff
                #            restarted — retried or exhausted next rounds
            eng.begin_stream()
            self.engines.append(eng)
            self.stats.respawns.append({
                "replica": eng.tag or "r0", "origin": slot.origin,
                "round": self.stats.rounds, "attempt": attempt,
                "spare": from_spare})
            self._alive_changed()

    # --- the loop -------------------------------------------------------

    def run(self) -> ServeStats:
        """The whole loop under the ``serve.run`` root span;
        ``ServeStats.wall_s`` — REAL elapsed seconds, the stall fraction's
        denominator (wall over wall, PR 11 fourth-pass review), never the
        scheduling clock — IS the root's duration (utils/profiling.py owns
        the wall-clock reads)."""
        with profiling.span("serve.run") as whole:
            self._serve_rounds()
        self.stats.wall_s = whole.duration_s
        return self.stats

    def _serve_rounds(self) -> None:
        # each pass of the scheduler is one ``serve.round``: a pass that
        # dispatched holds a ``serve.step_dispatch``, an idle pass a
        # ``serve.idle_wait``. The round stays INLINE in this loop: a loop
        # body in a driver module is what firacheck scans for blocking
        # calls and device syncs (analysis/astutil.hot_spans)
        n = len(self._times)
        for eng in self.engines:
            # fresh host scheduling state per request stream (a no-op on
            # a just-constructed engine; required when a caller reuses a
            # warmed engine across serving runs — scripts/serve_bench.py)
            eng.begin_stream()
        if self._snapshot is not None:
            with profiling.span("serve.snapshot"):
                self._snapshot(self)   # a valid partial artifact exists
            #                            from the first moment (kill contract)
        while self._final < n:
            with profiling.span("serve.round", round=self.stats.rounds):
                self._heal()
                if not self.engines:
                    if (self._recovery is not None
                            and self._recovery.can_recover()):
                        # all replicas lost but respawn budget remains: PAUSE
                        # admission (nothing dispatches) while arrivals keep
                        # queuing and deadline clocks keep ticking at their
                        # TRUE rounds — the recorded queue-depth/deadline-
                        # pressure signal stays honest through the outage —
                        # and let the round clock tick so the respawn backoff
                        # elapses: a recoverable outage, not a shed-the-
                        # remainder collapse. The budget is finite, so this
                        # loop always terminates: either a replacement
                        # attaches or can_recover goes False.
                        self._poll_arrivals(self.clock.now())
                        self._shed_deadlines()
                        self._flush_shed_log()
                        self.stats.admission_paused_rounds += 1
                        if isinstance(self.clock, WallClock):
                            # wall outage: the respawn gate is wall-time
                            # (RecoveryManager.due) and rounds are STEP
                            # DISPATCHES — nothing dispatches, so the
                            # deadline clock must not inflate with spin
                            # iterations; just wait a beat
                            with profiling.span("serve.idle_wait"):
                                time.sleep(0.01)  # firacheck: allow[SCHED-BLOCK] bounded 10ms beat on the ALL-REPLICAS-LOST pause branch: nothing can dispatch, arrivals are polled each beat, and the alternative is a busy-spin (PR 12 review)
                        else:
                            # virtual replay: the round clock IS the backoff
                            # gate — tick it deterministically
                            self.clock.on_step()
                            self.stats.rounds += 1
                        continue
                    # every replica retired and no respawn budget left: shed
                    # the remainder with the reason recorded —
                    # position-complete output, no hang
                    last = (self.stats.retirements[-1]["error"]
                            if self.stats.retirements else "unknown")
                    self._shed_all_remaining(
                        f"no live replicas (all retired; last error: {last})")
                    self._flush_shed_log()
                    break
                with profiling.span("serve.poll"):
                    self._poll_arrivals(self.clock.now())
                if self._tier is not None:
                    # disaggregated prefill tier tick (serve/disagg.py):
                    # sweep dead workers, deliver checksum-verified
                    # artifacts into every replica's cache, submit fresh
                    # misses — pure host work before admission, so this
                    # round's walk can already seat freshly-landed hits
                    self._tier.service(self._queue, self.engines)
                self._shed_deadlines()
                with profiling.span("serve.admit"):
                    self._admit()
                live = [e for e in self.engines if e.in_flight()]
                if not live:
                    if self._queue or self._promoted \
                            or any(e.staged_rows for e in self.engines):
                        if self._tier is not None \
                                and not any(e.staged_rows
                                            for e in self.engines):
                            # nothing dispatchable and the queue is waiting
                            # on the prefill tier: block briefly on the
                            # worker pipes instead of busy-spinning
                            with profiling.span("serve.idle_wait"):
                                self._tier.idle_wait(0.05)
                        continue    # seats free up / budget admits next round
                    if self._arr_idx < n:
                        # idle: jump (virtual) / sleep (wall) to the next
                        # scheduled arrival — open loop, the generator never
                        # waits for us, only we for it
                        with profiling.span("serve.idle_wait"):
                            self.clock.advance_to(self._times[self._arr_idx])
                        continue
                    if self._final < n:   # pragma: no cover - loop invariant
                        # a retirement always requeues into self._queue, so
                        # final < n still implies queued/staged/arriving work
                        raise RuntimeError(
                            "serve loop stalled with requests unaccounted for")
                    break
                if self._dedup_on:
                    # tell each replica which of its seats serve a fan-out
                    # group (loop-level dedup keeps the followers up here) so
                    # the engine's shared-block high-water meter covers them
                    leaders = {p for p, fl in self._followers.items() if fl}
                    for eng in live:
                        eng.shared_positions = leaders
                with profiling.span("serve.step_dispatch"):
                    for eng in live:
                        try:
                            if self._faults is not None:
                                self._faults.check("fleet.replica")
                            run_with_watchdog(
                                eng.step_dispatch, self._watchdog,
                                label=f"serve_step[{eng.tag or 'r0'}]")
                        except Exception as e:
                            self._retire_replica(eng, e)
                self.clock.on_step()
                self.stats.rounds += 1
                self._stamp_heartbeats()
                items = []
                with profiling.span("serve.harvest"):
                    for eng in live:
                        if eng.retired:
                            continue
                        try:
                            items.extend(run_with_watchdog(
                                eng.harvest, self._watchdog,
                                label=f"serve_harvest[{eng.tag or 'r0'}]"))
                        except Exception as e:
                            self._retire_replica(eng, e)
                with profiling.span("serve.emit"):
                    t = self.clock.now()   # post-harvest: the honest reading
                    for rec in self._awaiting_first_step:
                        if rec.status == "seated":   # not requeued mid-round
                            rec.first_step_t = t
                    self._awaiting_first_step = []
                    done_now: List[int] = []
                    for it in items:
                        rec = self._rec_by_pos[it.position]
                        rec.done_t = t
                        rec.done_round = self.stats.rounds
                        rec.status = "done"
                        if self._deadline and (
                                rec.done_round - rec.arrival_round
                                > self._deadline):
                            rec.deadline_missed = True
                        self._final += 1
                        self._payloads.pop(it.position, None)
                        self.stats.completions.append(it.position)
                        done_now.append(it.position)
                        self.emit(it.position, it.host, it.row, it.tokens,
                                  it.probs)
                        # dedup fan-out delivery: the leader's settled beams
                        # are byte-identical to what every coalesced
                        # follower's own decode would have produced (same
                        # digest => same packed payload), so each follower
                        # emits them at its OWN output position with its OWN
                        # lifecycle stamps
                        d = self._leader_digest.pop(it.position, None)
                        if d is not None:
                            self._leaders.pop(d, None)
                        group = self._followers.pop(it.position, [])
                        if group:
                            self.stats.dedup_groups += 1
                            self.stats.dedup_fanout_max = max(
                                self.stats.dedup_fanout_max, 1 + len(group))
                        for f in group:
                            fr = f.record
                            if math.isnan(fr.first_step_t):
                                # coalesced after the leader's first step: its
                                # first observable progress IS this harvest
                                fr.first_step_t = t
                            fr.done_t = t
                            fr.done_round = self.stats.rounds
                            fr.status = "done"
                            if self._deadline and (
                                    fr.done_round - fr.arrival_round
                                    > self._deadline):
                                fr.deadline_missed = True
                            self._final += 1
                            self.stats.completions.append(fr.position)
                            done_now.append(fr.position)
                            self.emit(fr.position, f.host, 0, it.tokens,
                                      it.probs)
                if self._journal is not None and done_now:
                    # terminal WAL records AFTER the writer took the lines
                    # (line-buffered — on disk): one record per request, one
                    # fsync per harvest round
                    with profiling.span("serve.journal"):
                        self._journal.done(done_now)
                self._flush_shed_log()
                if (self._snapshot is not None
                        and self.stats.rounds % SNAPSHOT_EVERY_ROUNDS == 0):
                    with profiling.span("serve.snapshot"):
                        self._snapshot(self)
        self._flush_shed_log()   # sheds recorded after the last harvest


# --------------------------------------------------------------------------
# driver (the serving twin of decode.runner.run_test)
# --------------------------------------------------------------------------

def make_clock(clock: str, *, step_cost_s: float = 1.0,
               prefill_cost_s: float = 1.0):
    """The serve drivers' clock selector (serve_split and
    ingest.service.serve_diffs share it — one definition, no twin)."""
    if clock == "wall":
        return WallClock()
    if clock == "virtual":
        return VirtualClock(step_cost_s=step_cost_s,
                            prefill_cost_s=prefill_cost_s)
    raise ValueError(f"clock {clock!r} not in {{'wall', 'virtual'}}")


def build_engines(model, params, cfg: FiraConfig, *, engine=None,
                  engine_slots=None, guard=None, faults=None,
                  fleet_always: bool = False):
    """Engine/fleet construction shared by the serve drivers: returns
    (owner, engines, built) — ``built`` False when the caller passed a
    (presumably warm) ``engine`` whose prewarm must not rerun.
    ``fleet_always``: build an EngineFleet even at 1 replica — the
    respawn path (robust/recovery.py) needs the fleet's replace_slot /
    spare-pool surface, and a fleet-of-one is byte-identical to the bare
    engine."""
    if engine is not None:
        return engine, (getattr(engine, "engines", None) or [engine]), False
    n_rep = max(1, int(cfg.engine_replicas))
    if n_rep > 1 or fleet_always:
        from fira_tpu.parallel import fleet as fleet_lib

        owner = fleet_lib.EngineFleet(model, params, cfg, replicas=n_rep,
                                      slots=engine_slots, guard=guard,
                                      faults=faults)
        return owner, owner.engines, True
    owner = SlotEngine(model, params, cfg, slots=engine_slots,
                       guard=guard, faults=faults)
    return owner, [owner], True


def prepare_templates(owner, split, cfg: FiraConfig, table, *,
                      guard=None, prewarm: bool = True) -> Dict[int, Dict]:
    """Per-bucket all-pad templates (+ program-family prewarm when the
    driver built the engine itself): the packed-batch scaffolding both
    serve drivers share. ``split`` supplies shapes/dtypes only — the
    corpus split for graph requests, a one-row template split for
    raw-diff requests."""
    from fira_tpu.data.batching import make_batch

    bs = int(cfg.test_batch_size)
    if table is not None:
        if prewarm:
            if guard is not None:
                guard.declare(owner.labels(table))
            owner.prewarm((buckets_lib.warmup_batch(split, cfg, g, bs),
                           buckets_lib.geom_tag(g)) for g in table)
        return {b: buckets_lib.warmup_batch(split, cfg, g, bs)
                for b, g in enumerate(table)}
    templates = {0: make_batch(split, np.arange(0), cfg, batch_size=bs)}
    if prewarm:
        # unbucketed: pre-warm the single-geometry program family too
        # (prefill + no-op insert/step) — the dispatch
        # watchdog depends on post-warmup dispatches never paying a
        # first-use XLA compile (docs/FAULTS.md)
        owner.prewarm([(templates[0], None)])
    return templates


def run_loop_guarded(loop: "ServeLoop", snapshot) -> ServeStats:
    """Run the loop under the abort-flush contract: on ANY failure the
    freshest partial metrics snapshot survives alongside the ordered
    writer's .partial prefix (shared by both serve drivers)."""
    try:
        return loop.run()
    except BaseException:
        if snapshot is not None:
            try:
                snapshot(loop)
            except Exception:
                pass
        raise


def finalize_serve_result(stats: ServeStats, owner, faults, *,
                          out_path: str, bleu_by_pos: Dict[int, float],
                          metrics_path: Optional[str]) -> Dict:
    """The serve drivers' shared tail: split-order BLEU aggregation, the
    result dict, and the atomic final metrics artifact (+ .partial
    cleanup) — one definition so the graphs-path and diffs-path
    serve_metrics.json can never silently fork."""
    n_done = len(bleu_by_pos)
    total_bleu = sum(bleu_by_pos[p] for p in sorted(bleu_by_pos))
    result = {
        "sentence_bleu": total_bleu / max(n_done, 1),
        "n": float(n_done),
        "output_path": out_path,
        "serve": stats.summary(),
        "engine": owner.stats.summary(),
        **({"faults": faults.summary()} if faults else {}),
        "request_records": [dataclasses.asdict(r) for r in stats.records],
    }
    if metrics_path:
        write_metrics_atomic(metrics_path, {
            "serve": result["serve"],
            "engine": result["engine"],
            **({"faults": faults.summary()} if faults else {}),
            "request_records": _json_safe_records(stats.records),
        })
        if os.path.exists(metrics_path + ".partial"):
            os.remove(metrics_path + ".partial")
        result["metrics_path"] = metrics_path
    return result


def metrics_snapshotter(metrics_path: Optional[str], owner, faults):
    """The crash-contract partial-metrics hook both serve drivers pass
    to ServeLoop (None when no metrics artifact is maintained)."""
    if not metrics_path:
        return None
    partial_path = metrics_path + ".partial"
    # terminal records serialize once across the run's snapshots (see
    # _json_safe_records) — the snapshot's cost tracks the ACTIVE set,
    # not the full request count
    done_cache: Dict[int, Dict] = {}

    def snapshot(loop):
        write_metrics_atomic(partial_path, {
            "in_progress": True,
            "serve": loop.stats.summary(),
            "engine": owner.stats.summary(),
            **({"faults": faults.summary()} if faults else {}),
            "request_records": _json_safe_records(loop.stats.records,
                                                  done_cache),
        })

    return snapshot

def _request_tasks(data, cfg: FiraConfig, n: int, table, assignment,
                   mix=None):
    """One single-row ``make_batch`` task per request, request order — the
    async Feeder pre-assembles request payloads ahead of their arrival
    (an open-loop generator knows its requests up front; arrival TIME, not
    assembly, is what admission is gated on). Each task carries a ``note``
    (request position + bucket geometry) so a poisoned payload's recorded
    error names its sample.

    ``mix``: optional request->split-position map (request ``i`` serves
    sample ``mix[i]``; identity when None) — the repeated-traffic door:
    byte-identical requests at distinct output positions, which is what
    the prefix cache and the in-flight dedup exist for. With
    ``cfg.prefix_cache`` each task also stamps the payload's content
    digest WORKER-side (prefix_cache.stamp_digests), so the scheduler
    thread never pays the hashing."""
    from fira_tpu.data.batching import make_batch
    from fira_tpu.data.feeder import task_note
    from fira_tpu.decode import quant
    from fira_tpu.decode.prefix_cache import stamp_digests

    stamp = cfg.prefix_cache
    # digests carry the low-precision tier's namespace (decode/quant.py):
    # worker-side stamping and the engine's on-demand hashing both derive
    # it from the same cfg, so a cached f32 artifact never seats a bf16
    # slot and a tier change is a miss, never a wrong answer
    tier_ns = quant.tier_namespace(cfg)
    for i in range(n):
        j = int(mix[i]) if mix is not None else i  # firacheck: allow[HOST-SYNC] mix is a host request->sample index map; task generation is pure host-side planning
        geom = table[int(assignment[i])] if table is not None else None  # firacheck: allow[HOST-SYNC] host numpy bucket-assignment array — task generation is pure host-side planning
        def task(j=j, geom=geom):
            b = make_batch(data, np.asarray([j]), cfg, batch_size=1,  # firacheck: allow[HOST-SYNC] np.asarray of a host int list builds the make_batch index chunk; no device value exists here
                           geom=geom)
            return stamp_digests(b, tier_ns) if stamp else b
        task.note = task_note(
            [j], geom_tag=buckets_lib.geom_tag(geom) if geom else None,
            site="serve request")
        yield task


_TERMINAL_STATUSES = ("done", "shed_queue_full", "shed_deadline",
                      "shed_error")


def _json_safe_records(records: List[RequestRecord],
                       cache: Optional[Dict[int, Dict]] = None
                       ) -> List[Dict]:
    """Request-record dicts with NaN lifecycle stamps (shed requests were
    never seated) serialized as null — the metrics artifact is strict
    JSON (allow_nan=False).

    ``cache``: optional id(record) -> serialized-dict memo for the
    periodic snapshot path. A record in a TERMINAL status never mutates
    again, so its asdict walk (which deep-copies the per-request
    ``_ingest``/``retries`` payload) runs once instead of once per
    snapshot — without it the every-16-rounds snapshot re-serializes
    every finished request's stamps for the rest of the run, an O(n) tax
    per snapshot that profiling showed dominated by exactly this
    dataclasses.asdict + ingest-stamp rebuild."""
    out = []
    for r in records:
        if cache is not None:
            hit = cache.get(id(r))
            if hit is not None:
                out.append(hit)
                continue
        d = dataclasses.asdict(r)
        d = {k: (None if isinstance(v, float) and v != v else v)
             for k, v in d.items()}
        if cache is not None and r.status in _TERMINAL_STATUSES:
            cache[id(r)] = d
        out.append(d)
    return out


def write_metrics_atomic(path: str, payload: Dict) -> str:
    """Write a metrics artifact ATOMICALLY: full dump to ``path + ".tmp"``
    then one ``os.replace`` — a kill at any instant leaves either the
    previous complete file or the new one, never a torn JSON document
    (the OrderedStreamWriter crash discipline applied to metrics)."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, allow_nan=False)
        f.flush()
        os.fsync(f.fileno())  # firacheck: allow[SCHED-BLOCK] the atomic-artifact crash contract REQUIRES the fsync before the rename (docs/FAULTS.md); it runs once per snapshot cadence (16 rounds), not per dispatch, and the cost is metered in the journal-overhead rows
    os.replace(tmp, path)
    return path


def serve_split(model: FiraModel, params, dataset: FiraDataset,
                cfg: Optional[FiraConfig] = None, *,
                arrival_times: np.ndarray,
                out_dir: str = "OUTPUT",
                ablation: Optional[str] = None,
                var_maps: Optional[List[Dict[str, str]]] = None,
                split: str = "test",
                guard=None,
                engine_slots: Optional[int] = None,
                refill_order: str = "fifo",
                clock: str = "wall",
                step_cost_s: float = 1.0,
                prefill_cost_s: float = 1.0,
                engine=None,
                faults=None,
                metrics_path: Optional[str] = None,
                request_mix=None,
                journal_path: Optional[str] = None,
                resume: bool = False) -> Dict:
    """Serve the first ``len(arrival_times)`` samples of ``split`` as an
    open-loop request stream (request ``i`` = split position ``i``,
    arriving at ``arrival_times[i]``). Writes the same position-ordered
    output file as drain-mode ``run_test`` (shed requests write an empty
    line, so the file stays position-complete; with zero sheds the bytes
    are identical to drain mode) and returns its metrics dict plus
    ``serve`` (ServeStats.summary), ``engine`` (engine/fleet stats), and
    ``request_records`` (per-request lifecycle dicts).

    ``engine``: an already-constructed (and ideally already-warmed)
    SlotEngine or EngineFleet to serve on, instead of building one —
    the bench reuses one warm engine across swept rates so the latency
    rows measure serving, not per-run cold compiles. The caller owns
    its cfg consistency (and stats resets between timed runs); the
    scheduler state itself is reset per run.

    ``faults``: an armed robust.faults.FaultInjector (None resolves from
    ``cfg.inject_faults`` — "" keeps it off at zero overhead).
    ``metrics_path``: when set, the serve metrics artifact is maintained
    THROUGH the run — a ``<path>.partial`` snapshot refreshes atomically
    every few scheduler rounds (and once on abort), and the final file
    is written atomically (tmp + rename) at completion, matching the
    ordered writer's crash contract (docs/FAULTS.md).
    ``request_mix``: optional request->split-position map (request ``i``
    serves sample ``request_mix[i]``; identity when None). Repeated
    entries are byte-identical requests at distinct output positions —
    the repeated-traffic regime the prefix cache / in-flight dedup
    (cfg.prefix_cache) exist for; the bench and chaos repeat legs drive
    exactly this.
    ``journal_path``: when set, a write-ahead request journal (one
    fsync'd JSONL record per request at admit and at done/shed —
    robust/recovery.py) is maintained next to the output, making the run
    resumable after a hard kill. ``resume``: recover a killed run —
    finished lines are read back from the journal + the ordered writer's
    crash pair and only the not-yet-done suffix is re-served; the final
    output file is byte-identical to an uninterrupted run (exactly-once
    output, docs/FAULTS.md "Recovery contracts"). Respawn (cfg
    .max_respawns / cfg.engine_spares) arms the self-healing fleet:
    retirements are followed by replacements instead of permanent
    capacity loss."""
    cfg = cfg or dataset.cfg
    if faults is None:
        faults = faults_lib.injector_from(cfg)
    data = dataset.splits[split]
    vocab = dataset.word_vocab
    indices = dataset.split_indices[split]
    times = np.asarray(arrival_times, dtype=np.float64)
    n_req = len(times)
    mix = None
    if request_mix is not None:
        mix = np.asarray(request_mix, dtype=np.int64)
        if len(mix) != n_req:
            raise ValueError(
                f"request_mix has {len(mix)} entries for {n_req} arrivals")
        if len(mix) and (mix.min() < 0 or mix.max() >= len(data)):
            raise ValueError(
                f"request_mix references split position "
                f"{int(mix.min()) if mix.min() < 0 else int(mix.max())} "
                f"outside split {split!r} (size {len(data)})")
        indices = np.asarray(indices)[mix]
    elif n_req > len(data):
        raise ValueError(
            f"arrival trace has {n_req} requests but split {split!r} holds "
            f"only {len(data)} samples")
    errs = serve_errors(cfg, trace=True)
    errs += disagg_lib.disagg_errors(cfg)
    if errs:
        raise ValueError("; ".join(errs))
    clk = make_clock(clock, step_cost_s=step_cost_s,
                     prefill_cost_s=prefill_cost_s)

    if cfg.buckets:
        table = buckets_lib.decode_table(cfg)
        ext = buckets_lib.sample_extents(data, cfg)
        assignment = buckets_lib.assign_buckets(
            ext, table, use_msg=cfg.decode_tar_buckets)
        if mix is not None:
            # request-indexed view: request i's bucket is its SAMPLE's
            assignment = np.asarray(assignment)[mix]
    else:
        table = assignment = None

    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, output_name(ablation))

    # --- crash-resume (robust/recovery.py; docs/FAULTS.md "Recovery
    # contracts"): recover every finished line of the killed run from
    # the journal + the ordered writer's crash pair, then re-serve
    # EXACTLY the not-yet-done suffix — recovered positions are
    # re-emitted verbatim, served positions are deterministic per
    # position, so the final file is byte-identical to an uninterrupted
    # run (exactly-once output). The recovery read happens BEFORE the
    # writer opens (which truncates the .partial prefix).
    from fira_tpu.robust import recovery as recovery_lib

    recovered: Dict[int, str] = {}
    remaining: Optional[np.ndarray] = None
    if resume:
        if not journal_path:
            raise recovery_lib.ResumeError(
                "resume=True requires journal_path (the write-ahead "
                "request journal of the interrupted run)")
        res_errs = recovery_lib.resume_errors(journal_path, n_req, times,
                                              mix=mix)
        if res_errs:
            raise recovery_lib.ResumeError("; ".join(res_errs))
        recovered = recovery_lib.recover_output(out_path, n_req)
        remaining = np.asarray(
            [i for i in range(n_req) if i not in recovered],
            dtype=np.int64)
        if not len(remaining):
            # everything already finished: rebuild the final file from
            # the recovered lines — no engine, no serving
            with OrderedStreamWriter(out_path, expected=n_req) as w:
                for p in sorted(recovered):
                    w.add(p, recovered[p])
            stats = ServeStats(records=[])
            stats.resumed = n_req
            result = {"sentence_bleu": 0.0, "n": 0.0,
                      "output_path": out_path, "serve": stats.summary(),
                      "engine": {}, "request_records": []}
            if metrics_path:
                write_metrics_atomic(metrics_path, {
                    "serve": result["serve"], "engine": {},
                    "request_records": []})
                if os.path.exists(metrics_path + ".partial"):
                    os.remove(metrics_path + ".partial")
                result["metrics_path"] = metrics_path
            return result

    # the serving loop's view of the stream: full on a fresh run, the
    # not-yet-done suffix (original positions kept) on a resume
    times_loop, positions, task_mix, loop_assignment = \
        times, None, mix, assignment
    if remaining is not None:
        times_loop = times[remaining]
        positions = remaining
        task_mix = mix[remaining] if mix is not None else remaining
        loop_assignment = (np.asarray(assignment)[remaining]
                           if assignment is not None else None)

    # self-healing fleet (robust/recovery.py): with a respawn budget
    # armed the engines are ALWAYS fleet-built (the fleet owns
    # replace_slot + the warm-spare pool; a fleet-of-one is
    # byte-identical to the bare engine)
    respawn_armed = cfg.max_respawns > 0
    owner, engines, built = build_engines(model, params, cfg,
                                          engine=engine,
                                          engine_slots=engine_slots,
                                          guard=guard, faults=faults,
                                          fleet_always=respawn_armed)
    templates = prepare_templates(owner, data, cfg, table, guard=guard,
                                  prewarm=built)
    recovery = None
    if respawn_armed and hasattr(owner, "replace_slot"):
        if cfg.engine_spares:
            owner.build_spares(cfg.engine_spares)
        recovery = recovery_lib.RecoveryManager(
            owner, cfg, wall_clock=(clock == "wall"))

    # disaggregated prefill tier (serve/disagg.py; docs/SERVING.md
    # "Disaggregated tiers"): spawn the worker pool AFTER the decode
    # templates exist (the workers warm the same per-bucket prefill
    # family) — each child gets the ORIGINAL f32 params as host numpy
    # (prefill always runs f32, whatever the decode tier's precision)
    tier = None
    if cfg.serve_tiers != "off":
        import jax

        params_host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), params)
        tier = disagg_lib.PrefillTier(params_host, cfg,
                                      templates=templates, faults=faults)

    bleu_by_pos: Dict[int, float] = {}
    snapshot = metrics_snapshotter(metrics_path, owner, faults)
    journal = (recovery_lib.Journal(journal_path, n=n_req, times=times,
                                    mix=mix, resume=resume)
               if journal_path else None)

    try:
        with OrderedStreamWriter(out_path, expected=n_req) as writer, \
                Feeder(_request_tasks(data, cfg, len(times_loop), table,
                                      loop_assignment, task_mix),
                       num_workers=cfg.feeder_workers,
                       depth=cfg.feeder_depth,
                       put=False,
                       # the per-task error channel: a poisoned payload is
                       # retried in the worker, then delivered WITH its
                       # error for the loop to shed — never a consumer
                       # re-raise
                       on_error="record",
                       retries=max(0, cfg.robust_retries),
                       # one task is one REQUEST: its life is the stamps
                       # on its RequestRecord, not a span each
                       faults=faults, per_request=True) as feed:
            # resume: the recovered lines re-enter the position-keyed
            # writer first (prefix + above-gap tails both), exactly once
            for p in sorted(recovered):
                writer.add(p, recovered[p])
            emit = sample_emitter(writer, vocab=vocab, cfg=cfg,
                                  bleu_by_pos=bleu_by_pos, n_total=n_req,
                                  var_maps=var_maps, indices=indices)
            loop = ServeLoop(
                engines, cfg, arrival_times=times_loop, feed=feed,
                table=table, assignment=loop_assignment,
                templates=templates, clock=clk, emit=emit,
                # a shed request still owns its output position: an empty
                # line keeps the file position-complete and deterministic
                shed=lambda rec: writer.add(rec.position, "\n"),
                refill_order=refill_order, faults=faults,
                snapshot=snapshot, positions=positions, journal=journal,
                recovery=recovery, tier=tier)
            loop.stats.resumed = len(recovered)
            if tier is not None:
                # end-of-run counters, the ingest_cache pattern: the
                # summary closure reads the tier's final meters
                loop.stats.tiers = tier.stats.summary
            stats = run_loop_guarded(loop, snapshot)
    finally:
        if tier is not None:
            tier.close()
        if journal is not None:
            journal.close()
    # resource-lifecycle oracle (analysis.sanitizer.LeakGuard): with the
    # sanitizer armed, the run ends with every paged-block grant released
    # and every pipeline thread joined or sanctioned — a leak raises HERE
    # naming its acquire site, on the success path only (a serve error
    # must surface as itself, not be masked by its own leak fallout)
    lg = sanitizer.leak_guard()
    if lg is not None:
        lg.assert_clean("serve teardown")
    return finalize_serve_result(stats, owner, faults, out_path=out_path,
                                 bleu_by_pos=bleu_by_pos,
                                 metrics_path=metrics_path)
