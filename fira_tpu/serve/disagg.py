"""Disaggregated prefill/decode serving tiers (docs/SERVING.md
"Disaggregated tiers").

DistServe-style process split (OSDI'24; PAPERS.md): prefill and decode
interfere when they share one runtime — every prefill admitted
mid-stream stalls the seated slots' next decode step, which is exactly
the ``serve_prefill_budget`` tradeoff the in-process serve loop carries.
This module deletes that tradeoff structurally. A pool of **prefill
worker processes** (the ``ingest_exec=process`` spawn-pool template —
spawn, never fork: the parent runs live feeder/engine threads) each
holds its OWN jax runtime + params and computes per-request prefill
artifacts — exactly the prefix-cache payload (encoder output / one-beam
cross K/V / copy-head src projections, per-row content checksum,
tier-namespaced digest) — and ships them to the decode tier over a
process transport: pipe messages for control + small rows, shared-memory
segments for large artifact blobs. The decode side seeds every replica's
prefix cache (``SlotEngine.cache_put``) so requests admit through the
existing ALL-HIT cache path: host assemble + one device_put, ZERO
prefill dispatches on the decode replica, post-warmup.

Contract (pinned by tests/test_disagg.py and the check.sh disagg smoke
leg): trace-replay through the disaggregated path is byte-identical to
in-process serve, invariant to prefill-worker count and transport
interleaving; zero post-warmup retraces on the decode tier; every
shipped row is checksum-verified at seat (a corrupt transport — the
``disagg.transport`` fault site — re-prefills, never a wrong answer).
Lifecycle rides the existing retirement machinery: a dead worker process
retires and its in-flight work resubmits to survivors; all-workers-lost
is a RECORDED fallback to in-process prefill (``TierStats.fallback``),
never a hang — once the tier has served. A pool in which NO worker ever
reported ready did not start: that raises :class:`TierStartupError`
instead of carrying on in-process as if the tier had run.

CPU only. Each worker is a process with its own jax runtime on the
parent's platform, and an accelerator belongs to one process: under a
TPU parent every child would die in backend init. ``disagg_errors``
refuses the tier there at parse time (ROADMAP S6 re-founds it as devices
inside one process).

This module imports no JAX at module level: it is the spawn-entry module
for the worker children, and the child pins ``JAX_PLATFORMS`` from the
parent's backend BEFORE its first jax import.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from fira_tpu.config import FiraConfig
from fira_tpu.decode import prefix_cache as prefix_cache_lib
from fira_tpu.robust import faults as faults_lib

TIERS = ("off", "prefill-pool")

# rows whose packed artifact blob crosses this ship via a shared-memory
# segment (one segment per result message, parent attaches/copies/
# unlinks); smaller results ride the pipe inline. Module-level so tests
# can pin either transport (both are checksum-verified identically).
SHM_MIN_BYTES = 1 << 18

# a digest may be submitted to the pool at most this many times ON TOP
# of cfg.robust_retries before the tier gives it up to the decode
# replica's own in-process prefill (the per-request fallback — bounded,
# so a persistently-corrupting transport degrades, never livelocks)
_BASE_ATTEMPTS = 1


class TierStartupError(RuntimeError):
    """Every prefill worker was lost before any reported ready."""


def disagg_errors(cfg: FiraConfig,
                  platform: Optional[str] = None) -> List[str]:
    """Parse-time validation for the disaggregated-tier knobs (CLI exit
    2 — the named-knob contract every serving knob meets).

    ``platform``: the backend the serving process runs on; None asks jax
    (only when the tier is armed — the off path stays jax-free)."""
    errs: List[str] = []
    if cfg.serve_tiers not in TIERS:
        errs.append(
            f"serve_tiers {cfg.serve_tiers!r} is not one of {TIERS}; "
            f"see docs/SERVING.md 'Disaggregated tiers'")
    if cfg.serve_tiers != "off":
        if not cfg.decode_engine:
            errs.append(
                "serve_tiers=prefill-pool requires decode_engine: the "
                "decode tier seats shipped artifacts through the slot "
                "engine's cache-admission path")
        if not cfg.prefix_cache:
            errs.append(
                "serve_tiers=prefill-pool requires prefix_cache: shipped "
                "artifacts enter decode replicas through the prefix "
                "cache (the all-hit admission path)")
        if platform is None:
            import jax
            platform = jax.default_backend()
        if platform != "cpu":
            errs.append(
                f"serve_tiers=prefill-pool cannot run on platform "
                f"{platform!r}: its prefill workers are separate processes "
                f"and an accelerator belongs to one process, so every "
                f"worker would fail in backend init (ROADMAP S6); use "
                f"serve_tiers=off")
    if cfg.prefill_workers < 1:
        errs.append(
            f"prefill_workers must be >= 1, got {cfg.prefill_workers}")
    if cfg.serve_artifact_budget_mb < 0:
        errs.append(
            f"serve_artifact_budget_mb must be >= 0 (0 = unbounded), "
            f"got {cfg.serve_artifact_budget_mb}")
    return errs


# --------------------------------------------------------------------------
# worker child
# --------------------------------------------------------------------------

def _ship_result(conn, seq: int, rows) -> None:
    """Ship one computed group back: ``rows`` is
    ``[(digest, checksum, payload_dict), ...]``. Small groups ride the
    pipe; large ones pack every array into ONE shared-memory segment and
    send (name, dtype, shape, offset) metadata — the parent copies out
    and unlinks. The checksum covers the payload CONTENT either way, so
    the verify-at-seat contract is transport-agnostic."""
    total = sum(prefix_cache_lib.payload_nbytes(p) for _d, _c, p in rows)
    if total < SHM_MIN_BYTES:
        conn.send(("result", seq, rows, None))
        return
    from multiprocessing import shared_memory

    shm = shared_memory.SharedMemory(create=True, size=max(1, total))
    try:
        # the PARENT owns unlink (it outlives this copy): deregister the
        # segment from the child's resource tracker so child exit does
        # not double-unlink / warn about a segment that is not leaked
        from multiprocessing import resource_tracker
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    off = 0
    meta = []
    for d, c, p in rows:
        fields = []
        for name in sorted(p):
            a = np.ascontiguousarray(p[name])
            nb = int(a.nbytes)  # firacheck: allow[HOST-SYNC] host numpy payload being packed into the shm segment — no device value in the child's ship path
            shm.buf[off:off + nb] = a.tobytes()
            fields.append((name, str(a.dtype), tuple(a.shape), off, nb))
            off += nb
        meta.append((d, c, fields))
    name = shm.name
    shm.close()
    conn.send(("result", seq, meta, name))


def _worker_main(conn, init: Dict) -> None:
    """Prefill-worker entry (spawn child). Pins the jax platform from
    the parent's backend BEFORE the first jax import, builds a real
    SlotEngine from the shipped cfg + host params (byte-identity: the
    worker's prefill IS the decode engine's ``_prefill`` program), warms
    its prefill family once per bucket, then serves ``work`` messages
    until ``stop``. An injected ``disagg.worker`` raise exits the
    PROCESS — deliberately: worker death is the failure mode under
    test, and the parent's sweep retires + resubmits."""
    os.environ.setdefault("JAX_PLATFORMS", init["platform"])
    import jax
    from fira_tpu.decode.engine import SlotEngine
    from fira_tpu.model.model import FiraModel

    cfg: FiraConfig = init["cfg"]
    wid: int = init["worker_id"]
    templates: Dict[int, Dict] = init["templates"]
    inj = faults_lib.injector_from(cfg)
    eng = SlotEngine(FiraModel(cfg), init["params"], cfg,
                     slots=max(1, cfg.test_batch_size))

    def _prefill_group(bucket: int, rows) -> List[Tuple]:
        tmpl = templates[bucket]
        batch = {k: np.array(v) for k, v in tmpl.items()  # firacheck: allow[HOST-SYNC] host-side wire assembly from the host template — the single H2D device_put below is the boundary
                 if not k.startswith("_")}
        for j, (_d, rh) in enumerate(rows):
            for k in batch:
                batch[k][j] = rh[k][0]
        chunk = eng._prefill(eng.params, jax.device_put(batch))
        chunk_host = {f: np.asarray(jax.device_get(chunk[f]))  # firacheck: allow[HOST-SYNC] the worker child's whole job is materializing prefill artifacts on host for transport; this D2H is the product, not a stall
                      for f in prefix_cache_lib.ARTIFACT_FIELDS}
        entries = prefix_cache_lib.extract_payloads(
            chunk_host, list(range(len(rows))))
        return [(rows[j][0], prefix_cache_lib.payload_checksum(entries[j]),
                 entries[j]) for j in range(len(rows))]

    # prewarm the prefill program per bucket and report the measured
    # per-row artifact footprint — the parent's backpressure unit
    est: Dict[int, int] = {}
    for b in sorted(templates):
        wire = {k: np.array(v) for k, v in templates[b].items()  # firacheck: allow[HOST-SYNC] prewarm-time host wire assembly, once per bucket before any request exists
                if not k.startswith("_")}
        chunk = eng._prefill(eng.params, jax.device_put(wire))
        chunk_host = {f: np.asarray(jax.device_get(chunk[f]))  # firacheck: allow[HOST-SYNC] prewarm-time artifact sizing for the ready handshake (once per bucket, before any request exists)
                      for f in prefix_cache_lib.ARTIFACT_FIELDS}
        entry = prefix_cache_lib.extract_payloads(chunk_host, [0])[0]
        est[b] = prefix_cache_lib.payload_nbytes(entry)
    conn.send(("ready", wid, est))

    while True:
        msg = conn.recv()
        if msg[0] == "stop":
            break
        _kind, seq, bucket, rows = msg
        if inj is not None:
            try:
                inj.check("disagg.worker", key=f"w{wid}:{seq}")
            except faults_lib.InjectedFault:
                # worker DEATH, quietly (no traceback spew into chaos
                # runs): the parent sees the pipe close / dead process
                conn.close()
                os._exit(17)
        _ship_result(conn, seq, _prefill_group(bucket, rows))
    conn.close()


def _unpack_rows(rows, shm_name: Optional[str]) -> List[Tuple]:
    """Parent-side receive: inline rows pass through; shared-memory rows
    copy out of the segment, which is then closed AND unlinked (the
    parent owns the segment's end of life)."""
    if shm_name is None:
        return list(rows)
    from multiprocessing import shared_memory

    shm = shared_memory.SharedMemory(name=shm_name)
    try:
        out = []
        for d, c, fields in rows:
            p = {}
            for name, dt, shape, off, nb in fields:
                dtype = np.dtype(dt)
                p[name] = np.frombuffer(
                    shm.buf, dtype=dtype, count=nb // dtype.itemsize,
                    offset=off).reshape(shape).copy()
            out.append((d, c, p))
        return out
    finally:
        shm.close()
        shm.unlink()


def _discard_shm(shm_name: Optional[str]) -> None:
    """Unlink a segment whose message was dropped (transport fault or
    tier shutdown) without reading it — the no-leak path."""
    if shm_name is None:
        return
    from multiprocessing import shared_memory

    try:
        shm = shared_memory.SharedMemory(name=shm_name)
        shm.close()
        shm.unlink()
    except Exception:
        pass


# --------------------------------------------------------------------------
# parent-side tier
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TierStats:
    """Prefill-tier observability (serve_metrics.json ``tiers`` block —
    present only when tiers ran, so tier-less summaries stay
    byte-stable). Every field lands in :meth:`summary`."""

    workers: int = 0
    workers_lost: int = 0
    fallback: bool = False
    fallback_reason: str = ""
    groups_submitted: int = 0
    rows_submitted: int = 0
    rows_delivered: int = 0
    rows_resubmitted: int = 0
    rows_given_up: int = 0
    transport_msgs_lost: int = 0
    transport_integrity_drops: int = 0
    shm_segments: int = 0
    artifact_bytes: int = 0
    inflight_bytes: int = 0
    peak_inflight_bytes: int = 0
    peak_backlog: int = 0
    prefill_busy_s: float = 0.0
    rows_by_worker: Dict[int, int] = dataclasses.field(default_factory=dict)

    def summary(self) -> Dict:
        return {
            "workers": self.workers,
            "workers_lost": self.workers_lost,
            "fallback": self.fallback,
            "fallback_reason": self.fallback_reason,
            "groups_submitted": self.groups_submitted,
            "rows_submitted": self.rows_submitted,
            "rows_delivered": self.rows_delivered,
            "rows_resubmitted": self.rows_resubmitted,
            "rows_given_up": self.rows_given_up,
            "transport_msgs_lost": self.transport_msgs_lost,
            "transport_integrity_drops": self.transport_integrity_drops,
            "shm_segments": self.shm_segments,
            "artifact_bytes": self.artifact_bytes,
            "inflight_bytes": self.inflight_bytes,
            "peak_inflight_bytes": self.peak_inflight_bytes,
            "peak_backlog": self.peak_backlog,
            "prefill_busy_s": self.prefill_busy_s,
            "rows_by_worker": {str(k): v
                               for k, v in sorted(self.rows_by_worker.items())},
        }


@dataclasses.dataclass
class _Group:
    """One submitted work item: a same-bucket batch of queue entries."""

    seq: int
    bucket: int
    entries: List[object]      # serve/server._Queued
    bytes_est: int
    submit_t: float


class _Worker:
    """One prefill worker process + its pipe end, parent side."""

    def __init__(self, wid: int, proc, conn) -> None:
        self.wid = wid
        self.proc = proc
        self.conn = conn
        self.ready = False
        self.retired = False
        self.row_bytes: Dict[int, int] = {}
        self.inflight: Dict[int, _Group] = {}

    @property
    def live(self) -> bool:
        return not self.retired and self.proc.is_alive()


class PrefillTier:
    """The parent-side prefill pool: submission (``service`` — pump the
    serve queue into worker batches under the in-flight byte budget),
    delivery (drain results, checksum-verify, seed every decode
    replica's cache), and lifecycle (dead worker => retire + resubmit to
    survivors; all lost => recorded in-process fallback). Stateless
    about queue membership on purpose: requests STAY in the serve
    loop's admission queue (held by ``holds``) until their artifacts
    land, so sheds/promotions/retirements keep their existing semantics
    untouched."""

    def __init__(self, params_host, cfg: FiraConfig, *,
                 templates: Dict[int, Dict], faults=None) -> None:
        import multiprocessing

        self.cfg = cfg
        self._bs = max(1, int(cfg.test_batch_size))
        self._budget = int(cfg.serve_artifact_budget_mb) * (1 << 20)
        self._max_attempts = _BASE_ATTEMPTS + max(0, int(cfg.robust_retries))
        self._watchdog_s = float(cfg.dispatch_watchdog_s or 0.0)
        self._faults = faults
        self.stats = TierStats(workers=int(cfg.prefill_workers))
        self._pending: Dict[str, int] = {}     # digest -> owning seq
        self._attempts: Dict[str, int] = {}    # digest -> submit count
        self._given_up: set = set()
        self._first_seen: Dict[str, float] = {}
        self._inflight_bytes = 0
        self._seq = 0
        self._rr = 0
        self._dead = False
        self._closed = False
        platform = os.environ.get("JAX_PLATFORMS", "")
        if not platform:
            import jax
            platform = jax.default_backend()
        from fira_tpu.analysis.sanitizer import leak_guard
        self._leaks = leak_guard()
        if self._leaks is not None:
            self._leaks.note_acquire(
                "pool", f"PrefillTier@{id(self):x}",
                what=f"prefill worker pool ({cfg.prefill_workers} procs)")
        # spawn, never fork: the parent runs live feeder/engine threads
        # (the ingest_exec=process rule) and each child needs a FRESH
        # jax runtime of its own
        ctx = multiprocessing.get_context("spawn")
        self._workers: List[_Worker] = []
        for wid in range(cfg.prefill_workers):
            parent_conn, child_conn = ctx.Pipe()
            init = {"cfg": cfg, "params": params_host,
                    "templates": templates, "platform": platform,
                    "worker_id": wid}
            proc = ctx.Process(target=_worker_main,
                               args=(child_conn, init), daemon=True,
                               name=f"fira-prefill-w{wid}")
            proc.start()
            child_conn.close()
            self._workers.append(_Worker(wid, proc, parent_conn))

    # --- scheduling surface (serve/server.ServeLoop) --------------------

    @property
    def alive(self) -> bool:
        return not self._dead and not self._closed

    def holds(self, digest) -> bool:
        """True when the tier owns prefill for this digest: the serve
        loop holds such misses in the queue (NEVER dispatching a decode-
        tier prefill for them) until delivery flips their admission to a
        cache hit. False once the tier is dead or the digest exhausted
        its resubmit budget — the recorded in-process fallback."""
        return self.alive and digest is not None \
            and digest not in self._given_up

    def service(self, queue, engines) -> None:
        """One scheduler-round tick: sweep dead workers, drain every
        available result, then pump fresh queue misses into worker
        batches. Called from the serve loop's round head — all host
        work, no jax dispatch, so the decode tier's round cadence is
        untouched."""
        if not self.alive:
            return
        self._sweep(engines)
        self._drain(engines)
        self._pump(queue, engines)

    def idle_wait(self, timeout: float) -> None:
        """Bounded wait for tier progress when the serve loop has
        NOTHING dispatchable (every queued request is tier-held): block
        on the worker pipes up to ``timeout`` instead of busy-spinning
        the scheduler. Wakes early on any message (ready/result) or
        worker death (pipe close wakes the wait too)."""
        if not self.alive:
            return
        busy = any(w.inflight for w in self._workers) \
            or bool(self._pending) or not all(
                w.ready for w in self._workers if w.live)
        conns = [w.conn for w in self._workers if not w.retired]
        if not busy or not conns:
            return
        from multiprocessing import connection
        # bounded idle wait while ZERO dispatchable work exists (every
        # queued request is tier-held awaiting a worker result); the
        # alternative is a hot busy-spin of the scheduler round — same
        # contract as the all-replicas-lost 10ms beat
        connection.wait(conns, timeout)

    # --- internals ------------------------------------------------------

    def _sweep(self, engines) -> None:
        now = time.perf_counter()
        for w in self._workers:
            if w.retired:
                continue
            if not w.proc.is_alive():
                self._retire_worker(w, "process died")
            elif self._watchdog_s and w.inflight:
                oldest = min(g.submit_t for g in w.inflight.values())
                if now - oldest > self._watchdog_s:
                    self._retire_worker(
                        w, f"work item exceeded the "
                           f"{self._watchdog_s:.1f}s dispatch watchdog")
        if not any(w.live for w in self._workers) and not self._dead:
            if not any(w.ready for w in self._workers):
                # nothing was ever served by this pool: a start-up
                # failure (bad platform, import error, OOM at prewarm),
                # not a degradation to record and run past
                raise TierStartupError(
                    f"all {len(self._workers)} prefill workers exited "
                    f"before reporting ready; the prefill tier never "
                    f"started (see the workers' stderr above)")
            self._dead = True
            self.stats.fallback = True
            self.stats.fallback_reason = (
                "all prefill workers lost; decode tier resumed "
                "in-process prefill")

    def _retire_worker(self, w: _Worker, reason: str) -> None:
        if w.retired:
            return
        w.retired = True
        self.stats.workers_lost += 1
        for group in w.inflight.values():
            # requeue to survivors: the digests simply leave the pending
            # set — the entries never left the serve queue, so the next
            # pump resubmits them to whichever workers remain
            self._inflight_bytes -= group.bytes_est
            for e in group.entries:
                if self._pending.pop(e.digest, None) is not None:
                    self.stats.rows_resubmitted += 1
        w.inflight.clear()
        try:
            w.conn.close()
        except Exception:
            pass
        if w.proc.is_alive():
            w.proc.terminate()
        self.stats.inflight_bytes = self._inflight_bytes

    def _drain(self, engines) -> None:
        for w in self._workers:
            if w.retired:
                continue
            while True:
                try:
                    if not w.conn.poll(0):
                        break
                    msg = w.conn.recv()
                except (EOFError, OSError, BrokenPipeError):
                    self._retire_worker(w, "transport connection lost")
                    break
                self._handle(w, msg, engines)

    def _handle(self, w: _Worker, msg, engines) -> None:
        if msg[0] == "ready":
            _kind, _wid, est = msg
            w.ready = True
            w.row_bytes = dict(est)
            return
        if msg[0] != "result":
            return
        _kind, seq, rows, shm_name = msg
        recv_t = time.perf_counter()
        group = w.inflight.pop(seq, None)
        if group is not None:
            self._inflight_bytes -= group.bytes_est
            self.stats.inflight_bytes = self._inflight_bytes
            self.stats.prefill_busy_s += recv_t - group.submit_t
        if self._faults is not None \
                and self._faults.armed("disagg.transport"):
            try:
                self._faults.check("disagg.transport", key=seq)
            except faults_lib.InjectedFault:
                # the message is LOST in transport: discard it (and its
                # segment) — the digests leave pending and the next pump
                # resubmits them; bytes-identical output, later
                _discard_shm(shm_name)
                self.stats.transport_msgs_lost += 1
                if group is not None:
                    for e in group.entries:
                        if self._pending.pop(e.digest, None) is not None:
                            self.stats.rows_resubmitted += 1
                return
        try:
            unpacked = _unpack_rows(rows, shm_name)
        except (OSError, ValueError):
            # segment vanished (e.g. producer died mid-ship): same
            # degrade as a lost message
            self.stats.transport_msgs_lost += 1
            if group is not None:
                for e in group.entries:
                    if self._pending.pop(e.digest, None) is not None:
                        self.stats.rows_resubmitted += 1
            return
        if shm_name is not None:
            self.stats.shm_segments += 1
        for i, (digest, checksum, payload) in enumerate(unpacked):
            if self._faults is not None:
                payload = self._faults.corrupt("disagg.transport",
                                               f"{seq}:{i}", payload)
            if prefix_cache_lib.payload_checksum(payload) != checksum:
                # checksum caught a scrambled row at the seat boundary:
                # drop it and re-prefill — NEVER a wrong answer
                self.stats.transport_integrity_drops += 1
                if self._pending.pop(digest, None) is not None:
                    self.stats.rows_resubmitted += 1
                continue
            nb = prefix_cache_lib.payload_nbytes(payload)
            for eng in engines:
                eng.cache_put(digest, payload)
            self._pending.pop(digest, None)
            self.stats.rows_delivered += 1
            self.stats.artifact_bytes += nb
            self.stats.rows_by_worker[w.wid] = \
                self.stats.rows_by_worker.get(w.wid, 0) + 1
            if group is not None and i < len(group.entries):
                rec = group.entries[i].record
                if rec.status == "queued":
                    rec.transport_s = recv_t - group.submit_t
                    rec.artifact_bytes = nb

    def _pump(self, queue, engines) -> None:
        now = time.perf_counter()
        cand = []
        for e in queue:
            d = e.digest
            if d is None or d in self._pending or d in self._given_up \
                    or e.record.status != "queued":
                continue
            if d not in self._first_seen:
                self._first_seen[d] = now
            if engines and all(eng.cache_contains(d) for eng in engines):
                continue
            if self._attempts.get(d, 0) >= self._max_attempts:
                self._given_up.add(d)
                self.stats.rows_given_up += 1
                continue
            cand.append(e)
        self.stats.peak_backlog = max(self.stats.peak_backlog, len(cand))
        ready = [w for w in self._workers if w.ready and w.live]
        if not ready:
            return
        while cand:
            bucket = cand[0].bucket
            take, rest = [], []
            for e in cand:
                if e.bucket == bucket and len(take) < self._bs:
                    take.append(e)
                else:
                    rest.append(e)
            cand = rest
            est = len(take) * max(
                1, ready[0].row_bytes.get(bucket, SHM_MIN_BYTES))
            if self._budget and self._inflight_bytes \
                    and self._inflight_bytes + est > self._budget:
                # backpressure: the in-flight artifact budget is spent —
                # wait for deliveries. A single group alone still ships
                # (inflight==0 path), the same degrade rule as the
                # prefix cache's byte cap.
                break
            w = ready[self._rr % len(ready)]
            self._rr += 1
            seq = self._seq
            self._seq += 1
            rows = [(e.digest,
                     {k: v for k, v in e.host.items()
                      if not k.startswith("_")}) for e in take]
            try:
                w.conn.send(("work", seq, bucket, rows))
            except (OSError, BrokenPipeError, ValueError):
                self._retire_worker(w, "submit failed")
                ready = [x for x in self._workers if x.ready and x.live]
                if not ready:
                    return
                cand = take + cand
                continue
            group = _Group(seq, bucket, take, est, now)
            w.inflight[seq] = group
            self._inflight_bytes += est
            self.stats.inflight_bytes = self._inflight_bytes
            self.stats.peak_inflight_bytes = max(
                self.stats.peak_inflight_bytes, self._inflight_bytes)
            self.stats.groups_submitted += 1
            self.stats.rows_submitted += len(take)
            for e in take:
                self._pending[e.digest] = seq
                self._attempts[e.digest] = \
                    self._attempts.get(e.digest, 0) + 1
                rec = e.record
                rec.prefill_queue_s = now - self._first_seen[e.digest]

    def close(self) -> None:
        """Tear the pool down: best-effort drain of already-shipped
        results first (their shared-memory segments must be unlinked —
        the no-leak path the RES-LEAK sanitizer pins), then stop + join
        every worker, terminating stragglers."""
        if self._closed:
            return
        self._closed = True
        for w in self._workers:
            if w.retired:
                continue
            try:
                while w.conn.poll(0):
                    msg = w.conn.recv()
                    if msg and msg[0] == "result":
                        _discard_shm(msg[3])
            except Exception:
                pass
            try:
                w.conn.send(("stop",))
            except Exception:
                pass
        for w in self._workers:
            if not w.retired:
                w.proc.join(timeout=5.0)
                if w.proc.is_alive():
                    w.proc.terminate()
                    w.proc.join(timeout=1.0)
            try:
                w.conn.close()
            except Exception:
                pass
        if self._leaks is not None:
            self._leaks.note_release("pool", f"PrefillTier@{id(self):x}")

    def __enter__(self) -> "PrefillTier":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
