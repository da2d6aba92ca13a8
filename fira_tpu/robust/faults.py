"""Seeded, site-addressed fault-injection registry (docs/FAULTS.md).

The serving stack's graceful-degradation contracts — poison-request
quarantine, replica retirement + requeue, dispatch watchdog — are only
real if a test can *trigger* the failure deterministically. This module
is the trigger: named injection points ("sites") along the request path,
armed by a parse-time-validated spec string

    site:kind:rate:seed[,site:kind:rate:seed...]

with kinds ``raise`` (the site throws :class:`InjectedFault`), ``hang``
(the site sleeps ``fault_hang_s`` wall seconds — the watchdog's prey),
and ``corrupt`` (the site's host payload is deterministically scrambled
in place, same shapes/dtypes — the ``CORRUPT_SITES`` that own a host
payload only). Whether a given event fires is a pure
function of ``(seed, site, event key)`` via a keyed blake2b digest — NO
process-global RNG, NO call-order dependence — so every chaos run
replays exactly, thread pools included (feeder sites key by task
sequence number, single-threaded scheduler sites by a per-site counter).

Off by default: with no spec armed the injector is ``None`` and every
site check is a single ``is not None`` branch — zero hot-path overhead.
Faults act on the HOST side only (raise before a dispatch, sleep,
scramble a numpy batch in place): no new jitted program ever exists, so
the zero-post-warmup-retrace contract holds with faults armed (pinned
under the compile guard by tests/test_robust.py).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from fira_tpu.config import FiraConfig

SITES = (
    "feeder.assemble",    # host batch assembly on a feeder worker
    "feeder.device_put",  # the worker-side H2D transfer
    "ingest.parse",       # raw-diff ingest on a feeder worker
    #                       (ingest/service.py): raise/hang fire before
    #                       the parse (the malformed-request class — the
    #                       quarantine sheds with the reason recorded);
    #                       corrupt scrambles the ASSEMBLED payload (a
    #                       garbage request the downstream must serve or
    #                       shed, never crash on)
    "ingest.cache",       # a whole-diff result-cache lookup
    #                       (ingest/cache.py): raise => absorbed as a
    #                       MISS (full re-ingest, bytes unchanged, never
    #                       a shed); corrupt => the read payload is
    #                       scrambled, the entry's content checksum
    #                       catches it, the entry is dropped and the
    #                       request re-ingests (never a wrong answer)
    "engine.prefill",     # the engine's prefill dispatch (admit)
    "engine.step",        # the engine's step dispatch
    "engine.harvest",     # the one transfer of the step's outputs
    "fleet.replica",      # one replica's whole service round
    "serve.admit",        # a request's admission into the serve queue
    "cache.lookup",       # a prefix-cache lookup (decode/prefix_cache.py):
    #                       raise => absorbed as a MISS (re-prefill, never a
    #                       wrong answer); corrupt => the read payload is
    #                       scrambled, the entry's content checksum catches
    #                       it, and the entry is dropped
    "disagg.transport",   # a prefill-tier artifact delivery at the
    #                       decode-side receive boundary
    #                       (serve/disagg.py): raise => the message is
    #                       treated as lost and its requests resubmit to
    #                       the pool; hang sleeps the receive; corrupt
    #                       scrambles the shipped payload — the per-row
    #                       content checksum catches it at seat and the
    #                       row re-prefills (never a wrong answer)
    "disagg.worker",      # one prefill-worker work item (child-side,
    #                       serve/disagg.py _worker_main): raise kills
    #                       the worker PROCESS (the uncaught exception
    #                       exits it) => the parent retires the worker
    #                       and requeues its in-flight work to
    #                       survivors; all-workers-lost => recorded
    #                       in-process prefill fallback; hang sleeps
    #                       inside the child (the lifecycle watchdog's
    #                       prey)
)
KINDS = ("raise", "hang", "corrupt")
# corrupt scrambles a HOST payload in place; only the sites that own a
# host payload qualify (every other site is a dispatch boundary with
# nothing host-mutable): batch assembly, raw-diff ingest assembly, the
# two content-cache read paths, and the disagg transport's shipped
# artifact rows (whose checksums must catch the scramble —
# docs/FAULTS.md)
CORRUPT_SITES = ("feeder.assemble", "ingest.parse", "ingest.cache",
                 "cache.lookup", "disagg.transport")


class InjectedFault(RuntimeError):
    """A fault fired by the injection registry — the exception the
    degradation machinery must absorb (quarantine or retirement), never
    a bug in itself."""

    def __init__(self, site: str, key) -> None:
        super().__init__(f"injected fault at {site} (event {key})")
        self.site = site
        self.key = key


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One armed site: fire ``kind`` with probability ``rate`` per event,
    deterministically under ``seed``."""

    site: str
    kind: str
    rate: float
    seed: int


def parse_fault_specs(spec: str) -> List[FaultSpec]:
    """Parse ``site:kind:rate:seed[,...]``; raises ValueError with a
    named-knob message on any malformed entry (the CLI turns it into
    exit 2 via :func:`robust_errors`)."""
    specs: List[FaultSpec] = []
    seen: set = set()
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        fields = entry.split(":")
        if len(fields) != 4:
            raise ValueError(
                f"inject_faults entry {entry!r} is not site:kind:rate:seed "
                f"(four ':'-separated fields); see docs/FAULTS.md")
        site, kind, rate_s, seed_s = fields
        if site not in SITES:
            raise ValueError(
                f"inject_faults site {site!r} is not a registered fault "
                f"site; choose from {', '.join(SITES)}")
        if kind not in KINDS:
            raise ValueError(
                f"inject_faults kind {kind!r} at site {site} is not one of "
                f"{', '.join(KINDS)}")
        if kind == "corrupt" and site not in CORRUPT_SITES:
            raise ValueError(
                f"inject_faults kind 'corrupt' is only meaningful at "
                f"{', '.join(CORRUPT_SITES)} (the site that owns a host "
                f"payload to scramble); {site} is a dispatch boundary")
        try:
            rate = float(rate_s)  # firacheck: allow[HOST-SYNC] rate_s is a parse-time CLI spec string field, not a device value
        except ValueError:
            raise ValueError(
                f"inject_faults rate {rate_s!r} at site {site} is not a "
                f"float")
        if not 0.0 <= rate <= 1.0:
            raise ValueError(
                f"inject_faults rate {rate} at site {site} must be in "
                f"[0, 1] (a per-event fire probability)")
        try:
            seed = int(seed_s)  # firacheck: allow[HOST-SYNC] seed_s is a parse-time CLI spec string field, not a device value
        except ValueError:
            raise ValueError(
                f"inject_faults seed {seed_s!r} at site {site} is not an "
                f"integer")
        if site in seen:
            raise ValueError(
                f"inject_faults arms site {site} twice; one spec per site "
                f"(the event-key stream is per site)")
        seen.add(site)
        specs.append(FaultSpec(site=site, kind=kind, rate=rate, seed=seed))
    return specs


def robust_errors(cfg: FiraConfig) -> List[str]:
    """Parse-time robustness-knob admission check (the chaos twin of
    parallel.mesh.divisibility_errors / serve.server.serve_errors): one
    named-knob message per violation, CLI exit 2. Checks the fault-spec
    grammar, the watchdog timeout (0 = off, else > 0), the quarantine
    retry count (>= 0), and the injected-hang duration (> 0)."""
    errs: List[str] = []
    if cfg.inject_faults:
        try:
            parse_fault_specs(cfg.inject_faults)
        except ValueError as e:
            errs.append(str(e))
    if cfg.dispatch_watchdog_s < 0:
        errs.append(
            f"dispatch_watchdog_s {cfg.dispatch_watchdog_s} must be 0 "
            f"(watchdog off) or > 0 wall seconds per dispatch")
    if cfg.robust_retries < 0:
        errs.append(
            f"robust_retries {cfg.robust_retries} must be >= 0 (retries "
            f"granted to a poisoned request before it is shed)")
    if cfg.fault_hang_s <= 0:
        errs.append(
            f"fault_hang_s {cfg.fault_hang_s} must be > 0 wall seconds "
            f"(the duration an injected 'hang' fault sleeps)")
    return errs


def backoff_s(attempt: int) -> float:
    """The quarantine retry backoff curve, shared by every retry site
    (feeder assembly, serve admission, serve prefill): linear in the
    attempt number, capped — long enough to outlive a transient blip,
    short enough that a virtual-clock replay stays fast. One definition
    so the quarantine policy cannot silently diverge between sites."""
    return min(0.01 * max(1, attempt), 0.05)


class FaultInjector:
    """The armed registry: one :class:`FaultSpec` per site, a keyed
    deterministic draw per event, and an observability counter of what
    actually fired (``summary()`` lands in stats artifacts)."""

    def __init__(self, specs: List[FaultSpec], *, hang_s: float = 2.0):
        self._by_site: Dict[str, FaultSpec] = {s.site: s for s in specs}
        self._counters: Dict[str, int] = {}
        self.hang_s = float(hang_s)
        self.fired: "collections.Counter" = collections.Counter()
        # per-site event keys that actually fired — feeder sites key by
        # task sequence, so for serve request streams (one single-row
        # task per split position) these ARE the affected positions; the
        # chaos smoke reads them to bound the corrupt blast radius
        self.fired_keys: Dict[str, List] = collections.defaultdict(list)
        # fired accounting is mutated from concurrent feeder workers —
        # Counter += is a non-atomic read-modify-write
        self._lock = threading.Lock()
        # lock-discipline sanitizer (--sanitize / tests): exactly the
        # unlocked-increment bug the PR 9 review caught here — armed, a
        # `fired[site] += 1` outside `with self._lock` raises at the line
        from fira_tpu.analysis.sanitizer import guard_structures

        self._lock, (self.fired,) = guard_structures(
            self, self._lock, [(self.fired, "fired")])

    def _record_fire(self, site: str, key) -> None:
        with self._lock:
            self.fired[site] += 1
            self.fired_keys[site].append(key)

    def armed(self, site: str) -> bool:
        return site in self._by_site

    @staticmethod
    def _draw(spec: FaultSpec, key) -> bool:
        """One uniform in [0, 1) per (seed, site, key), via a keyed
        blake2b digest: deterministic across processes and thread
        schedules (tuple ``hash()`` is salted per process — never use
        it for replayable chaos)."""
        msg = f"{spec.seed}:{spec.site}:{key}".encode()
        u = int.from_bytes(hashlib.blake2b(msg, digest_size=8).digest(),
                           "big") / 2.0 ** 64
        return u < spec.rate

    def check(self, site: str, key=None) -> None:
        """Fire the site's raise/hang fault for this event if the draw
        says so. ``key`` identifies the event deterministically (feeder
        sites pass the task sequence number so thread scheduling cannot
        reorder draws); ``None`` uses a per-site monotone counter —
        correct for the single-threaded scheduler sites. Every call is a
        FRESH draw, so a retried event may succeed (rate < 1)."""
        spec = self._by_site.get(site)
        if spec is None or spec.kind == "corrupt":
            return
        if key is None:
            key = self._counters[site] = self._counters.get(site, 0) + 1
        if not self._draw(spec, key):
            return
        self._record_fire(site, key)
        if spec.kind == "hang":
            # a bounded stall, not an exception: the watchdog (or the
            # caller's patience) decides whether this retires anything
            time.sleep(self.hang_s)
            return
        raise InjectedFault(site, key)

    def corrupt(self, site: str, key, batch: Dict) -> Dict:
        """Deterministically scramble ONE host batch: the integer content
        fields roll one position, same shapes and dtypes — a different
        (garbage) sample the downstream must degrade on, never crash on,
        and whose blast radius is exactly its own output row (per-row
        beam independence)."""
        spec = self._by_site.get(site)
        if spec is None or spec.kind != "corrupt" \
                or not self._draw(spec, key):
            return batch
        self._record_fire(site, key)
        out = dict(batch)
        for f in ("diff", "sub_token"):
            if f in out:
                out[f] = np.roll(out[f], 1, axis=-1)
        return out

    def summary(self) -> Dict[str, int]:
        """Fired-event counts per site (the machine record chaos rows and
        serve_metrics.json carry)."""
        with self._lock:
            return {site: int(n) for site, n in sorted(self.fired.items())}


def injector_from(cfg: FiraConfig) -> Optional[FaultInjector]:
    """The armed injector for ``cfg.inject_faults``, or None when no spec
    is armed (the zero-overhead default every driver branches on)."""
    if not cfg.inject_faults:
        return None
    return FaultInjector(parse_fault_specs(cfg.inject_faults),
                         hang_s=cfg.fault_hang_s)
