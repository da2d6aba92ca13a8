"""Runtime sanitizer: the dynamic twin of firacheck's static rules.

``--sanitize`` on the train/test CLIs arms four checks for the whole run:

- ``jax_debug_nans`` / ``jax_debug_infs``: every jitted program is
  re-checked for non-finite outputs (JAX re-runs op-by-op on a hit, so the
  raise points at the culprit primitive). Costs a sync per dispatch —
  this is a debugging mode, not a training mode.
- compile capture: ``jax_log_compiles`` routes one "Compiling <name>..."
  log record per XLA compilation through :class:`CompileWatcher`;
- :class:`CompileGuard`: the one-compile fixed-geometry contract
  (README Design notes; static twin: RETRACE). Call ``guard.step(label)``
  after each dispatch of a program; a label's FIRST step may compile
  (warmup), any compilation attributed to a later step of a known label
  raises :class:`RetraceError` with the captured program names.
- :class:`ThreadGuard`: the lock-discipline sanitizer (static twin:
  SHARED-MUT). While armed, the threaded shared structures — the ingest
  result cache / lex+hunk memos (ingest/cache.py), the fault injector's
  fired accounting (robust/faults.py), and the feeder's ordered-ready
  channel (data/feeder.py) — are constructed as GUARDED proxies: a
  mutation by a thread that does not hold the structure's owning lock
  raises :class:`LockDisciplineError` at the mutating line, and every
  lock acquisition records its ordering edges so an inversion (A→B
  observed after B→A) is flagged in ``ThreadGuard.inversions``.
  Unarmed, nothing is wrapped: the structures are plain dicts/Counters
  and the only cost is one is-None branch at construction — the
  CompileGuard zero-overhead discipline.
- :class:`LeakGuard`: the resource-lifecycle sanitizer (static twin:
  RES-LEAK). While armed, the acquire/release pairs the static rule
  reasons about are ALSO tracked at runtime — paged-block grants
  (decode/engine.py's refcounted allocator), pipeline threads
  (data/feeder.py start/join, robust/watchdog.py's deliberately
  abandoned dispatch thread), and the ingest process pool
  (ingest/cache.py). Every acquire records its acquire SITE
  (file:line in function); ``assert_clean()`` at engine/fleet/serve
  teardown raises :class:`LeakError` naming the acquire site of every
  resource still held — the dynamic proof of the bug class the static
  rule flags, and the chaos harness's leak oracle. The watchdog's
  abandoned thread is SANCTIONED via :meth:`LeakGuard.abandon_thread`
  (moved to the ``abandoned`` book with its reason, not counted as a
  leak) — an armed teardown distinguishes "leaked" from "abandoned by
  design". Unarmed, ``leak_guard()`` is None and every call site is
  one is-None branch — no record, no allocation, no lock.

The guard is deliberately per-label, not global: a fused-steps run
legitimately compiles the grouped program at step 1 and the per-step
program at the epoch tail; each label gets exactly one warmup dispatch.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import sys
import threading
from typing import Dict, Iterator, List, Optional, Tuple

_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",  # "Compiling <fn> with global shapes..."
    "jax._src.dispatch",           # "Finished XLA compilation of <fn>..."
)
_COMPILE_PREFIXES = ("Compiling ",)


class RetraceError(RuntimeError):
    """A post-warmup step triggered a fresh XLA compilation."""


class LockDisciplineError(RuntimeError):
    """A guarded shared structure was mutated by a thread that does not
    hold its owning lock (ThreadGuard; static twin: SHARED-MUT)."""


class LeakError(RuntimeError):
    """A tracked resource was still held at a teardown assert_clean()
    (LeakGuard; static twin: RES-LEAK). The message names every leaked
    resource's ACQUIRE site — the line that owes the release."""


def program_label(kind: str, tag: Optional[str] = None, group: int = 1) -> str:
    """Canonical label for one member of the (geometry x entrypoint x
    group-size) program family — the single format every driver labels and
    declares with, so the declared-family check can close over grouped
    programs too:

    ``program_label('train_step')``                    -> ``train_step``
    ``program_label('train_step', 'a16.e256.t8')``     -> ``train_step[a16.e256.t8]``
    ``program_label('grouped_step', 'a16.e256.t8', 8)``-> ``grouped_step[a16.e256.t8.g8]``
    ``program_label('grouped_step', None, 8)``         -> ``grouped_step[g8]``

    ``tag`` is a bucket geometry tag (data.buckets.geom_tag) or None;
    ``group`` > 1 is the stacked leading dim (fused K / accum A), so a
    grouped program at an undeclared (geom, K) raises at the dispatch that
    produced it, not as a mystery recompile."""
    mods = ".".join(m for m in (tag, f"g{group}" if group > 1 else None) if m)
    return f"{kind}[{mods}]" if mods else kind


class CompileWatcher(logging.Handler):
    """Counts XLA compilations by listening to jax's log_compiles records.

    Host-side only: reading ``count`` never touches the device. The
    messages are also kept (most recent first-N) so a RetraceError can
    name the program that recompiled.

    This one GUARDS: it exists only under the sanitizer, where
    :class:`CompileGuard` turns its count into a raise. The one that
    COUNTS, in every run, is ``utils/profiling.py``'s ``jax.monitoring``
    listener (``jax.trace`` / ``jax.lower`` / ``xla.compile`` events in
    the recorder's ring, with the program's name and the span it ran
    under, and the build counters of every ``phases`` block). The two are not fed from each other: the guard needs
    jax's log record (its message names shapes the RetraceError quotes,
    and ``jax_log_compiles`` is its arming switch), the listener needs
    neither.
    """

    def __init__(self, keep: int = 20) -> None:
        super().__init__(level=logging.DEBUG)
        self.count = 0
        # most-recent `keep` messages: a RetraceError must name the program
        # that JUST recompiled, not a warmup-era one
        self.messages: collections.deque = collections.deque(maxlen=keep)

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # a malformed record must never kill a train run
            return
        if msg.startswith(_COMPILE_PREFIXES):
            self.count += 1
            # first clause of the message names the compiled program
            self.messages.append(msg.split(" with ")[0])


@dataclasses.dataclass
class CompileGuard:
    """Per-program-label compile budget: 1 warmup dispatch, then zero.

    With a bucketed geometry family (data/buckets.py) every bucket's
    program gets its own label (``train_step[a16.e256.t8]``), and grouped
    dispatch (data/grouping.py) widens the family along the group-size
    axis (``grouped_step[a16.e256.t8.g8]`` — see :func:`program_label`):
    N programs warm up, then still zero post-warmup compiles. Drivers
    additionally :meth:`declare` the family after pre-warming — from then
    on a dispatch under an UNDECLARED label raises, so a geometry or
    group size outside the declared (geom, K) table (shape drift, a
    mis-packed batch) is caught at the step that produced it, not as a
    mystery recompile."""

    watcher: CompileWatcher
    _last_count: int = 0
    _extra: int = 0
    _seen: Dict[str, int] = dataclasses.field(default_factory=dict)
    _declared: Optional[set] = None

    def declare(self, labels) -> None:
        """Close the program family: after this, ``step()`` on a label not
        in the (cumulative) declared set raises RetraceError. Idempotent
        and additive — train and decode each declare their own labels."""
        self._declared = (self._declared or set()) | set(labels)

    @property
    def family_closed(self) -> bool:
        """True once declare() has closed the program family. Mid-run
        label additions (a respawned replica's fresh program set —
        robust/recovery.py) must declare ADDITIVELY into a closed family
        and must never be the FIRST declare: closing an open family
        around only the replacement's labels would outlaw every
        already-serving program."""
        return self._declared is not None

    def step_counting(self, label: str) -> int:
        """Attribute compilations since the last call to ``label``'s
        current dispatch and record them; returns the number of
        post-warmup compilations attributed to this dispatch."""
        new = self.watcher.count - self._last_count
        self._last_count = self.watcher.count
        steps = self._seen.get(label, 0)
        self._seen[label] = steps + 1
        extra = new if steps >= 1 else 0
        self._extra += extra
        return extra

    def step(self, label: str) -> None:
        """step_counting + raise: the drivers' per-dispatch check."""
        if self._declared is not None and label not in self._declared:
            raise RetraceError(
                f"sanitizer: program '{label}' is not in the declared "
                f"program family {sorted(self._declared)} — a geometry "
                f"outside the declared bucket table reached a dispatch "
                f"site (shape drift or a mis-packed batch)")
        extra = self.step_counting(label)
        if extra:
            recent = "; ".join(list(self.watcher.messages)[-min(extra, 5):])
            raise RetraceError(
                f"sanitizer: {extra} new XLA compilation(s) at step "
                f"{self._seen[label]} of program '{label}' — the "
                f"one-compile fixed-geometry invariant is broken (shape "
                f"drift or a re-constructed jit). Recent compiles: "
                f"{recent}")

    def compiles_after_warmup(self) -> int:
        """Total compilations attributed past some label's warmup step —
        0 on a healthy run (the compile-count regression test pins this
        without needing the raise path)."""
        return self._extra


# --------------------------------------------------------------------------
# ThreadGuard: the runtime lock-discipline sanitizer (static twin:
# SHARED-MUT / rules_concurrency.py)
# --------------------------------------------------------------------------

class _GuardedLock:
    """A lock (or Condition) wrapper that records held-set membership in
    the owning ThreadGuard's thread-local state and lock-order edges on
    every acquisition. All other attributes (``wait``, ``notify_all``,
    ...) pass through, so a Condition keeps working as a Condition."""

    def __init__(self, guard: "ThreadGuard", lock, name: str):
        self._tg_guard = guard
        self._tg_lock = lock
        self.name = name

    def acquire(self, *args, **kwargs):
        got = self._tg_lock.acquire(*args, **kwargs)
        if got:
            self._tg_guard._note_acquire(self.name)
        return got

    def release(self):
        self._tg_guard._note_release(self.name)
        self._tg_lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def __getattr__(self, attr):
        # Condition.wait/notify/notify_all etc. pass through; wait()
        # releases and reacquires the UNDERLYING lock internally — the
        # held-set entry stays put, which is correct: from this thread's
        # point of view the critical section never closed
        return getattr(self._tg_lock, attr)


class _GuardedMutations:
    """The ONE copy of the mutation-check machinery the guarded
    containers mix in (before their base in the MRO, so ``super()``
    resolves to the real container). Reads are unchecked — the
    sanitizer targets unsynchronized WRITES, the SHARED-MUT bug class.
    During base-class ``__init__`` (which may call ``update``/
    ``__setitem__``) the class-level ``_tg_guard = None`` default makes
    every check a no-op; ThreadGuard.wrap binds the instance attrs
    afterwards."""

    _tg_guard: "ThreadGuard" = None  # set by ThreadGuard.wrap
    _tg_lock: str = ""
    _tg_label: str = ""

    def _tg_check(self):
        if self._tg_guard is not None:
            self._tg_guard._check_mutation(self._tg_lock, self._tg_label)

    def __setitem__(self, k, v):
        self._tg_check()
        super().__setitem__(k, v)

    def __delitem__(self, k):
        self._tg_check()
        super().__delitem__(k)

    def pop(self, *a, **kw):
        self._tg_check()
        return super().pop(*a, **kw)

    def popitem(self, *a, **kw):
        self._tg_check()
        return super().popitem(*a, **kw)

    def clear(self):
        self._tg_check()
        super().clear()

    def update(self, *a, **kw):
        self._tg_check()
        super().update(*a, **kw)

    def setdefault(self, *a, **kw):
        self._tg_check()
        return super().setdefault(*a, **kw)


class _GuardedDict(_GuardedMutations, collections.OrderedDict):
    """Mutation-checked mapping proxy (order-preserving, so it stands in
    for both plain dicts and OrderedDicts)."""

    def move_to_end(self, *a, **kw):
        self._tg_check()
        super().move_to_end(*a, **kw)


class _GuardedCounter(_GuardedMutations, collections.Counter):
    """Mutation-checked Counter (``c[k] += 1`` routes through
    ``__setitem__``, exactly the unlocked-increment bug class)."""

    def subtract(self, *a, **kw):
        self._tg_check()
        super().subtract(*a, **kw)


class ThreadGuard:
    """Runtime lock-discipline sanitizer (docs/ANALYSIS.md "Runtime
    sanitizer"): declared shared structures mutate only under their
    owning lock, and lock-acquisition order is recorded to flag
    inversions.

    Usage (the pattern ingest/cache.py, robust/faults.py and
    data/feeder.py follow)::

        tg = thread_guard()           # None when unarmed
        if tg is not None:
            self._lock = tg.lock(self._lock, "IngestCache._lock")
            self._lru = tg.wrap(self._lru, self._lock, "IngestCache._lru")

    A ``wrap``-ped structure raises :class:`LockDisciplineError` on any
    mutation by a thread not currently holding the named lock. ``lock``
    additionally records ordering edges: whenever B is acquired while A
    is held the edge A→B is added, and if B→A was ever observed the
    inversion is recorded in :attr:`inversions` (recorded, not raised —
    a single observed inversion is a deadlock PRECONDITION, and the
    post-mortem wants the full pair list, not the first half of it).
    """

    def __init__(self):
        self._tls = threading.local()
        self._meta = threading.Lock()   # guards the order/violation books
        self._edges: Dict[Tuple[str, str], Tuple[str, str]] = {}
        self.inversions: List[Dict] = []
        self.violations: List[Dict] = []

    # --- held-set bookkeeping (per thread) ---

    def _held(self) -> List[str]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def _note_acquire(self, name: str) -> None:
        held = self._held()
        if held:
            with self._meta:
                for h in held:
                    if h == name:
                        continue
                    edge = (h, name)
                    if edge not in self._edges:
                        self._edges[edge] = (threading.current_thread().name,
                                             "")
                        if (name, h) in self._edges:
                            self.inversions.append({
                                "first": f"{name} -> {h}",
                                "then": f"{h} -> {name}",
                                "thread": threading.current_thread().name,
                            })
        held.append(name)

    def _note_release(self, name: str) -> None:
        held = self._held()
        # remove the LAST occurrence: locks nest, releases unwind
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                break

    def _check_mutation(self, lock_name: str, label: str) -> None:
        held = self._held()
        if lock_name in held:
            return
        record = {"structure": label, "lock": lock_name,
                  "thread": threading.current_thread().name,
                  "held": list(held)}
        with self._meta:
            self.violations.append(record)
        raise LockDisciplineError(
            f"sanitizer: `{label}` mutated without holding its owning "
            f"lock `{lock_name}` (thread {record['thread']}, held locks: "
            f"{record['held'] or 'none'}) — the SHARED-MUT discipline: "
            f"every write site takes the lock, or the lock protects "
            f"nothing")

    # --- declaration surface ---

    def lock(self, lock, name: str) -> _GuardedLock:
        """Wrap a threading.Lock/RLock/Condition so acquisitions are
        tracked. ``name`` should be unique per instance (the callers
        suffix ``@{id(self):x}``)."""
        return _GuardedLock(self, lock, name)

    def wrap(self, obj, lock, label: str):
        """Wrap a shared structure so mutations require holding ``lock``
        (a :meth:`lock`-wrapped GuardedLock, or its name). Supports the
        mapping/Counter shapes the armed structures actually are;
        anything else is returned unwrapped (never break a run over an
        unguardable type)."""
        lock_name = lock.name if isinstance(lock, _GuardedLock) else str(lock)
        if isinstance(obj, collections.Counter):
            new: object = _GuardedCounter(obj)
        elif isinstance(obj, dict):
            new = _GuardedDict(obj)
        else:
            return obj
        new._tg_guard = self
        new._tg_lock = lock_name
        new._tg_label = label
        return new

    def summary(self) -> Dict:
        with self._meta:
            return {"violations": len(self.violations),
                    "lock_order_edges": len(self._edges),
                    "inversions": list(self.inversions)}


# --------------------------------------------------------------------------
# LeakGuard: the runtime resource-lifecycle sanitizer (static twin:
# RES-LEAK / rules_resources.py)
# --------------------------------------------------------------------------

class LeakGuard:
    """Runtime acquire/release ledger (docs/ANALYSIS.md "Runtime
    sanitizer"): every tracked acquire records its acquire site, every
    release retires the record, and :meth:`assert_clean` at teardown
    raises :class:`LeakError` naming the acquire site of whatever is
    still held.

    Usage (the pattern decode/engine.py, data/feeder.py and
    ingest/cache.py follow)::

        self._leaks = leak_guard()    # None when unarmed
        ...
        if self._leaks is not None:
            self._leaks.note_acquire("block", key, what="paged block 3")

    Resources are keyed ``(kind, key)`` where the caller's key embeds
    ``@{id(owner):x}`` so two engines never alias each other's blocks.
    Threads get dedicated helpers (:meth:`track_thread` /
    :meth:`note_joined` / :meth:`abandon_thread`) keyed by the thread
    object, so track and join sites never have to agree on a string.
    ``abandon_thread`` is the watchdog's sanction: a deliberately
    abandoned dispatch thread moves to the :attr:`abandoned` book with
    its reason instead of counting as a leak.
    """

    def __init__(self) -> None:
        self._meta = threading.Lock()
        self._open: Dict[Tuple[str, str], Dict] = {}
        self.abandoned: List[Dict] = []
        self.acquires = 0
        self.releases = 0
        # releases with no matching acquire: 0 on a healthy run — a
        # nonzero count means a double-release or an untracked acquire
        self.unmatched_releases = 0

    @staticmethod
    def _site(skip: int) -> str:
        """``file.py:line in func`` for the frame ``skip`` levels above
        the caller of this method — the acquire site a LeakError names."""
        f = sys._getframe(skip + 1)
        return (f"{os.path.basename(f.f_code.co_filename)}:{f.f_lineno} "
                f"in {f.f_code.co_name}")

    @staticmethod
    def _thread_key(thread: threading.Thread) -> str:
        return f"{thread.name}@{id(thread):x}"

    # --- the ledger ---

    def note_acquire(self, kind: str, key: str, what: str = "",
                     site: Optional[str] = None) -> None:
        site = site if site is not None else self._site(1)
        record = {"kind": kind, "key": str(key), "what": what or kind,
                  "site": site,
                  "thread": threading.current_thread().name}
        with self._meta:
            self.acquires += 1
            self._open[(kind, str(key))] = record

    def note_release(self, kind: str, key: str) -> None:
        with self._meta:
            self.releases += 1
            if self._open.pop((kind, str(key)), None) is None:
                self.unmatched_releases += 1

    def track_thread(self, thread: threading.Thread,
                     what: str = "") -> None:
        self.note_acquire("thread", self._thread_key(thread),
                          what=what or f"thread '{thread.name}'",
                          site=self._site(1))

    def note_joined(self, thread: threading.Thread) -> None:
        self.note_release("thread", self._thread_key(thread))

    def abandon_thread(self, thread: threading.Thread,
                       reason: str) -> None:
        """Sanction a deliberately unjoined thread (the watchdog's
        abandoned dispatch): the record moves to :attr:`abandoned` with
        its reason and no longer counts as held."""
        with self._meta:
            rec = self._open.pop(("thread", self._thread_key(thread)),
                                 None)
            if rec is not None:
                rec["reason"] = reason
                self.abandoned.append(rec)

    # --- the teardown oracle ---

    def open_resources(self) -> List[Dict]:
        with self._meta:
            return list(self._open.values())

    def assert_clean(self, scope: str = "teardown") -> None:
        """Raise :class:`LeakError` naming the acquire site of every
        resource still held (sanctioned abandons excluded). The
        engine/fleet/serve teardown call — the dynamic twin of a
        RES-LEAK finding."""
        leaks = self.open_resources()
        if not leaks:
            return
        sites = "; ".join(
            f"{r['what']} ({r['kind']} '{r['key']}') acquired at "
            f"{r['site']}" for r in leaks[:5])
        more = f" (+{len(leaks) - 5} more)" if len(leaks) > 5 else ""
        raise LeakError(
            f"sanitizer: {len(leaks)} resource(s) still held at {scope}: "
            f"{sites}{more} — every acquire owes a release on every exit "
            f"path (RES-LEAK discipline)")

    def summary(self) -> Dict:
        with self._meta:
            return {"acquires": self.acquires,
                    "releases": self.releases,
                    "open": len(self._open),
                    "abandoned": len(self.abandoned),
                    "unmatched_releases": self.unmatched_releases}


# process-global arming point: the threaded structures are constructed
# deep inside worker machinery, so they look the guard up here instead
# of threading it through every constructor. None = unarmed = nothing
# is ever wrapped (the zero-overhead contract).
_THREAD_GUARD: Optional[ThreadGuard] = None
# same contract for the resource ledger: None = unarmed = every tracked
# call site is one is-None branch and nothing is recorded.
_LEAK_GUARD: Optional[LeakGuard] = None


def leak_guard() -> Optional[LeakGuard]:
    """The armed LeakGuard, or None. Captured at construction time by
    the tracked owners (FiraDecodeEngine, Feeder, IngestExecutor) so an
    owner's whole lifecycle reports to ONE ledger even if arming flips
    mid-run."""
    return _LEAK_GUARD


@contextlib.contextmanager
def leak_guarding(guard: Optional[LeakGuard] = None
                  ) -> Iterator[LeakGuard]:
    """Arm a LeakGuard for the block (tests / chaos harness; jax-free).
    Owners constructed INSIDE the block are tracked; pre-existing ones
    are not (arming is a construction-time choice, like ThreadGuard)."""
    global _LEAK_GUARD
    prev = _LEAK_GUARD
    lg = guard if guard is not None else LeakGuard()
    _LEAK_GUARD = lg
    try:
        yield lg
    finally:
        _LEAK_GUARD = prev


def thread_guard() -> Optional[ThreadGuard]:
    """The armed ThreadGuard, or None. Called at construction time by
    the guarded classes (IngestCache, FaultInjector, Feeder)."""
    return _THREAD_GUARD


def guard_structures(owner, lock, structures, lock_label: str = "_lock"):
    """Construction-time arming hook for the guarded classes
    (IngestCache/LexMemo/HunkMemo, FaultInjector, Feeder): returns
    ``(lock, [structures...])`` untouched when no ThreadGuard is armed
    (one is-None branch, zero steady-state overhead), else the guarded
    lock plus mutation-checked proxies. ``structures`` is a list of
    ``(structure, label)`` pairs; ``lock_label`` is the owner's REAL
    attribute name for the lock (Feeder's is ``_cond``) so a violation
    message points at an attribute that exists; names are suffixed
    ``@id`` so two instances never alias each other's held-lock
    authority."""
    tg = thread_guard()
    if tg is None:
        return lock, [s for s, _label in structures]
    name = f"{type(owner).__name__}.{lock_label}@{id(owner):x}"
    glock = tg.lock(lock, name)
    return glock, [tg.wrap(s, glock,
                           f"{type(owner).__name__}.{label}@{id(owner):x}")
                   for s, label in structures]


@contextlib.contextmanager
def thread_guarding(guard: Optional[ThreadGuard] = None
                    ) -> Iterator[ThreadGuard]:
    """Arm a ThreadGuard for the block (tests; jax-free — this touches
    no jax config). Structures constructed INSIDE the block are guarded;
    pre-existing ones are not (arming is a construction-time choice)."""
    global _THREAD_GUARD
    prev = _THREAD_GUARD
    tg = guard if guard is not None else ThreadGuard()
    _THREAD_GUARD = tg
    try:
        yield tg
    finally:
        _THREAD_GUARD = prev


@contextlib.contextmanager
def compile_capture() -> Iterator[CompileWatcher]:
    """Arm jax_log_compiles and attach the counting handler; restores
    both on exit. Usable standalone (tests) or via :func:`sanitize`."""
    import jax

    watcher = CompileWatcher()
    loggers = [logging.getLogger(name) for name in _COMPILE_LOGGERS]
    prev_levels = [lg.level for lg in loggers]
    prev_flag = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(watcher)
        # the record must reach our handler even under a quiet root config;
        # the EFFECTIVE level is what gates isEnabledFor (an unset logger
        # inherits a root ERROR config and would drop WARNING records)
        if lg.getEffectiveLevel() > logging.WARNING:
            lg.setLevel(logging.WARNING)
    try:
        yield watcher
    finally:
        for lg, lvl in zip(loggers, prev_levels):
            lg.removeHandler(watcher)
            lg.setLevel(lvl)
        jax.config.update("jax_log_compiles", prev_flag)


def arm(enabled: bool = True, *, nans: bool = True, infs: bool = True,
        ) -> Optional[CompileGuard]:
    """Process-lifetime arming — CLI-ONLY (fira_tpu/cli.py). Mutates global
    jax config and logger state with no teardown, which is fine exactly
    when the process dies with the run. Library callers and tests must use
    the :func:`sanitize` context manager and pass the resulting guard into
    train()/run_test() instead."""
    if not enabled:
        return None
    import jax

    jax.config.update("jax_debug_nans", nans)
    jax.config.update("jax_debug_infs", infs)
    jax.config.update("jax_log_compiles", True)
    watcher = CompileWatcher()
    for name in _COMPILE_LOGGERS:
        lg = logging.getLogger(name)
        lg.addHandler(watcher)
        if lg.getEffectiveLevel() > logging.WARNING:
            lg.setLevel(logging.WARNING)
    # lock-discipline + resource-lifecycle sanitizers: process-lifetime
    # arming like the rest of this function — threaded shared structures
    # and resource owners constructed from here on are guarded
    # (docstring above; thread_guarding()/leak_guarding() are the scoped
    # alternatives for library callers/tests)
    global _THREAD_GUARD, _LEAK_GUARD
    _THREAD_GUARD = ThreadGuard()
    _LEAK_GUARD = LeakGuard()
    return CompileGuard(watcher)


@contextlib.contextmanager
def sanitize(enabled: bool = True, *, nans: bool = True, infs: bool = True,
             ) -> Iterator[Optional[CompileGuard]]:
    """Arm the full sanitizer; yields a CompileGuard (None when disabled).

    The drivers thread the guard through their dispatch sites:
    ``train/loop.py`` labels per-step/grouped/dev programs,
    ``decode/runner.py`` labels the beam program.
    """
    if not enabled:
        yield None
        return
    import jax

    prev_nans = jax.config.jax_debug_nans
    prev_infs = jax.config.jax_debug_infs
    jax.config.update("jax_debug_nans", nans)
    jax.config.update("jax_debug_infs", infs)
    try:
        with compile_capture() as watcher, thread_guarding(), \
                leak_guarding():
            yield CompileGuard(watcher)
    finally:
        jax.config.update("jax_debug_nans", prev_nans)
        jax.config.update("jax_debug_infs", prev_infs)
