"""The program's one recorder: spans, build counters, traces, the meter.

The reference has no profiling at all — an unused ``import time`` and
step-rate prints (/root/reference/run_model.py:114-115,181-182). Here one
module owns every wall-clock read the layers take of themselves:

- ``span(name, **ids)``: a context manager at a LAYER BOUNDARY. It enters
  ``jax.profiler.TraceAnnotation(name, **ids)`` — so whenever a profiler
  session runs, the span is an event on ``/host:CPU`` of the ``.xplane.pb``,
  on the device trace's clock — and appends one :class:`Event` to a bounded
  in-memory ring when it closes (parent from a thread-local stack). The
  ring is always on, like ``EngineStats``; what keeps it free is
  GRANULARITY: a span per round, dispatch, chunk or set-up phase, never
  per row, token or request (a request's life stays the stamps on its
  ``RequestRecord``). ``begin(name)`` is the same for a root that outlives a
  ``yield`` (``SlotEngine.run``): ring only, and the thread's parent stack
  is held only inside ``with root:`` stretches, never across the yield.
- the build listener: one ``jax.monitoring`` registration records each of
  jax's three build stages of a program as an event in the same ring —
  ``jax.trace`` (Python tracing to a jaxpr), ``jax.lower`` (jaxpr to an MLIR
  module) and ``xla.compile`` (the backend build: ``compile_or_get_cached``,
  so a COMPILE OR a persistent-cache LOAD; ids ``cache: hit|miss`` where the
  cache was asked and ``load_s``, the cache's read, where it served) — ids:
  the program's name as jax gives it (``_step_fn``, ``jit__step_fn``,
  ``jit(_step_fn)``), parent: the span open on that thread. jax reports a
  stage once a build, never a call: a warm loop records none. Builds nest
  (tracing ``jit_multi_step`` traces the jits inside it), so a time over
  build events is a union of intervals, never a sum of durations. The
  counters ``compiles`` / ``compile_s`` (every backend build, cache-served
  or compiled: ``cache_misses`` is the count that compiled, ``cache_hits``
  the count loaded, ``cache_load_s`` the loads' seconds), ``traces`` /
  ``trace_s`` and ``lowers`` / ``lower_s`` (sums of durations: nested
  traces count inside their parents too). It COUNTS, always;
  ``analysis.sanitizer.CompileWatcher`` is the one that GUARDS (it raises,
  and only under ``--sanitize``).
- two clocks beside the ring's (``time.perf_counter``): the process's start
  read once from the OS (:func:`process_start`), so a reader can tell how
  much of start-up no event covers; and the offset to the profiler's clock
  (:func:`profiler_offset_ns`), so a ring time lines up with an
  ``.xplane.pb`` by arithmetic. Both are in ``dump``'s header.
- :class:`Phases`: per span name ``count`` / ``total_s`` / ``max_s`` plus
  the build counters, over the spans that closed while the object lived
  — the ``phases`` block of ``EngineStats.summary()`` and
  ``ServeStats.summary()``, so a stats reset resets its phases with it.
- ``events()`` / ``dump(path)``: a copy of the ring / the ring as JSON
  lines (``cli`` writes ``<out_dir>/spans.jsonl``).
- ``trace(log_dir)``: a ``jax.profiler`` session around a block, producing
  the XPlane trace the spans land in;
- ``step_annotation(step)``: names each training step in the trace so device
  timelines line up with host steps;
- ``Meter``: windowed wall-clock meter for steady-state throughput
  (items/sec) and step latency percentiles, excluding warm-up/compile steps.
- ``stopwatch()``: a span's clock without its record, for a caller whose
  unit of work is a request (the serve path's per-request feeder).

One entry point a need: ``span`` for a block (``with span(...)``) or a whole
function (``@span(...)``: a fresh span each call), ``begin`` for the root
that outlives a ``yield``, ``stopwatch`` for a time that is no layer
boundary. The module-level ``span`` / ``begin`` / ``events`` / ``dump`` /
``collect`` / ``counters`` / ``process_start`` / ``profiler_offset_ns`` ARE
the program's interface; the ring's act on ``RECORDER``, the process's one
:class:`Recorder` (the class is where the state lives; only a test that
must not see the process's ring makes another).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import json
import os
import threading
import time
import weakref
from typing import Dict, Iterator, List, NamedTuple, Optional

RING_EVENTS = 1 << 16   # ~1.5 h of serve rounds, ~40 min of drain dispatches
TRACE_EVENT = "jax.trace"
LOWER_EVENT = "jax.lower"
COMPILE_EVENT = "xla.compile"
BUILD_EVENTS = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)

# jax's monitoring names of the three build stages -> the ring's names
_BUILD_STAGES = {"/jax/core/compile/jaxpr_trace_duration": TRACE_EVENT,
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     LOWER_EVENT,
                 "/jax/core/compile/backend_compile_duration": COMPILE_EVENT}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class Event(NamedTuple):
    """One closed span (or one build stage of a program). Times are
    ``time.perf_counter`` seconds; ``parent_id`` 0 means no span was open
    on the thread."""

    span_id: int
    parent_id: int
    name: str
    t_start: float
    t_end: float
    thread: int
    ids: Optional[Dict]

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start


class Phases:
    """Totals of the spans that closed, and the builds that ran, while this
    object lived (made by :meth:`Recorder.collect`). ``compiles`` counts
    every backend build, the cache's loads among them (``cache_hits``);
    ``cache_misses`` those that compiled."""

    def __init__(self) -> None:
        self.spans: Dict[str, List[float]] = {}   # name -> [count, total, max]
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_load_s = 0.0
        self.traces = 0
        self.trace_s = 0.0
        self.lowers = 0
        self.lower_s = 0.0

    def _add(self, name: str, seconds: float) -> None:
        row = self.spans.get(name)
        if row is None:
            self.spans[name] = [1, seconds, seconds]
        else:
            row[0] += 1
            row[1] += seconds
            if seconds > row[2]:
                row[2] = seconds

    def _build(self, name: str, seconds: float,
               load_s: Optional[float]) -> None:
        if name == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds
            self.cache_load_s += load_s or 0.0
        elif name == TRACE_EVENT:
            self.traces += 1
            self.trace_s += seconds
        else:
            self.lowers += 1
            self.lower_s += seconds

    def counters(self) -> Dict[str, float]:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "cache_load_s": round(self.cache_load_s, 6),
                "traces": self.traces,
                "trace_s": round(self.trace_s, 6),
                "lowers": self.lowers,
                "lower_s": round(self.lower_s, 6)}

    def summary(self) -> Dict:
        """Sorted by name: thread timing decides which span closes first,
        and identical runs must not differ in key order."""
        return {**{name: {"count": int(c), "total_s": round(t, 6),
                          "max_s": round(m, 6)}
                   for name, (c, t, m) in sorted(self.spans.items())},
                **self.counters()}


class _Span(contextlib.ContextDecorator):
    """One open span; ``duration_s`` is valid once it has closed. As a
    decorator it opens a fresh span around each call of the function."""

    __slots__ = ("_rec", "name", "ids", "span_id", "parent_id", "t_start",
                 "t_end", "_ann")

    def __init__(self, rec: "Recorder", name: str, ids: Dict) -> None:
        self._rec, self.name, self.ids = rec, name, ids
        self.t_start = self.t_end = 0.0

    def _recreate_cm(self) -> "_Span":
        return _Span(self._rec, self.name, self.ids)

    def __enter__(self) -> "_Span":
        stack = self._rec._stack()
        self.span_id, self.parent_id = self._rec._new_id(stack)
        stack.append(self.span_id)
        self._ann = _annotation_type()(self.name, **self.ids)
        self._ann.__enter__()
        self.t_start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t_end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._record(self.span_id, self.parent_id, self.name,
                          self.t_start, self.t_end, self.ids)

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start

    def note(self, **ids) -> None:
        """Ids learned while the span is open (what it built, not what it
        was asked for): on the ring's event, not on the trace annotation,
        which took its ids when it opened."""
        self.ids = {**self.ids, **ids}


class _Root:
    """A span opened by :meth:`Recorder.begin`: recorded at :meth:`end`,
    and the parent of the spans that open inside ``with root:``."""

    __slots__ = ("_rec", "name", "ids", "span_id", "parent_id", "t_start",
                 "_open")

    def __init__(self, rec: "Recorder", name: str, ids: Dict) -> None:
        self._rec, self.name, self.ids = rec, name, ids
        self.span_id, self.parent_id = rec._new_id(rec._stack())
        self._open = True
        self.t_start = time.perf_counter()

    def __enter__(self) -> "_Root":
        self._rec._stack().append(self.span_id)
        return self

    def __exit__(self, *exc) -> None:
        self._rec._stack().pop()

    def end(self) -> None:
        if self._open:
            self._open = False
            self._rec._record(self.span_id, self.parent_id, self.name,
                              self.t_start, time.perf_counter(), self.ids)


class _Stopwatch:
    __slots__ = ("t_start", "t_end")

    def __enter__(self) -> "_Stopwatch":
        self.t_start = self.t_end = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t_end = time.perf_counter()

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start

    def note(self, **_ids) -> None:
        pass


def stopwatch(_name: str = "", **_ids) -> _Stopwatch:
    """``span``'s clock without its annotation or its ring entry."""
    return _Stopwatch()


class Recorder:
    """The ring, the thread-local parent stacks, the live :class:`Phases`
    collectors and the build listener's state."""

    def __init__(self, maxlen: int = RING_EVENTS) -> None:
        self._ring: "collections.deque[Event]" = collections.deque(
            maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._collectors: "weakref.WeakSet[Phases]" = weakref.WeakSet()
        self.recorded = 0               # events appended, the dropped too
        self.total = self.collect()     # the process's own, never dropped

    # --- spans ---

    def span(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids)

    def begin(self, name: str, **ids) -> _Root:
        return _Root(self, name, ids)

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _new_id(self, stack: List[int]):
        """-> (a fresh span id, its parent: the span open on this thread)."""
        return next(self._ids), (stack[-1] if stack else 0)

    def _record(self, span_id: int, parent_id: int, name: str,
                t_start: float, t_end: float, ids: Optional[Dict]) -> None:
        self._ring.append(Event(span_id, parent_id, name, t_start, t_end,
                                threading.get_ident(), ids or None))
        with self._lock:
            self.recorded += 1
            for c in self._collectors:
                c._add(name, t_end - t_start)

    # --- builds (fed by the module's one jax.monitoring registration) ---

    def _on_cache_event(self, hit: bool) -> None:
        self._local.cache = "hit" if hit else "miss"
        with self._lock:
            for c in self._collectors:
                if hit:
                    c.cache_hits += 1
                else:
                    c.cache_misses += 1

    def _on_cache_load(self, seconds: float) -> None:
        self._local.load_s = seconds

    def _on_build(self, name: str, seconds: float,
                  fun_name: Optional[str]) -> None:
        """One build stage that just ended on this thread, ``seconds``
        long. A backend build takes the cache's verdict and load time that
        jax reported inside it, on the same thread, just before."""
        now = time.perf_counter()
        span_id, parent_id = self._new_id(self._stack())
        ids = {"program": fun_name}
        load_s = None
        if name == COMPILE_EVENT:
            cache = self._local.__dict__.pop("cache", None)
            load_s = self._local.__dict__.pop("load_s", None)
            if cache is not None:
                ids["cache"] = cache
            if load_s is not None:
                ids["load_s"] = load_s
        self._ring.append(Event(span_id, parent_id, name, now - seconds, now,
                                threading.get_ident(), ids))
        with self._lock:
            self.recorded += 1
            for c in self._collectors:
                c._build(name, seconds, load_s)

    # --- reading ---

    def collect(self) -> Phases:
        p = Phases()
        with self._lock:
            self._collectors.add(p)
        return p

    def events(self) -> List[Event]:
        return list(self._ring)

    def dropped(self) -> int:
        """Events the bounded ring let go (0 until it has wrapped)."""
        return max(0, self.recorded - len(self._ring))

    def counters(self) -> Dict[str, float]:
        return self.total.counters()

    def dump(self, path: str) -> str:
        """The ring as JSON lines, oldest first. The first line says how
        many events the ring holds of how many were recorded, when the
        process started on the ring's clock (``process_start``, seconds)
        and the profiler's clock less the ring's (``profiler_offset_ns``):
        an event at ring time ``t`` is at ``t * 1e9 + profiler_offset_ns``
        on the profiler's clock, which an ``.xplane.pb`` gives relative to
        its session's start (plane ``Task Environment``, stat
        ``profile_start_time``)."""
        events = self.events()
        with open(path, "w") as f:
            f.write(json.dumps({"recorder": {
                "clock": "perf_counter", "events": len(events),
                "recorded": self.recorded,
                "process_start": process_start(),
                "profiler_offset_ns": profiler_offset_ns(),
                **self.counters()}}) + "\n")
            for ev in events:
                f.write(json.dumps(ev._asdict(), default=str) + "\n")
        return path


def _offset_ns(other_ns) -> int:
    """``other_ns()`` less the ring's clock, in ns, from the tightest of a
    few bracketing reads: the ring's clock, the other, the ring's again."""
    best = None
    for _ in range(16):
        a = time.perf_counter_ns()
        o = other_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, o - (a + b) // 2)
    return best[1]


@functools.lru_cache(maxsize=None)
def process_start() -> Optional[float]:
    """When this process started, on the ring's clock (``perf_counter``
    seconds), read once from the OS: ``/proc/self/stat``'s start time
    (clock ticks after boot, so to 1/CLK_TCK, 10 ms) against
    ``CLOCK_BOOTTIME``. None where the OS does not say (no ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            after_name = f.read().rsplit(")", 1)[1].split()
        ticks = int(after_name[19])          # field 22, starttime
        hz = os.sysconf("SC_CLK_TCK")
        boot = _offset_ns(lambda: time.clock_gettime_ns(time.CLOCK_BOOTTIME))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return ticks / hz - boot / 1e9


def profiler_offset_ns() -> int:
    """The profiler's clock less the ring's, in ns, sampled now (the two
    may slew apart over hours). The profiler stamps host and device events
    with the wall clock (``absl::GetCurrentTimeNanos``)."""
    return _offset_ns(time.time_ns)


RECORDER = Recorder()
span = RECORDER.span
begin = RECORDER.begin
events = RECORDER.events
dump = RECORDER.dump
collect = RECORDER.collect
counters = RECORDER.counters


@functools.lru_cache(maxsize=None)
def _annotation_type():
    """jax is imported, and the build listener registered (if the entry
    point's ``utils/startup`` call has not already), at the first span and
    not with this module (importing it starts nothing)."""
    from jax.profiler import TraceAnnotation

    listen()
    return TraceAnnotation


_listening = False
_listen_lock = threading.Lock()


def listen() -> None:
    """Register the one build listener with ``jax.monitoring`` (idempotent;
    the entry points' ``utils/startup`` call makes it, else the first
    span). jax hands the cache's hit and miss to plain-event listeners, and
    each build stage's duration and program name, and the cache's load
    time, to duration listeners: both feed ``RECORDER``."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring

        def on_event(event: str, **_kw) -> None:
            if event == _CACHE_HIT:
                RECORDER._on_cache_event(True)
            elif event == _CACHE_MISS:
                RECORDER._on_cache_event(False)

        def on_duration(event: str, seconds: float, **kw) -> None:
            stage = _BUILD_STAGES.get(event)
            if stage is not None:
                RECORDER._on_build(stage, float(seconds), kw.get("fun_name"))
            elif event == _CACHE_LOAD:
                RECORDER._on_cache_load(float(seconds))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _listening = True


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Profile everything inside the block to ``log_dir`` (no-op if None)."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def step_annotation(step: int):
    """Label the current host step on the device timeline."""
    import jax

    return jax.profiler.StepTraceAnnotation("train_step", step_num=step)


@dataclasses.dataclass
class Meter:
    """Steady-state throughput/latency meter with feed-stall attribution.

    ``warmup`` leading intervals are discarded (they contain compilation).
    Call ``tick(n_items, stall_s=...)`` once per completed step after
    syncing with the device — ``stall_s`` is how much of the interval the
    host spent blocked waiting on the input feed (data/feeder.py hands it
    per batch); read ``summary()`` at the end. ``feed_stall_frac`` is the
    denominator the next perf round needs: the share of steady-state wall
    clock that was feed, not device compute. ``pause()`` .. ``start()``
    brackets what is not train time: a dev-eval pass, and between epochs
    the checkpoint save and the next feeder's pipeline fill (train/loop.py
    restarts the meter at the epoch's first batch).
    """

    warmup: int = 1
    _intervals: List[float] = dataclasses.field(default_factory=list)
    _items: List[int] = dataclasses.field(default_factory=list)
    _stalls: List[float] = dataclasses.field(default_factory=list)
    _last: Optional[float] = None
    _seen: int = 0

    def start(self) -> None:
        self._last = time.perf_counter()

    def pause(self) -> None:
        """Exclude the time until the next start() (e.g. a dev-eval pass)."""
        self._last = None

    @property
    def paused(self) -> bool:
        return self._last is None

    def tick(self, n_items: int = 1, stall_s: float = 0.0) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                self._intervals.append(now - self._last)
                self._items.append(n_items)
                self._stalls.append(stall_s)
        self._last = now

    def summary(self) -> Dict[str, float]:
        if not self._intervals:
            return {"steps": 0, "items_per_sec": 0.0,
                    "mean_step_ms": 0.0, "p50_step_ms": 0.0,
                    "p99_step_ms": 0.0, "feed_stall_frac": 0.0,
                    "feed_stall_ms_per_step": 0.0}
        total_t = sum(self._intervals)
        xs = sorted(self._intervals)

        def pct(p: float) -> float:
            return xs[min(len(xs) - 1, int(p * len(xs)))]

        total_stall = sum(self._stalls)
        return {
            "steps": float(len(xs)),
            "items_per_sec": sum(self._items) / total_t,
            "mean_step_ms": 1e3 * total_t / len(xs),
            "p50_step_ms": 1e3 * pct(0.50),
            "p99_step_ms": 1e3 * pct(0.99),
            # share of measured wall clock the host spent blocked on the
            # input feed (assembly + transfer not hidden behind compute)
            "feed_stall_frac": min(1.0, total_stall / total_t),
            "feed_stall_ms_per_step": 1e3 * total_stall / len(xs),
        }
