"""The program's one recorder: spans, compile counters, traces, the meter.

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
- the compile listener: one ``jax.monitoring`` registration records every
  backend compile as an ``xla.compile`` event in the same ring (ids: the
  program's name as jax gives it, ``jit(_step_fn)``, and whether the
  persistent cache served it; parent: the span open on that thread) and as
  the counters ``compiles`` / ``compile_s`` / ``cache_hits`` /
  ``cache_misses``. It COUNTS, always; ``analysis.sanitizer.CompileWatcher``
  is the one that GUARDS (it raises, and only under ``--sanitize``).
- :class:`Phases`: per span name ``count`` / ``total_s`` / ``max_s`` plus
  the compile counters, over the spans that closed while the object lived
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
``collect`` / ``counters`` ARE the program's interface; they act on
``RECORDER``, the process's one :class:`Recorder` (the class is where the
state lives; only a test that must not see the process's ring makes another).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import json
import threading
import time
import weakref
from typing import Dict, Iterator, List, NamedTuple, Optional

RING_EVENTS = 1 << 16   # ~1.5 h of serve rounds, ~40 min of drain dispatches
COMPILE_EVENT = "xla.compile"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Event(NamedTuple):
    """One closed span (or one compile). Times are ``time.perf_counter``
    seconds; ``parent_id`` 0 means no span was open on the thread."""

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
    """Totals of the spans that closed, and the compiles that ran, while
    this object lived (made by :meth:`Recorder.collect`)."""

    def __init__(self) -> None:
        self.spans: Dict[str, List[float]] = {}   # name -> [count, total, max]
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _add(self, name: str, seconds: float) -> None:
        row = self.spans.get(name)
        if row is None:
            self.spans[name] = [1, seconds, seconds]
        else:
            row[0] += 1
            row[1] += seconds
            if seconds > row[2]:
                row[2] = seconds

    def counters(self) -> Dict[str, float]:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

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
    collectors and the compile listener's state."""

    def __init__(self, maxlen: int = RING_EVENTS) -> None:
        self._ring: "collections.deque[Event]" = collections.deque(
            maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._collectors: "weakref.WeakSet[Phases]" = weakref.WeakSet()
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
            for c in self._collectors:
                c._add(name, t_end - t_start)

    # --- compiles (fed by the module's one jax.monitoring registration) ---

    def _on_cache_event(self, hit: bool) -> None:
        self._local.cache = "hit" if hit else "miss"
        with self._lock:
            for c in self._collectors:
                if hit:
                    c.cache_hits += 1
                else:
                    c.cache_misses += 1

    def _on_compile(self, seconds: float, fun_name: Optional[str]) -> None:
        now = time.perf_counter()
        span_id, parent_id = self._new_id(self._stack())
        ids = {"program": fun_name}
        cache = getattr(self._local, "cache", None)
        if cache is not None:       # the cache event just before, same thread
            del self._local.cache
            ids["cache"] = cache
        self._ring.append(Event(span_id, parent_id, COMPILE_EVENT,
                                now - seconds, now, threading.get_ident(),
                                ids))
        with self._lock:
            for c in self._collectors:
                c.compiles += 1
                c.compile_s += seconds

    # --- reading ---

    def collect(self) -> Phases:
        p = Phases()
        with self._lock:
            self._collectors.add(p)
        return p

    def events(self) -> List[Event]:
        return list(self._ring)

    def counters(self) -> Dict[str, float]:
        return self.total.counters()

    def dump(self, path: str) -> str:
        """The ring as JSON lines, oldest first; the first line says how
        many events the ring holds of how many were recorded."""
        events = self.events()
        recorded = sum(int(row[0]) for row in self.total.spans.values()) \
            + self.total.compiles
        with open(path, "w") as f:
            f.write(json.dumps({"recorder": {
                "clock": "perf_counter", "events": len(events),
                "recorded": recorded, **self.counters()}}) + "\n")
            for ev in events:
                f.write(json.dumps(ev._asdict(), default=str) + "\n")
        return path


RECORDER = Recorder()
span = RECORDER.span
begin = RECORDER.begin
events = RECORDER.events
dump = RECORDER.dump
collect = RECORDER.collect
counters = RECORDER.counters


@functools.lru_cache(maxsize=None)
def _annotation_type():
    """jax is imported, and the compile listener registered, at the first
    span and not with this module (importing it starts nothing)."""
    from jax.profiler import TraceAnnotation

    listen()
    return TraceAnnotation


_listening = False
_listen_lock = threading.Lock()


def listen() -> None:
    """Register the one compile listener with ``jax.monitoring`` (idempotent;
    the first span of the process calls it). jax hands the
    cache's hit and miss to plain-event listeners and the compile's duration
    and program name to duration listeners: both feed ``RECORDER``."""
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
            if event == _BACKEND_COMPILE:
                RECORDER._on_compile(float(seconds), kw.get("fun_name"))

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
