"""Set-up read off the program's own ring (``fira_tpu/utils/profiling.py``):
how the stretch from the process's start to the window's start was spent.

The stretch starts at ``profiling.process_start()`` (the OS's start time of
the process, on the ring's clock) and ends where the window starts, found
from the program's own roots by one fixed rule — the first of these that
the ring holds:

- ``serve.run``: the start of the last one (the serve driver's ``t_setup``;
  its warm-up burst is an earlier one);
- ``engine.run``: the end of the last one less the window (every drain
  driver closes its generator within one dispatch of the window's end);
- ``feeder.next``: the start of the ``counters["dispatches"]``-th last one
  (the train window opens each dispatch with one).

The window is the one the driver counted its counters over,
``counters["window_s"]`` (the ``ctx["window_s"]`` of every driver): a
context without it is no run's, and gives nothing.

``stat``:

- ``build_s``: seconds of the stretch in which jax was building a program,
  the union of its ``jax.trace``, ``jax.lower`` and ``xla.compile`` events
  (builds nest: never a sum of durations);
- ``cache_hit_share``: of the backend builds (``xla.compile``) begun in the
  stretch, the share in % that the persistent cache served (``cache: hit``);
- ``unspanned_s``: seconds of the stretch that no event of the ring covers
  on any thread: no span, root or build.

A program without ``process_start`` (the recorder before PR 37), a ring
without a root, a ring that has wrapped (it may have dropped events of the
stretch) or a stretch that ends before it starts: nothing to read, and the
metric is left out of the line.
"""

from typing import Dict, Iterable, Optional, Sequence, Tuple

BUILDS = ("jax.trace", "jax.lower", "xla.compile")


def union_s(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Seconds of [lo, hi] that at least one interval covers."""
    total, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def window_start(events: Sequence, counters: Dict) -> Optional[float]:
    window_s = counters.get("window_s")
    if window_s is None:
        return None
    for root in ("serve.run", "engine.run"):
        runs = [e for e in events if e.name == root]
        if runs:
            last = max(runs, key=lambda e: e.t_end)
            return last.t_start if root == "serve.run" \
                else last.t_end - float(window_s)
    nexts = sorted(e.t_start for e in events if e.name == "feeder.next")
    n = int(counters.get("dispatches") or 0)
    return nexts[-n] if 0 < n <= len(nexts) else None


def measure(events: Sequence, process_start: Optional[float],
            counters: Dict, stat: str, dropped: int = 0) -> Optional[float]:
    if process_start is None or dropped:
        return None
    end = window_start(events, counters)
    if end is None or end <= process_start:
        return None
    before = [e for e in events if e.t_start < end]
    if stat == "build_s":
        return union_s(((e.t_start, e.t_end) for e in before
                        if e.name in BUILDS), process_start, end)
    if stat == "unspanned_s":
        return (end - process_start) - union_s(
            ((e.t_start, e.t_end) for e in before), process_start, end)
    if stat == "cache_hit_share":
        builds = [e for e in before if e.name == "xla.compile"
                  and e.t_start >= process_start]
        if not builds:
            return None
        hits = sum((e.ids or {}).get("cache") == "hit" for e in builds)
        return 100.0 * hits / len(builds)
    raise ValueError(f"unknown stat {stat!r}")


def read(ctx: Dict, stat: str) -> Optional[float]:
    try:
        from fira_tpu.utils import profiling
    except ImportError:
        return None
    start = getattr(profiling, "process_start", None)
    if start is None:
        return None
    rec = profiling.RECORDER
    events = rec.events()
    return measure(events, start(), ctx.get("counters") or {}, stat,
                   rec.dropped())
