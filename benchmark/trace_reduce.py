"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

What a TPU v5e trace holds (looked at by hand, PERF.md section 3): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per
executed HLO op (the ``while`` of a ``lax.scan`` is an event that spans its
body's ops, so times are taken as a UNION of intervals, never a sum), and
whose line ``XLA Modules`` has one event per executed program, named
``jit_<function>(<fingerprint>)``. The host's threads are lines of the plane
``/host:CPU``; a ``jax.profiler.TraceAnnotation`` is an event there under
its own name, on the same clock as the device's events.

The reduction is split so that it can be checked on a hand-made event
list: ``load`` reads the file into plain tuples, ``reduce_events`` computes.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]           # (name, start_ns, duration_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
WINDOW_SPAN = "bench.window"
OP_NAME_CHARS = 160    # an HLO op's event name is its whole instruction


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_span_names: Iterable[str]) -> Dict:
    """-> {"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "host": [Event]} — host events filtered to the benchmark's own spans."""
    from jax.profiler import ProfileData

    keep = set(host_span_names) | {WINDOW_SPAN}
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key].extend((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)) for ev in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events if ev.name in keep)
    return {"devices": devices, "host": host}


def union_intervals(events: Sequence[Event], lo: Optional[int] = None,
                    hi: Optional[int] = None) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals, clipped to [lo, hi)."""
    spans = []
    for _n, s, d in events:
        e = s + d
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            spans.append((s, e))
    spans.sort()
    out: List[Tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _covering(host: Sequence[Event], s: int, e: int) -> str:
    """Name of the host span that covers most of [s, e); innermost (the
    shortest) on ties; 'host:untraced' when none overlaps."""
    best, best_key = "host:untraced", (0, 0)
    for name, hs, hd in host:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov > 0 and (ov, -hd) > best_key:
            best, best_key = name, (ov, -hd)
    return best


def reduce_events(ops: Sequence[Event], modules: Sequence[Event],
                  host: Sequence[Event], top: int = 10) -> Dict:
    """Busy union, idle share, per-module times and the breakdown of one
    device. The traced window is the ``bench.window`` host span where the
    trace has one, else the extent of the device's own events."""
    win = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if win:
        lo, hi = min(w[0] for w in win), max(w[1] for w in win)
    elif ops:
        lo = min(s for _n, s, _d in ops)
        hi = max(s + d for _n, s, d in ops)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                "device_ops": [], "idle_gaps": []}
    busy = union_intervals(ops, lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    # per-program time: modules whose whole run lies inside the window. A
    # program that is running when the device's trace begins or ends is
    # recorded cut off there (PERF.md section 3), so a module that touches
    # either end of the device's own events is left out.
    everything = list(ops) + list(modules)
    first = min((s for _n, s, _d in everything), default=lo)
    last = max((s + d for _n, s, d in everything), default=hi)
    mods: Dict[str, Dict[str, float]] = {}
    for name, s, d in modules:
        if s >= lo and s + d <= hi and s > first and s + d < last:
            m = mods.setdefault(name.split("(")[0], {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d / 1e9
    # ops by total time; a loop's event spans its body, so leave out any op
    # that holds other ops (its time is theirs)
    starts = sorted((s, s + d) for _n, s, d in ops)
    by_name: Dict[str, float] = {}
    keys = [s for s, _e in starts]
    for name, s, d in ops:
        e = s + d
        if e <= lo or s >= hi or d <= 0:
            continue
        i = bisect.bisect_right(keys, s)
        holds = i < len(starts) and starts[i][0] < e and starts[i][1] <= e
        if not holds:
            by_name[name] = by_name.get(name, 0.0) + d / 1e9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    # idle gaps, attributed to what the host was doing
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    by_span: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        name = _covering(host, s, e)
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
    idle_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / 1e9, "window_s": (hi - lo) / 1e9,
            "modules": mods,
            "device_ops": [[n[:OP_NAME_CHARS], s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps]}


def reduce_trace(trace_dir: str, host_span_names: Iterable[str]) -> Dict:
    """Whole file -> one summary: busy/window averaged over the device
    planes that ran anything, modules and breakdown of the busiest."""
    data = load(find_xplane(trace_dir), host_span_names)
    per_dev = [reduce_events(d["ops"], d["modules"], data["host"])
               for d in data["devices"].values()]
    per_dev = [r for r in per_dev if r["busy_s"] > 0]
    if not per_dev:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                "device_ops": [], "idle_gaps": [], "devices": 0}
    lead = max(per_dev, key=lambda r: r["busy_s"])
    return {"busy_s": sum(r["busy_s"] for r in per_dev) / len(per_dev),
            "window_s": sum(r["window_s"] for r in per_dev) / len(per_dev),
            "modules": lead["modules"], "device_ops": lead["device_ops"],
            "idle_gaps": lead["idle_gaps"], "devices": len(per_dev)}
