"""A time read off the program's own spans (``fira_tpu/utils/profiling.py``):
the mean duration of the spans named ``span``, in seconds times ``scale``.

``run.py`` hands a reader counters, records and the reduced trace, not the
program's spans, so this one asks the program's recorder for its ring
in-process. Which spans count is found from the program's own roots:

- ``root``: only spans inside the LAST span of that name. The serve
  driver's window is one ``serve.run`` (its warm-up burst is an earlier
  one); a drain's is the last ``engine.run``.
- ``tail``: of that root, only its last ``ctx["window_s"]`` seconds — the
  drain driver opens its window well into the generator's life and closes
  the generator within one dispatch of the window's end.
- ``holding``: only spans that hold a span of that name (a serve round that
  dispatched holds a ``serve.step_dispatch``; an idle pass does not).
- ``inside``: ``span`` is measured per span named ``inside`` (which is what
  ``holding`` then applies to): the sum inside each, averaged over them.
- ``less``: from each span's duration, take the spans of that name inside
  it (a round less the wait for its step is the round's host work).
- ``stat``: ``mean``, or ``last`` for the newest such span on its own.

"Inside" is by time, on any thread. A program without the recorder, a ring
without the root, or no such span: nothing to read, and the metric is left
out of the line.
"""

from typing import Dict, List, Optional, Sequence, Tuple

Span = Tuple[str, float, float]          # (name, t_start, t_end)


def program_spans() -> Optional[List[Span]]:
    try:
        from fira_tpu.utils import profiling
    except ImportError:
        return None
    events = getattr(profiling, "events", None)
    if events is None:
        return None
    return [(e.name, e.t_start, e.t_end) for e in events()]


def _inside(spans: Sequence[Span], name: str, lo: float, hi: float
            ) -> List[Span]:
    return [s for s in spans if s[0] == name and s[1] >= lo and s[2] <= hi]


def measure(spans: Sequence[Span], span: str,
            window_s: Optional[float] = None, root: Optional[str] = None,
            tail: bool = False, holding: Optional[str] = None,
            inside: Optional[str] = None, less: Optional[str] = None,
            stat: str = "mean", scale: float = 1.0) -> Optional[float]:
    lo, hi = float("-inf"), float("inf")
    if root is not None:
        roots = [s for s in spans if s[0] == root]
        if not roots:
            return None
        _n, lo, hi = max(roots, key=lambda s: s[2])
        if tail:
            if not window_s:
                return None
            lo = max(lo, hi - float(window_s))
    spans = [s for s in spans if s[1] >= lo and s[2] <= hi]
    units = _inside(spans, inside or span, lo, hi)
    if holding is not None:
        units = [u for u in units if _inside(spans, holding, u[1], u[2])]
    if not units:
        return None
    if stat == "last":
        units = [max(units, key=lambda s: s[2])]
    total = 0.0
    for _n, a, b in units:
        if inside is not None:
            total += sum(e - s for _m, s, e in _inside(spans, span, a, b))
        else:
            total += b - a
        if less is not None:
            total -= sum(e - s for _m, s, e in _inside(spans, less, a, b))
    return scale * total / len(units)


def read(ctx: Dict, **args) -> Optional[float]:
    spans = program_spans()
    if not spans:
        return None
    return measure(spans, window_s=ctx.get("window_s"), **args)
