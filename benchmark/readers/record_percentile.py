"""A percentile over all finished requests of ``end - start``, two stamps of
the program's per-request records, recomputed here."""

from typing import Dict, Optional

from benchmark.common import percentile


def read(ctx: Dict, end: str, start: str, q: float) -> Optional[float]:
    spans = [r[end] - r[start] for r in ctx["records"]
             if r.get("status") == "done"
             and r.get(end) is not None and r.get(start) is not None
             and r[end] == r[end] and r[start] == r[start]]
    return percentile(spans, q)
