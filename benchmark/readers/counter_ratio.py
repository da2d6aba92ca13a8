"""A ratio of the window's counters: ``num`` over the product of ``den``,
times ``scale``. Nothing to read (a counter missing, a zero divisor) gives
nothing."""

from typing import Dict, List, Optional


def read(ctx: Dict, num: str, den: List[str], scale: float = 1.0
         ) -> Optional[float]:
    c = ctx["counters"]
    if num not in c or any(d not in c for d in den):
        return None
    bottom = 1.0
    for d in den:
        bottom *= float(c[d])
    return scale * float(c[num]) / bottom if bottom else None
