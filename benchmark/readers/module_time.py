"""Device time of one compiled program, from the trace: the summed duration
of the XLA module whose name holds ``module`` over the traced stretch, per
run of it, divided by the counter ``per`` where given (the steps fused into
one run), in milliseconds."""

from typing import Dict, Optional


def read(ctx: Dict, module: str, per: Optional[str] = None
         ) -> Optional[float]:
    trace = ctx["trace"]
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items() if module in name]
    runs = sum(m["count"] for m in hits)
    if not runs:
        return None
    ms = 1e3 * sum(m["seconds"] for m in hits) / runs
    if per is not None:
        if not ctx["counters"].get(per):
            return None
        ms /= float(ctx["counters"][per])
    return ms
