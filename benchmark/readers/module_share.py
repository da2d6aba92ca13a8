"""One compiled program's share of the device's busy time, from the trace:
the summed duration of the XLA modules whose name holds ``module`` over the
union of the intervals in which any operation ran, in percent. Says which
program sets the pace of a loop that alternates several."""

from typing import Dict, Optional


def read(ctx: Dict, module: str) -> Optional[float]:
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s"):
        return None
    secs = sum(m["seconds"] for name, m in trace["modules"].items()
               if module in name)
    return 100.0 * secs / trace["busy_s"] if secs else None
