"""The whole window's share of the chip's peak: the operations the algorithm
needs for the work the window finished (``flops.py``, summed by the driver
into the counter ``flops``) over the window's seconds over the bf16 peak of
``peaks.json``, in percent."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    flops, secs = ctx["counters"].get("flops"), ctx["window_s"]
    if not flops or not secs or not ctx.get("peak_flops"):
        return None
    return 100.0 * float(flops) / float(secs) / float(ctx["peak_flops"])
