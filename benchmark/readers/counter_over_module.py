"""A rate a dispatch of one compiled program reaches, as a share of a peak:
the counter ``num`` (operations or bytes the window's dispatches needed at
the least, summed by the driver) over the counter ``per`` (those
dispatches), over the program's device seconds a run (``module``'s events in
the trace), over the device's peak ``peak`` of ``benchmark/peaks.json``, in
percent. ``run.py`` hands readers the bf16 peak only, so a reader of another
peak looks the device's kind up itself."""

import json
import os
from typing import Dict, Optional


def _peak(name: str) -> Optional[float]:
    import jax

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks.get(jax.devices()[0].device_kind)
    return float(row[name]) if row and name in row else None


def read(ctx: Dict, num: str, per: str, module: str, peak: str
         ) -> Optional[float]:
    trace, c = ctx["trace"], ctx["counters"]
    if not trace or not c.get(num) or not c.get(per):
        return None
    hits = [m for name, m in trace["modules"].items() if module in name]
    runs = sum(m["count"] for m in hits)
    secs = sum(m["seconds"] for m in hits)
    top = _peak(peak)
    if not runs or not secs or not top:
        return None
    return 100.0 * (float(c[num]) / float(c[per])) / (secs / runs) / top
