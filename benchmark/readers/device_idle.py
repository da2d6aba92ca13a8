"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced span), in percent."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    trace = ctx["trace"]
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
